"""The gated per-head RMSNorm between a delta-rule scan and the output
projection (Kimi-Linear's KDA layers, Qwen3-Next's Gated DeltaNet layers)
as a Pallas kernel pair under one ``jax.custom_vjp``; ``ops/layers.py``
``gated_norm`` is the caller.

For the scan's ``o`` (heads of ``d`` channels), the gate's pre-activation
and the norm's weight ``w`` [d], per head ``h``::

    n = o_h * rsqrt(mean_d(o_h^2) + eps) * w        (rounded to o's dtype
                                                     where ``round_norm``)
    y_h = n * act(gate_h (+ bias_h))                act: sigmoid | silu

Statistics, products and the activation are float32 in VMEM; ``y`` is
rounded once, to the gate's dtype, as it is written. The two families'
published arithmetic differs in three things the caller says (static):
``act``, a ``bias`` or none, and ``round_norm`` (Kimi's norm returns its
input's dtype before the product; Qwen3-Next's stays float32 to the last
cast). Between the scan's kernel and the output matmul XLA would choose
the layout of every float32 [S, H d] intermediate of this arithmetic
itself, pay copies to come back and run the matmuls on both sides in that
layout (28.9 ms of the Kimi cell's step and 15.6 of the Qwen3-Next cell's
in ``ds.mix_post``, and 10 and 20 more in the projections beside it:
``PERF.md`` section 6, PR 55); a Mosaic call fixes its operands' layouts
on both sides.

- **Operands** are read where they lie: the gate and ``y`` [B, S, H d] (a
  matmul's output, a matmul's input), ``o`` either [B, S, H, d] or, from
  ``ops/kda.py`` ``chunk_kda(by_head=True)``, the heads' stack [G, B,
  H / G, S, d] as the scan's kernel writes it, a head group at its rows
  of the one stack: a head is a 128-lane column block of ``y``
  either way, so only ``o``'s index map differs, and ``do`` is written in
  ``o``'s form (no relayout of 2 B S H d bytes behind the scan's kernel,
  in front of this call or behind its backward).
- **Grid** (heads, batch, row tiles), the row tiles innermost. A grid
  step takes one head's 128-lane column block by ``_ROWS_FWD`` /
  ``_ROWS_BWD`` rows of one sequence (rows a step pay: 0.93 ms a forward
  call at 512, 0.63 at 8192) and walks it by chunks of ``_CHUNK`` rows in
  registers; a head's mean of squares is a lane reduction of its own
  column block.
- **Backward** (``ds_gated_norm_bwd``): residuals are the pair's inputs.
  A chunk makes ``r`` (the rsqrt), ``x = o r``, ``n`` and the activation
  again and, with ``t = dn w``::

      dn = dy act(a)          (through o's dtype where ``round_norm``, as
                               the cast's own transpose rounds it)
      da = dy n act'(a);      do = r (t - x mean_d(t x))
      dw = sum_rows,heads dn x;   dbias = sum_rows da

  ``do`` and ``dgate`` are written in the operands' dtypes; ``dw`` (a row
  a head, summed by the caller) and ``dbias`` are float32 sums carried in
  one output block while a head's sequences and row tiles pass. Rows past
  ``S`` in a last partial tile are kept out of the sums.

No more VMEM than any XLA op gets (a kernel that asks for more as the last
op of a loop's body costs the loop XLA's staging of its operands: PR 48).
Each kernel is traced once a shape (``_common._bind``). On the chip ``d``
must be a multiple of 128 lanes; interpret mode (any other backend, the
tests) takes any width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _bind, _interpret, _nbytes

_LANES = 128
_SUB = 8            # rows the sums are kept high: a sublane tile
_ROWS_FWD = 8192    # rows a grid step, at most: three tiles of 2 MiB in
_ROWS_BWD = 4096    # bf16, five of 1 MiB, each in two buffers
_CHUNK = 256        # rows a pass in registers, at most
_ACTS = ("sigmoid", "silu")
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def _geometry(n: int, d: int, dtype, rows: int):
    """(rows a grid step, rows a chunk) for sequences of ``n`` rows of
    ``dtype``, at most ``rows`` a step."""
    if d % _LANES and not _interpret():
        raise ValueError(
            f"gated_norm: on the chip a head must be a multiple of "
            f"{_LANES} channels, not {d}")
    pack = _SUB * max(4 // jnp.dtype(dtype).itemsize, 1)
    tr = min(rows, -(-n // pack) * pack)
    rc = max(c for c in range(pack, min(_CHUNK, tr) + 1, pack)
             if tr % c == 0)
    return tr, rc


def _act(a, act):
    """(act(a), its derivative), float32."""
    sig = jax.nn.sigmoid(a)
    if act == "sigmoid":
        return sig, sig * (1.0 - sig)
    return a * sig, sig * (1.0 + a * (1.0 - sig))


def _chunk_inputs(o_ref, gate_ref, bias_ref, rows):
    """(a chunk of ``o``, the gate's pre-activation with its bias),
    float32."""
    f32 = jnp.float32
    a = gate_ref[rows, :].astype(f32)
    if bias_ref is not None:
        a = a + bias_ref[:]
    return o_ref[rows, :].astype(f32), a


def _normed(x, w, eps, round_to):
    """(the rsqrt, x-hat, the normed value as the product sees it)."""
    f32 = jnp.float32
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    xh = x * r
    n = xh * w
    if round_to is not None:
        n = n.astype(round_to).astype(f32)
    return r, xh, n


# ---------------------------------------------------------------- forward
def _fwd_kernel(*refs, rc, act, eps, round_to, has_bias):
    """One head's column block by one tile of rows."""
    o_ref, gate_ref, w_ref = refs[:3]
    bias_ref = refs[3] if has_bias else None
    y_ref = refs[-1]
    w = w_ref[:]

    def chunk(c, _):
        rows = pl.ds(pl.multiple_of(c * rc, rc), rc)
        x, a = _chunk_inputs(o_ref, gate_ref, bias_ref, rows)
        _, _, n = _normed(x, w, eps, round_to)
        y_ref[rows, :] = (n * _act(a, act)[0]).astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, y_ref.shape[0] // rc, chunk, 0)


def _specs(tr, d, by_head):
    """BlockSpecs of a grid step (head, batch, row tile): ``o`` (the heads'
    stack [G, B, H / G, S, d] where ``by_head`` is the heads a group, else
    [B, S, H d]); gate-like [B, S, H d]; a row a head [., H d]; the weight
    [1, d]."""
    vm = pltpu.VMEM
    wide = pl.BlockSpec((None, tr, d), lambda h, b, i: (b, i, h),
                        memory_space=vm)
    stack = wide if not by_head else pl.BlockSpec(
        (None, None, None, tr, d),
        lambda h, b, i: (h // by_head, b, h % by_head, i, 0),
        memory_space=vm)

    def row(n):
        return pl.BlockSpec((n, d), lambda h, b, i: (0, h), memory_space=vm)
    weight = pl.BlockSpec((1, d), lambda h, b, i: (0, 0), memory_space=vm)
    return stack, wide, row, weight


def _forward(o, gate, w, bias, *, act, eps, round_norm):
    """o [B, S, H d] or [G, B, H / G, S, d]; gate [B, S, H d]; w [1, d]
    and bias [1, H d] or None, float32. y [B, S, H d] in the gate's
    dtype."""
    b, n, width = gate.shape
    d = w.shape[1]
    tr, rc = _geometry(n, d, o.dtype, _ROWS_FWD)
    stack, wide, row, weight = _specs(tr, d, o.shape[2] if o.ndim == 5
                                      else 0)
    has_bias = bias is not None
    args = (o, gate, w) + ((bias,) if has_bias else ())
    out_shape = jax.ShapeDtypeStruct(gate.shape, gate.dtype)
    round_to = o.dtype if round_norm else None
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, rc=rc, act=act, eps=eps,
                          round_to=round_to, has_bias=has_bias),
        grid=(width // d, b, pl.cdiv(n, tr)),
        in_specs=[stack, wide, weight] + ([row(1)] if has_bias else []),
        out_specs=wide,
        out_shape=out_shape,
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=int(12 * gate.size), transcendentals=int(gate.size),
            bytes_accessed=int(_nbytes(*args, out_shape))),
        interpret=_interpret(),
        name="ds_gated_norm_fwd",
    )
    # the scope and the kernel's name are all a device trace shows of this
    # call (telemetry/scopes.py)
    return _bind(call, "ds.mix_post",
                 ("gated_norm_fwd", tr, rc, act, eps, round_norm), *args)[0]


# ---------------------------------------------------------------- backward
def _bwd_kernel(*refs, rc, act, eps, round_to, has_bias, n_rows):
    """One head's column block by one tile of a sequence's rows.
    ``sums_ref`` [1 | 2, d] gathers the head's ``dw`` row and, with a
    bias, ``dbias`` while its sequences and row tiles pass."""
    f32 = jnp.float32
    o_ref, gate_ref, w_ref = refs[:3]
    bias_ref = refs[3] if has_bias else None
    dy_ref, do_ref, dgate_ref, sums_ref = refs[-4:]
    tr, d = dy_ref.shape
    w = w_ref[:]
    tile = pl.program_id(2)
    ragged = n_rows % tr != 0

    @pl.when((tile == 0) & (pl.program_id(1) == 0))
    def _():
        sums_ref[:] = jnp.zeros(sums_ref.shape, f32)

    def chunk(c, sums):
        rows = pl.ds(pl.multiple_of(c * rc, rc), rc)
        x, a = _chunk_inputs(o_ref, gate_ref, bias_ref, rows)
        dy = dy_ref[rows, :].astype(f32)
        r, xh, n = _normed(x, w, eps, round_to)
        s, ds = _act(a, act)
        da = dy * n * ds
        dn = dy * s
        if round_to is not None:
            dn = dn.astype(round_to).astype(f32)
        t = dn * w
        dx = r * (t - xh * jnp.mean(t * xh, axis=-1, keepdims=True))
        do_ref[rows, :] = dx.astype(do_ref.dtype)
        dgate_ref[rows, :] = da.astype(dgate_ref.dtype)
        parts = (dn * xh,) + ((da,) if has_bias else ())
        if ragged:      # a last tile's rows past the end hold anything
            live = (tile * tr + c * rc + jax.lax.broadcasted_iota(
                jnp.int32, (rc, 1), 0)) < n_rows
            parts = tuple(jnp.where(live, v, 0.0) for v in parts)
        # a chunk's sums over its rows, kept a sublane tile high
        fold = lambda v: v.reshape(rc // _SUB, _SUB, d).sum(  # noqa: E731
            axis=0)
        return tuple(acc + fold(v) for acc, v in zip(sums, parts))

    zeros = (jnp.zeros((_SUB, d), f32),) * (2 if has_bias else 1)
    sums = jax.lax.fori_loop(0, tr // rc, chunk, zeros)
    sums_ref[:] += jnp.concatenate(
        [jnp.sum(v, axis=0, keepdims=True) for v in sums], axis=0)


def _backward(o, gate, w, bias, dy, *, act, eps, round_norm):
    """(do like ``o``, dgate like ``gate``, sums [1 | 2, H d] float32: a
    head's ``dw`` in its own columns over, with a bias, ``dbias``)."""
    b, n, width = gate.shape
    d = w.shape[1]
    tr, rc = _geometry(n, d, o.dtype, _ROWS_BWD)
    stack, wide, row, weight = _specs(tr, d, o.shape[2] if o.ndim == 5
                                      else 0)
    has_bias = bias is not None
    args = (o, gate, w) + ((bias,) if has_bias else ()) + (dy,)
    n_sums = 2 if has_bias else 1
    out_shape = [jax.ShapeDtypeStruct(o.shape, o.dtype),
                 jax.ShapeDtypeStruct(gate.shape, gate.dtype),
                 jax.ShapeDtypeStruct((n_sums, width), jnp.float32)]
    round_to = o.dtype if round_norm else None
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, rc=rc, act=act, eps=eps,
                          round_to=round_to, has_bias=has_bias, n_rows=n),
        grid=(width // d, b, pl.cdiv(n, tr)),
        in_specs=[stack, wide, weight] + ([row(1)] if has_bias else [])
        + [wide],
        out_specs=[stack, wide, row(n_sums)],
        out_shape=out_shape,
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=int(40 * gate.size), transcendentals=int(gate.size),
            bytes_accessed=int(_nbytes(*args, *out_shape))),
        interpret=_interpret(),
        name="ds_gated_norm_bwd",
    )
    return _bind(call, "ds.mix_post",
                 ("gated_norm_bwd", tr, rc, act, eps, round_norm, n), *args)


# ---------------------------------------------------------------- public
def _operands(o, gate, w, bias):
    """The kernels' views: ``o`` [B, S, H d] or the heads' stack as it is,
    ``w`` [1, d] and the bias [1, H d] float32."""
    f32 = jnp.float32
    if o.ndim == 4:
        o = o.reshape(gate.shape)
    return (o, gate, w.astype(f32).reshape(1, -1),
            None if bias is None else bias.astype(f32).reshape(1, -1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _gated_norm(o, gate, w, bias, act, eps, round_norm):
    return _gated_norm_fwd(o, gate, w, bias, act, eps, round_norm)[0]


def _gated_norm_fwd(o, gate, w, bias, act, eps, round_norm):
    y = _forward(*_operands(o, gate, w, bias), act=act, eps=eps,
                 round_norm=round_norm)
    return y, (o, gate, w, bias)


def _gated_norm_bwd(act, eps, round_norm, inputs, dy):
    o, gate, w, bias = inputs
    d = o.shape[-1]
    # _bind opens ds.mix_post here too: a custom_vjp's backward function is
    # traced outside the scope its forward was called under
    do, dgate, sums = _backward(
        *_operands(o, gate, w, bias), dy.astype(gate.dtype), act=act,
        eps=eps, round_norm=round_norm)
    dw = sums[0].reshape(-1, d).sum(axis=0).astype(w.dtype)
    dbias = None if bias is None else sums[1].astype(bias.dtype)
    return do.reshape(o.shape), dgate, dw, dbias


_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


def gated_norm(o, gate, w, bias=None, *, act: str, eps: float = 1e-6,
               round_norm: bool = False):
    """``rmsnorm_d(o) w * act(gate (+ bias))`` a head (the module
    docstring). o [B, S, H, d] or the heads' stack [G, B, H / G, S, d];
    gate [B, S, H d]; w [d]; bias [H d] or None; ``act`` ``sigmoid`` or
    ``silu``; ``round_norm``: the normed value passes through ``o``'s
    dtype before the product. Returns [B, S, H d] in the gate's dtype."""
    if act not in _ACTS:
        raise ValueError(f"gated_norm: act {act!r} is none of {_ACTS}")
    b, s, width = gate.shape
    d = o.shape[-1]
    heads = width // d
    if (width % d or w.shape != (d,)
            or (bias is not None and bias.shape != (width,))
            or not (o.shape == (b, s, heads, d) or (
                o.ndim == 5 and heads % o.shape[0] == 0 and o.shape == (
                    o.shape[0], b, heads // o.shape[0], s, d)))):
        raise ValueError(
            f"gated_norm: o {o.shape} is neither [B, S, H, d] nor the "
            f"heads' stack [G, B, H / G, S, d] of gate {gate.shape}, w "
            f"{w.shape}" + ("" if bias is None else f", bias {bias.shape}"))
    return _gated_norm(o, gate, w, bias, act, float(eps), bool(round_norm))

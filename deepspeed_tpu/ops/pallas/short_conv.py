"""The short convolution of the recurrent mixers (KDA's q, k, v; Mamba-2's
xBC) with what follows it elementwise, as a Pallas kernel pair under one
``jax.custom_vjp``; ``ops/layers.py`` ``short_conv`` is the caller. LFM2's
gated form (two linear gates round the taps, no activation) is a second
pair on the same geometry, taps and carried rows, at the end of the file;
``ops/layers.py`` ``gated_short_conv`` is its caller.

Per channel ``c`` with ``n`` taps, zeros before the start::

    u_t = bias_c + sum_i w[i, c] x[t - (n - 1) + i, c]
    a_t = silu(u_t)
    y_t = a_t * rsqrt(sum_head(a_t^2) + 1e-6) * norm_scale     (norm_width)

``sum_head`` runs over each run of ``norm_width`` channels (a head); with
no ``norm_width`` ``y = a``. Everything is float32 in VMEM and rounded
once, to ``x``'s dtype, as it is written.

- **Forward** (``ds_short_conv_fwd``): grid (channel blocks, batch,
  sequence blocks), the sequence blocks in order. A grid step reads a
  block of ``x`` in its own [B, S, C] layout, 128 lanes (whole heads) by
  up to ``_SEQ_BLOCK`` rows, and takes it by chunks of ``_CHUNK`` rows: a chunk with the 8 rows before it is rolled
  along the rows once a tap. The 8 rows before a block are carried in
  VMEM across the sequence axis (zeros at its start).
- **Backward** (``ds_short_conv_bwd``): residuals ``x``, ``w``, ``bias``.
  The sequence blocks last to first, and a block's row chunks last to
  first. A chunk rebuilds ``u``, ``a`` and the norm from ``x`` (the rows
  before a block arrive through a second, ``_PACK``-row BlockSpec of
  ``x``), and::

      da = norm_scale r (dy - a r^2 sum_head(dy a))      r the rsqrt
      du = da s (1 + u (1 - s))                          s = sigmoid(u)
      dx_t = sum_i w[i] du[t + (n - 1) - i]
      dw[i] = sum_t du_t x[t - (n - 1) + i];   dbias = sum_t du_t

  The first 8 rows of a block's ``du`` are carried in VMEM to the block
  before (zeros past the end). ``dw`` [n, C] and ``dbias`` [1, C] are
  summed in float32 in one output block, which stays in VMEM while a
  channel block's batch rows and sequence blocks pass.

**A head's sum is the MXU's**: the squares (two bf16 pieces of them, three
for a float32 input) times a block-diagonal matrix of ones gives every
lane its head's sum, where a lane reduction and a lane broadcast a row
would hold the kernels to the XLU (``ops/pallas/ssd.py``).

The taps, the bias and ``norm_scale`` reach the kernels as rows of one
float32 [n + 2, C] operand, so a layer's q and k (which differ by the
scale alone) are one kernel. Each kernel is traced once a shape
(``_common._bind``). ``norm_width`` must divide a block's lanes; on the chip
``C`` must be a multiple of 128, interpret mode (any other backend, the
tests) takes any width as one block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _bind, _dot, _interpret, _nbytes, _pieces

_LANES = 128
_ROWS = 8           # rows carried from block to block: a sublane tile
_SEQ_BLOCK = 8192   # rows a grid step, at most
_CHUNK = 256        # rows a pass in registers, at most
_EPS = 1e-6
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def _pack(dtype) -> int:
    """Rows a tile of ``dtype``: what a block of rows starts and ends on."""
    return _ROWS * max(4 // jnp.dtype(dtype).itemsize, 1)


def _geometry(s: int, c: int, dtype, norm_width, seq_block=None):
    """(rows a block, channels a block, rows a chunk) from the shape. A
    block of channels is the MXU's tile of 128 lanes (whole heads); a
    block of the sequence the largest under ``seq_block`` (``_SEQ_BLOCK``
    where none is given) that divides it, taken by chunks of ``_CHUNK``
    rows."""
    pack = _pack(dtype)
    if s % pack:
        raise ValueError(
            f"short_conv: a sequence of {s} is not a multiple of {pack} "
            f"rows (a tile of {jnp.dtype(dtype).name})")
    if norm_width is not None and c % norm_width:
        raise ValueError(
            f"short_conv: {c} channels are not whole heads of {norm_width}")
    if c % _LANES and not _interpret():
        raise ValueError(
            f"short_conv: on the chip the channels must be a multiple of "
            f"{_LANES}, not {c}")
    cb = c if c % _LANES else _LANES
    if norm_width is not None and cb % norm_width:
        raise ValueError(
            f"short_conv: norm_width {norm_width} does not divide the "
            f"{cb} lanes of a block")
    sb = max(d for d in range(pack, min(s, seq_block or _SEQ_BLOCK) + 1,
                              pack) if s % d == 0)
    rc = max(d for d in range(pack, max(_CHUNK, pack) + 1, pack)
             if sb % d == 0)
    return sb, cb, rc


def _n_pieces(dtype) -> int:
    """bf16 pieces of a float32 that a head's sum is taken from: what the
    output's one rounding to ``dtype`` can show."""
    return 2 if dtype == jnp.bfloat16 else 3


def _head_sums(v, norm_width, pieces):
    """Each head's sum of ``v`` [rows, cb] (float32) on every lane of the
    head: the leading bf16 ``pieces`` of ``v`` times ones."""
    rows, cb = v.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (cb, cb), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (cb, cb), 1)
    ones = jnp.where(row // norm_width == col // norm_width, 1.0,
                     0.0).astype(jnp.bfloat16)
    parts = _pieces(v, jnp.bfloat16)[:pieces]
    got = _dot(jnp.concatenate(parts, axis=0), ones)
    return sum(got[p * rows:(p + 1) * rows] for p in range(pieces))


def _taps(before, cur, w, bias):
    """(the chunk as each tap sees it, ``u``): ``cur`` [rc, cb] with the
    ``_ROWS`` rows ``before`` it, float32."""
    n, rc = w.shape[0], cur.shape[0]
    ext = jnp.concatenate([before, cur], axis=0)
    seen = [cur if i == n - 1 else
            pltpu.roll(ext, n - 1 - i, 0)[_ROWS:_ROWS + rc]
            for i in range(n)]
    u = bias
    for i in range(n):
        u = u + w[i:i + 1] * seen[i]
    return seen, u


def _silu(u):
    """(silu(u), sigmoid(u)) through one tanh."""
    s = 0.5 + 0.5 * jnp.tanh(0.5 * u)
    return u * s, s


def _rows(rows_ref):
    """(taps [n, cb], bias [1, cb], the norm's scale [1, cb]) of the
    channel block: the rows of ``_operands``."""
    n = rows_ref.shape[0] - 2
    return rows_ref[:n], rows_ref[n:n + 1], rows_ref[n + 1:]


# ---------------------------------------------------------------- forward
def _fwd_kernel(x_ref, rows_ref, y_ref, tail_ref, *, rc, norm_width,
                pieces):
    """One block of the sequence by one block of channels. ``tail_ref``
    [8, cb] carries the block's last rows to the next."""
    f32 = jnp.float32
    w, bias, scale = _rows(rows_ref)

    @pl.when(pl.program_id(2) == 0)
    def _():
        tail_ref[:] = jnp.zeros(tail_ref.shape, f32)

    def chunk(r, before):
        rows = pl.ds(pl.multiple_of(r * rc, rc), rc)
        cur = x_ref[0, rows, :].astype(f32)
        _, u = _taps(before, cur, w, bias)
        a, _ = _silu(u)
        if norm_width is not None:
            a = a * (jax.lax.rsqrt(_head_sums(a * a, norm_width, pieces)
                                   + _EPS) * scale)
        y_ref[0, rows, :] = a.astype(y_ref.dtype)
        return cur[rc - _ROWS:]

    tail_ref[:] = jax.lax.fori_loop(0, x_ref.shape[1] // rc, chunk,
                                    tail_ref[:])


def _specs(geo, *, block_of):
    """BlockSpecs of a grid step (channel block, batch, sequence block):
    x-like [B, S, C] and rows a channel [., C]. ``block_of`` maps the
    grid's sequence index to the block."""
    sb, cb, _ = geo
    vm = pltpu.VMEM
    wide = pl.BlockSpec((1, sb, cb), lambda j, i, l: (i, block_of(l), j),
                        memory_space=vm)

    def rows(n):
        return pl.BlockSpec((n, cb), lambda j, i, l: (0, j), memory_space=vm)
    return wide, rows


def _forward(x, rows, norm_width):
    """x [B, S, C]; ``rows`` [n + 2, C] float32 (``_operands``). y
    [B, S, C] in ``x``'s dtype."""
    b, s, c = x.shape
    n = rows.shape[0] - 2
    geo = sb, cb, rc = _geometry(s, c, x.dtype, norm_width)
    wide, row = _specs(geo, block_of=lambda l: l)
    pieces = _n_pieces(x.dtype)
    out_shape = jax.ShapeDtypeStruct(x.shape, x.dtype)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, rc=rc, norm_width=norm_width,
                          pieces=pieces),
        grid=(c // cb, b, s // sb),
        in_specs=[wide, row(n + 2)],
        out_specs=wide,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((_ROWS, cb), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=int(x.size * (2 * n + 8 + (
                0 if norm_width is None else 4 * pieces * cb))),
            transcendentals=int(x.size),
            bytes_accessed=int(_nbytes(x, rows, out_shape))),
        interpret=_interpret(),
        name="ds_short_conv_fwd",
    )
    # the scope and the kernel's name are all a device trace shows of this
    # call (telemetry/scopes.py)
    return _bind(call, "ds.conv", ("conv_fwd", geo, norm_width), x, rows)[0]


# ---------------------------------------------------------------- backward
def _bwd_kernel(x_ref, halo_ref, rows_ref, dy_ref, dx_ref, drows_ref,
                head_ref, *, rc, norm_width, pieces):
    """One block of the sequence by one block of channels, the sequence
    blocks arriving last to first. ``halo_ref`` ends with the 8 rows of
    ``x`` before the block; ``head_ref`` [8, cb] carries the first rows of
    the block's ``du`` to the block before; ``drows_ref`` [n + 1, cb]
    gathers ``dw`` and ``dbias``."""
    f32 = jnp.float32
    sb, cb = x_ref.shape[1:]
    w, bias, scale = _rows(rows_ref)
    n = w.shape[0]
    pack = halo_ref.shape[1]
    blocks, chunks = pl.num_programs(2), sb // rc
    last_first = pl.program_id(2)

    @pl.when(last_first == 0)
    def _():
        head_ref[:] = jnp.zeros(head_ref.shape, f32)

    @pl.when((last_first == 0) & (pl.program_id(1) == 0))
    def _():
        drows_ref[:] = jnp.zeros(drows_ref.shape, f32)

    start = jnp.where(last_first == blocks - 1, 0.0,
                      halo_ref[0, pack - _ROWS:, :].astype(f32))

    def chunk(k, carry):
        after, sums = carry
        r = chunks - 1 - k
        rows = pl.ds(pl.multiple_of(r * rc, rc), rc)
        cur = x_ref[0, rows, :].astype(f32)
        back = pl.ds(pl.multiple_of(jnp.maximum(r * rc - pack, 0), pack),
                     pack)
        before = jnp.where(r == 0, start,
                           x_ref[0, back, :].astype(f32)[pack - _ROWS:])
        seen, u = _taps(before, cur, w, bias)
        a, sig = _silu(u)
        da = dy_ref[0, rows, :].astype(f32)
        if norm_width is not None:
            inv = jax.lax.rsqrt(_head_sums(a * a, norm_width, pieces) + _EPS)
            da = (da - a * (inv * inv) * _head_sums(
                da * a, norm_width, pieces)) * (inv * scale)
        du = da * (sig * (1.0 + u * (1.0 - sig)))
        ext = jnp.concatenate([du, after], axis=0)
        dx = w[n - 1:n] * du
        for i in range(n - 1):
            dx = dx + w[i:i + 1] * pltpu.roll(
                ext, rc + _ROWS - (n - 1 - i), 0)[:rc]
        dx_ref[0, rows, :] = dx.astype(dx_ref.dtype)
        # a chunk's sums over its rows, kept a sublane tile high
        fold = lambda v: v.reshape(rc // _ROWS, _ROWS, cb).sum(  # noqa: E731
            axis=0)
        return du[:_ROWS], tuple(acc + fold(du * v)
                                 for acc, v in zip(sums, (*seen, 1.0)))

    zeros = (jnp.zeros((_ROWS, cb), f32),) * (n + 1)
    head_ref[:], sums = jax.lax.fori_loop(0, chunks, chunk,
                                          (head_ref[:], zeros))
    drows_ref[:] += jnp.concatenate(
        [jnp.sum(v, axis=0, keepdims=True) for v in sums], axis=0)


def _backward(x, rows, dy, norm_width):
    """dx [B, S, C] in ``x``'s dtype; ``dw`` over ``dbias`` [n + 1, C]
    float32."""
    b, s, c = x.shape
    n = rows.shape[0] - 2
    geo = sb, cb, rc = _geometry(s, c, x.dtype, norm_width)
    blocks = s // sb
    wide, row = _specs(geo, block_of=lambda l: blocks - 1 - l)
    pack = _pack(x.dtype)
    halo = pl.BlockSpec(
        (1, pack, cb),
        lambda j, i, l: (i, jnp.maximum((blocks - 1 - l) * (sb // pack) - 1,
                                        0), j),
        memory_space=pltpu.VMEM)
    pieces = _n_pieces(x.dtype)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype),
                 jax.ShapeDtypeStruct((n + 1, c), jnp.float32)]
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, rc=rc, norm_width=norm_width,
                          pieces=pieces),
        grid=(c // cb, b, blocks),
        in_specs=[wide, halo, row(n + 2), wide],
        out_specs=[wide, row(n + 1)],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((_ROWS, cb), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=int(x.size * (6 * n + 24 + (
                0 if norm_width is None else 8 * pieces * cb))),
            transcendentals=int(x.size),
            bytes_accessed=int(_nbytes(x, rows, dy, *out_shape))),
        interpret=_interpret(),
        name="ds_short_conv_bwd",
    )
    return _bind(call, "ds.conv", ("conv_bwd", geo, norm_width),
                 x, x, rows, dy)


# ---------------------------------------------------------------- public
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _short_conv(x, w, bias, norm_width, norm_scale):
    return _short_conv_fwd(x, w, bias, norm_width, norm_scale)[0]


def _operands(w, bias, norm_scale):
    """[n + 2, C] float32: the taps, the bias and the norm's scale a
    channel (a row, so that q's and k's calls are one kernel)."""
    f32 = jnp.float32
    return jnp.concatenate(
        [w.astype(f32), bias.astype(f32).reshape(1, -1),
         jnp.full((1, w.shape[1]), norm_scale, f32)], axis=0)


def _short_conv_fwd(x, w, bias, norm_width, norm_scale):
    y = _forward(x, _operands(w, bias, norm_scale), norm_width)
    return y, (x, w, bias)


def _short_conv_bwd(norm_width, norm_scale, inputs, dy):
    x, w, bias = inputs
    n = w.shape[0]
    # _bind opens ds.conv here too: a custom_vjp's backward function is
    # traced outside the scope its forward was called under
    dx, drows = _backward(x, _operands(w, bias, norm_scale),
                          dy.astype(x.dtype), norm_width)
    return (dx, drows[:n].astype(w.dtype),
            drows[n].reshape(bias.shape).astype(bias.dtype))


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


def short_conv(x, w, bias=None, *, norm_width: int | None = None,
               norm_scale: float = 1.0):
    """The causal depthwise convolution, SiLU and, where ``norm_width`` is
    given, the l2 norm over each run of ``norm_width`` channels times
    ``norm_scale`` (the module docstring). x [B, S, C]; w [n, C]; bias [C]
    or None. Returns [B, S, C] in ``x``'s dtype."""
    if w.shape[0] - 1 > _ROWS:
        raise ValueError(
            f"short_conv: {w.shape[0]} taps reach past the {_ROWS} rows "
            f"carried from block to block")
    if w.shape[1:] != x.shape[2:]:
        raise ValueError(
            f"short_conv: taps {w.shape} for {x.shape[2]} channels")
    if bias is None:
        bias = jnp.zeros(x.shape[2:], w.dtype)
    return _short_conv(x, w, bias, norm_width, float(norm_scale))


# ------------------------------------------------------- the gated form
# LFM2's operator: two linear gates round the taps, no activation, no bias
#
#     [B | Cg | X] = bcx          three equal column runs of [B, S, 3 C]
#     u = B * X;   c_t = sum_i w[i] u[t - (n - 1) + i];   y = Cg * c
#
# - **Forward** (``ds_gated_conv_fwd``): the grid and the chunks of the
#   plain form; ``bcx`` arrives three times, a BlockSpec a column run, so
#   each run is read where the projection wrote it (no split, no copy).
#   The 8 rows of ``u`` before a block are carried in VMEM.
# - **Backward** (``ds_gated_conv_bwd``): residuals ``bcx``, ``w``. Blocks
#   and chunks last to first; a chunk rebuilds ``u`` and ``c`` and
#
#       dCg = dy * c;   dc = dy * Cg;   du_t = sum_i w[i] dc[t + (n-1) - i]
#       dB = du * X;    dX = du * B;    dw[i] = sum_t dc_t u[t - (n-1) + i]
#
#   The cotangent of ``bcx`` is ONE [B, S, 3 C] array in the projection's
#   own layout: the grid's last axis walks its three column runs. The
#   first visit of a block does the work, writes ``dB`` and keeps ``dCg``
#   and ``dX`` in VMEM; the two that follow only store them (the operands'
#   blocks do not move, so nothing is fetched again).
_GATED_SEQ_BLOCK = 2048     # rows a grid step: five blocks in flight and
#                             two kept, 0.5 MiB each in bf16
_GATED_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def _gated_geometry(bcx, w):
    b, s, c3 = bcx.shape
    n, c = w.shape
    if c3 != 3 * c:
        raise ValueError(
            f"gated_short_conv: {c3} columns are not [B | Cg | X] of "
            f"{c} channels each")
    if n - 1 > _ROWS:
        raise ValueError(
            f"gated_short_conv: {n} taps reach past the {_ROWS} rows "
            f"carried from block to block")
    return _geometry(s, c, bcx.dtype, None, _GATED_SEQ_BLOCK)


def _gated_specs(geo, nc, *, block_of):
    """BlockSpecs of a grid step (channel block, batch, sequence block, and
    in the backward the column run written): ``run(r)`` the block of
    column run ``r`` of a [B, S, 3 C] array (``r`` None: the run the
    grid's last axis names), ``halo(r, pack)`` the ``pack`` rows before
    it, ``taps(n)`` rows a channel."""
    sb, cb, _ = geo
    vm = pltpu.VMEM

    def run(r):
        return pl.BlockSpec(
            (1, sb, cb), lambda j, i, l, *at: (
                i, block_of(l), j + (at[0] if r is None else r) * nc),
            memory_space=vm)

    def halo(r, pack):
        return pl.BlockSpec(
            (1, pack, cb), lambda j, i, l, *at: (
                i, jnp.maximum(block_of(l) * (sb // pack) - 1, 0),
                j + r * nc), memory_space=vm)

    def taps(n):
        return pl.BlockSpec((n, cb), lambda j, i, l, *at: (0, j),
                            memory_space=vm)
    return run, halo, taps


def _gated_fwd_kernel(b_ref, cg_ref, x_ref, w_ref, y_ref, tail_ref, *, rc):
    """One block of the sequence by one block of channels. ``tail_ref``
    [8, cb] carries the last rows of the block's ``u`` to the next."""
    f32 = jnp.float32
    w = w_ref[:]

    @pl.when(pl.program_id(2) == 0)
    def _():
        tail_ref[:] = jnp.zeros(tail_ref.shape, f32)

    def chunk(r, before):
        rows = pl.ds(pl.multiple_of(r * rc, rc), rc)
        u = b_ref[0, rows, :].astype(f32) * x_ref[0, rows, :].astype(f32)
        _, c = _taps(before, u, w, 0.0)
        y_ref[0, rows, :] = (cg_ref[0, rows, :].astype(f32)
                             * c).astype(y_ref.dtype)
        return u[rc - _ROWS:]

    tail_ref[:] = jax.lax.fori_loop(0, x_ref.shape[1] // rc, chunk,
                                    tail_ref[:])


def _gated_forward(bcx, w):
    """bcx [B, S, 3 C]; w [n, C] float32. y [B, S, C] in ``bcx``'s dtype."""
    b, s, _ = bcx.shape
    n, c = w.shape
    geo = sb, cb, rc = _gated_geometry(bcx, w)
    run, _, taps = _gated_specs(geo, c // cb, block_of=lambda l: l)
    out_shape = jax.ShapeDtypeStruct((b, s, c), bcx.dtype)
    call = pl.pallas_call(
        functools.partial(_gated_fwd_kernel, rc=rc),
        grid=(c // cb, b, s // sb),
        in_specs=[run(0), run(1), run(2), taps(n)],
        out_specs=run(0),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((_ROWS, cb), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=int(b * s * c * (2 * n + 2)), transcendentals=0,
            bytes_accessed=int(_nbytes(bcx, w, out_shape))),
        interpret=_interpret(),
        name="ds_gated_conv_fwd",
    )
    return _bind(call, "ds.gconv_mix", ("gated_fwd", geo),
                 bcx, bcx, bcx, w)[0]


def _gated_bwd_kernel(b_ref, cg_ref, x_ref, bh_ref, xh_ref, w_ref, dy_ref,
                      d_ref, dw_ref, head_ref, keep_ref, *, rc):
    """One block of the sequence by one block of channels, three visits
    (the grid's last axis: the column run of ``d_ref``), the sequence
    blocks arriving last to first. ``bh_ref`` and ``xh_ref`` end with the
    8 rows of ``B`` and ``X`` before the block; ``head_ref`` [8, cb]
    carries the first rows of the block's ``dc`` to the block before;
    ``keep_ref`` [2, sb, cb] holds ``dCg`` and ``dX`` from the first visit
    to the two that store them; ``dw_ref`` [n, cb] gathers ``dw``."""
    f32 = jnp.float32
    sb, cb = x_ref.shape[1:]
    n = w_ref.shape[0]
    pack = xh_ref.shape[1]
    blocks, chunks = pl.num_programs(2), sb // rc
    last_first, visit = pl.program_id(2), pl.program_id(3)

    @pl.when((visit == 0) & (last_first == 0))
    def _():
        head_ref[:] = jnp.zeros(head_ref.shape, f32)

    @pl.when((visit == 0) & (last_first == 0) & (pl.program_id(1) == 0))
    def _():
        dw_ref[:] = jnp.zeros(dw_ref.shape, f32)

    @pl.when(visit == 0)
    def _():
        w = w_ref[:]
        start = jnp.where(
            last_first == blocks - 1, 0.0,
            bh_ref[0, pack - _ROWS:, :].astype(f32)
            * xh_ref[0, pack - _ROWS:, :].astype(f32))

        def chunk(k, carry):
            after, sums = carry
            r = chunks - 1 - k
            rows = pl.ds(pl.multiple_of(r * rc, rc), rc)
            gate_b = b_ref[0, rows, :].astype(f32)
            x = x_ref[0, rows, :].astype(f32)
            back = pl.ds(pl.multiple_of(jnp.maximum(r * rc - pack, 0),
                                        pack), pack)
            before = jnp.where(
                r == 0, start,
                (b_ref[0, back, :].astype(f32)
                 * x_ref[0, back, :].astype(f32))[pack - _ROWS:])
            seen, c = _taps(before, gate_b * x, w, 0.0)
            dy = dy_ref[0, rows, :].astype(f32)
            dc = dy * cg_ref[0, rows, :].astype(f32)
            ext = jnp.concatenate([dc, after], axis=0)
            du = w[n - 1:n] * dc
            for i in range(n - 1):
                du = du + w[i:i + 1] * pltpu.roll(
                    ext, rc + _ROWS - (n - 1 - i), 0)[:rc]
            d_ref[0, rows, :] = (du * x).astype(d_ref.dtype)
            keep_ref[0, rows, :] = (dy * c).astype(keep_ref.dtype)
            keep_ref[1, rows, :] = (du * gate_b).astype(keep_ref.dtype)
            # a chunk's sums over its rows, kept a sublane tile high
            fold = lambda v: v.reshape(  # noqa: E731
                rc // _ROWS, _ROWS, cb).sum(axis=0)
            return dc[:_ROWS], tuple(acc + fold(dc * v)
                                     for acc, v in zip(sums, seen))

        zeros = (jnp.zeros((_ROWS, cb), f32),) * n
        head_ref[:], sums = jax.lax.fori_loop(0, chunks, chunk,
                                              (head_ref[:], zeros))
        dw_ref[:] += jnp.concatenate(
            [jnp.sum(v, axis=0, keepdims=True) for v in sums], axis=0)

    @pl.when(visit > 0)
    def _():
        d_ref[0] = keep_ref[visit - 1]


def _gated_backward(bcx, w, dy):
    """d bcx [B, S, 3 C] in ``bcx``'s dtype; dw [n, C] float32."""
    b, s, _ = bcx.shape
    n, c = w.shape
    geo = sb, cb, rc = _gated_geometry(bcx, w)
    blocks, nc = s // sb, c // cb
    run, halo, taps = _gated_specs(geo, nc,
                                   block_of=lambda l: blocks - 1 - l)
    pack = _pack(bcx.dtype)
    out_shape = [jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                 jax.ShapeDtypeStruct((n, c), jnp.float32)]
    call = pl.pallas_call(
        functools.partial(_gated_bwd_kernel, rc=rc),
        grid=(nc, b, blocks, 3),
        in_specs=[run(0), run(1), run(2), halo(0, pack), halo(2, pack),
                  taps(n), run(0)],
        out_specs=[run(None), taps(n)],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((_ROWS, cb), jnp.float32),
                        pltpu.VMEM((2, sb, cb), bcx.dtype)],
        compiler_params=_GATED_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=int(b * s * c * (6 * n + 8)), transcendentals=0,
            bytes_accessed=int(_nbytes(bcx, w, dy, *out_shape))),
        interpret=_interpret(),
        name="ds_gated_conv_bwd",
    )
    return _bind(call, "ds.gconv_mix", ("gated_bwd", geo),
                 bcx, bcx, bcx, bcx, bcx, w, dy)


@jax.custom_vjp
def gated_short_conv(bcx, w):
    """``Cg * conv(B * X)`` of ``bcx`` = ``[B | Cg | X]`` [B, S, 3 C] with
    the taps ``w`` [n, C], causal and depthwise, zeros before the start,
    no bias and no activation (the comment above). Float32 inside, rounded
    once. Returns [B, S, C] in ``bcx``'s dtype."""
    return _gated_forward(bcx, w.astype(jnp.float32))


def _gated_conv_fwd(bcx, w):
    return gated_short_conv(bcx, w), (bcx, w)


def _gated_conv_bwd(inputs, dy):
    bcx, w = inputs
    # _bind opens ds.gconv_mix here too: a custom_vjp's backward function is
    # traced outside the scope its forward was called under
    dbcx, dw = _gated_backward(bcx, w.astype(jnp.float32),
                               dy.astype(bcx.dtype))
    return dbcx, dw.astype(w.dtype)


gated_short_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)

"""The chunk recurrence of Kimi Delta Attention as a Pallas kernel pair.

``ops/kda.py`` prepares, for every chunk of ``C`` tokens at once, what
does not need the state: ``u_v = T V``, ``w = T (K e^G)``, ``q_in = q
e^G``, ``a_qk``, ``k_out = k beta e^(G_C - G)`` and ``shrink = e^(G_C)``.
What is left is serial in the chunks: with the float32 state ``S``
[dk, dv] of one head, ``S = 0`` before the first chunk::

    u  = u_v - w S
    o  = q_in S + a_qk u
    S' = Diag(shrink) S + k_out^T u

``kda_recurrence`` runs that under one ``jax.custom_vjp``:

- **Forward** (``ds_kda_fwd``): grid (batch x heads, segments of ``SEG``
  chunks); a grid step loops over its segment's chunks with the state in
  registers and carries it to the next step in a VMEM scratch, zeroed at
  segment 0. The state never reaches HBM: the forward writes ``o`` alone.
- **Backward** (``ds_kda_bwd``): first the forward kernel's second form,
  which computes no ``o`` (two of the four matmuls) and writes the state
  each segment STARTS from (a segment checkpoint, float32, ``SEG`` times
  smaller than a state history). Then the segments last to first, ``dS``
  carried in VMEM: a grid step rebuilds its segment's ``SEG`` incoming
  states and its ``u`` from the checkpoint into VMEM, then takes the
  chunks last to first::

      du = a_qk^T do + k_out dS'      da_qk = do u^T     dq_in = do S^T
      dk_out = u dS'^T                dw = -du S^T       du_v = du
      dshrink = rowsum(S * dS')
      dS = Diag(shrink) dS' + q_in^T do - w^T du

  Under a ``jax.checkpoint`` that reruns the forward rule for its
  residuals (``chunk_kda`` puts one around each group of heads) the rule's
  ``o`` is dead and the forward kernel is not run again: the rerun costs
  the preparation and the checkpoint form only.

The kernels hold the state TRANSPOSED (``St`` [dv, dk]): ``shrink`` then
scales lanes and broadcasts as the row it is stored as, ``dshrink`` is a
sum over sublanes, and ``w S`` and ``q_in S`` are one matmul against one
latched ``St`` (``jnp.dot(a, b.T)`` latches ``b`` transposed).

Matmul operands are in the operands' dtype (bf16 in training) with
float32 accumulation, as the ``lax.scan`` form this replaces had them;
the state, ``u_v``, ``shrink`` and every sum are float32. A chunk count
that is not a multiple of the segment is padded with chunks that leave
the state alone (zero operands, ``shrink`` 1).

On the chip ``dk`` and ``dv`` must be multiples of 128 and ``C`` of 16
(Mosaic's tiles); interpret mode (any other backend, the tests) takes any
shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SEG = 16        # chunks a segment: a grid step, and a checkpoint's spacing
HEADS = 4       # heads a grid step: independent chains for the MXU

_LANES = 128
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _advance(st, ub, k_out, shrink):
    """The state after a chunk: ``St`` [dv, dk] scaled by the chunk's
    ``shrink`` row, plus ``u^T k_out``."""
    return st * shrink + _dot(ub, k_out, _TN)


# ---------------------------------------------------------------- forward
def _fwd_kernel(uv_ref, w_ref, q_ref, a_ref, k_ref, sh_ref, out_ref, st_ref,
                *, heads: int, seg: int, states: bool):
    """One segment of ``heads`` heads. ``states``: ``out_ref`` is the
    segment checkpoint [heads, 1, dv, dk] and no ``o`` is made; else it is
    ``o`` [heads, seg, C, dv]."""
    dt = w_ref.dtype
    c = w_ref.shape[2]

    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[:] = jnp.zeros_like(st_ref)

    if states:
        out_ref[:, 0] = st_ref[:]

    def chunk(i, sts):
        new = []
        for h, st in enumerate(sts):
            sb = st.astype(dt)
            if states:
                u = uv_ref[h, i] - _dot(w_ref[h, i], sb, _NT)
                ub = u.astype(dt)
            else:
                # w S and q_in S against one latched St
                r = _dot(jnp.concatenate([w_ref[h, i], q_ref[h, i]], axis=0),
                         sb, _NT)
                ub = (uv_ref[h, i] - r[:c]).astype(dt)
                out_ref[h, i] = (r[c:] + _dot(a_ref[h, i], ub)
                                 ).astype(out_ref.dtype)
            new.append(_advance(st, ub, k_ref[h, i],
                                sh_ref[h, pl.ds(i, 1), :]))
        return tuple(new)

    sts = jax.lax.fori_loop(0, seg, chunk,
                            tuple(st_ref[h] for h in range(heads)))
    for h, st in enumerate(sts):
        st_ref[h] = st


def _geometry(w):
    bh, n, c, dk = w.shape
    seg = min(SEG, n)
    heads = next(h for h in range(min(HEADS, bh), 0, -1) if bh % h == 0)
    return bh, n, c, dk, seg, heads


def _check_chip_shapes(c, dk, dv):
    if not _interpret() and (dk % _LANES or dv % _LANES or c % 16):
        raise ValueError(
            f"kda kernels: on the chip the key and value widths must be "
            f"multiples of {_LANES} and the chunk of 16, not {dk}, {dv} "
            f"and {c}")


def _pad_chunks(ops, pad: int):
    """``pad`` more chunks that leave the state alone."""
    if not pad:
        return ops
    *mats, shrink = ops
    grow = lambda x, v: jnp.pad(  # noqa: E731
        x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2), constant_values=v)
    return (*(grow(x, 0) for x in mats), grow(shrink, 1))


def _specs(shapes, heads, seg):
    """A segment of ``heads`` heads of each [BH, N, ...] array."""
    return [pl.BlockSpec((heads, seg, *s[2:]),
                         lambda b, j, _r=len(s) - 2: (b, j) + (0,) * _r,
                         memory_space=pltpu.VMEM) for s in shapes]


def _forward(ops, out_dtype, *, states: bool):
    """``o`` [BH, N, C, dv] in ``out_dtype``, or with ``states`` the float32
    checkpoints [BH, N / seg, dv, dk] (of the padded chunk count)."""
    u_v, w = ops[:2]
    bh, n, c, dk, seg, heads = _geometry(w)
    dv = u_v.shape[-1]
    _check_chip_shapes(c, dk, dv)
    ops = _pad_chunks(ops, -n % seg)
    nseg = ops[0].shape[1] // seg
    if states:
        out_shape = jax.ShapeDtypeStruct((bh, nseg, dv, dk), jnp.float32)
        out_spec = pl.BlockSpec((heads, 1, dv, dk),
                                lambda b, j: (b, j, 0, 0),
                                memory_space=pltpu.VMEM)
    else:
        out_shape = jax.ShapeDtypeStruct((bh, nseg * seg, c, dv), out_dtype)
        out_spec, = _specs([out_shape.shape], heads, seg)
    mm = 2 * c * dk * dv
    flops = bh * nseg * seg * (2 * mm if states else 3 * mm + 2 * c * c * dv)
    nbytes = sum(x.size * x.dtype.itemsize for x in ops) + (
        np.prod(out_shape.shape) * jnp.dtype(out_shape.dtype).itemsize)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, seg=seg, states=states),
        grid=(bh // heads, nseg),
        in_specs=_specs([x.shape for x in ops], heads, seg),
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(flops=int(flops), transcendentals=0,
                                      bytes_accessed=int(nbytes)),
        interpret=_interpret(),
        name="ds_kda_fwd",
    )
    # the scope and the kernel's name are all a device trace shows of this
    # call (telemetry/scopes.py)
    with jax.named_scope("ds.kda_fwd"):
        out = call(*ops)
    return out if states else out[:, :n]


# ---------------------------------------------------------------- backward
def _bwd_kernel(uv_ref, w_ref, q_ref, a_ref, k_ref, sh_ref, ck_ref, do_ref,
                duv_ref, dw_ref, dq_ref, da_ref, dk_ref, dsh_ref,
                st_ref, ub_ref, ds_ref, *, heads: int, seg: int):
    """One segment of ``heads`` heads, the segments arriving last to
    first. ``st_ref`` [heads, seg, dv, dk] and ``ub_ref`` [heads, seg, C,
    dv] are rebuilt from the checkpoint; ``ds_ref`` carries ``dS``
    (transposed, float32) to the segment before."""
    dt = w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[:] = jnp.zeros_like(ds_ref)

    def rebuild(i, sts):
        new = []
        for h, st in enumerate(sts):
            st_ref[h, i] = st
            u = uv_ref[h, i] - _dot(w_ref[h, i], st.astype(dt), _NT)
            ub = u.astype(dt)
            ub_ref[h, i] = ub
            new.append(_advance(st, ub, k_ref[h, i],
                                sh_ref[h, pl.ds(i, 1), :]))
        return tuple(new)

    # the last chunk's outgoing state is the next segment's: not needed
    jax.lax.fori_loop(0, seg, rebuild,
                      tuple(ck_ref[h, 0] for h in range(heads)))

    def chunk(t, dsts):
        i = seg - 1 - t
        new = []
        for h, dst in enumerate(dsts):
            st = st_ref[h, i]
            sb, dsb = st.astype(dt), dst.astype(dt)
            ub = ub_ref[h, i]
            dob = do_ref[h, i].astype(dt)
            du = _dot(a_ref[h, i], dob, _TN) + _dot(k_ref[h, i], dsb, _NT)
            dub = du.astype(dt)
            duv_ref[h, i] = du
            da_ref[h, i] = _dot(dob, ub, _NT).astype(da_ref.dtype)
            dq_ref[h, i] = _dot(dob, sb).astype(dq_ref.dtype)
            dk_ref[h, i] = _dot(ub, dsb).astype(dk_ref.dtype)
            dw_ref[h, i] = (-_dot(dub, sb)).astype(dw_ref.dtype)
            dsh_ref[h, pl.ds(i, 1), :] = jnp.sum(st * dst, axis=0,
                                                 keepdims=True)
            new.append(dst * sh_ref[h, pl.ds(i, 1), :]
                       + _dot(dob, q_ref[h, i], _TN)
                       - _dot(dub, w_ref[h, i], _TN))
        return tuple(new)

    dsts = jax.lax.fori_loop(0, seg, chunk,
                             tuple(ds_ref[h] for h in range(heads)))
    for h, dst in enumerate(dsts):
        ds_ref[h] = dst


def _backward(ops, ck, do):
    """The six cotangents, each in its operand's shape and dtype."""
    u_v, w = ops[:2]
    bh, n, c, dk, seg, heads = _geometry(w)
    dv = u_v.shape[-1]
    pad = -n % seg
    ops = _pad_chunks(ops, pad)
    if pad:
        do = jnp.pad(do, [(0, 0), (0, pad), (0, 0), (0, 0)])
    nseg = ops[0].shape[1] // seg
    last = nseg - 1
    rev = lambda spec: pl.BlockSpec(  # noqa: E731
        spec.block_shape,
        lambda b, j, _m=spec.index_map: _m(b, last - j),
        memory_space=pltpu.VMEM)
    shapes = [x.shape for x in ops]
    ck_spec = pl.BlockSpec((heads, 1, dv, dk), lambda b, j: (b, j, 0, 0),
                           memory_space=pltpu.VMEM)
    mm = 2 * c * dk * dv
    flops = bh * nseg * seg * (8 * mm + 4 * c * c * dv)
    nbytes = (2 * sum(x.size * x.dtype.itemsize for x in ops)
              + ck.size * 4 + do.size * do.dtype.itemsize)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, seg=seg),
        grid=(bh // heads, nseg),
        in_specs=[rev(s) for s in
                  (*_specs(shapes, heads, seg), ck_spec,
                   *_specs([do.shape], heads, seg))],
        out_specs=[rev(s) for s in _specs(shapes, heads, seg)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in ops],
        scratch_shapes=[pltpu.VMEM((heads, seg, dv, dk), jnp.float32),
                        pltpu.VMEM((heads, seg, c, dv), w.dtype),
                        pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(flops=int(flops), transcendentals=0,
                                      bytes_accessed=int(nbytes)),
        interpret=_interpret(),
        name="ds_kda_bwd",
    )
    with jax.named_scope("ds.kda_bwd"):
        grads = call(*ops, ck, do)
    return tuple(x[:, :n] for x in grads)


# ---------------------------------------------------------------- public
@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _recurrence(*ops_and_dtype):
    return _recurrence_fwd(*ops_and_dtype)[0]


def _recurrence_fwd(u_v, w, q_in, a_qk, k_out, shrink, out_dtype):
    ops = (u_v, w, q_in, a_qk, k_out, shrink)
    return _forward(ops, out_dtype, states=False), ops


def _recurrence_bwd(out_dtype, ops, do):
    # opened here: a custom_vjp's backward function is traced outside the
    # scope its forward was called under
    with jax.named_scope("ds.kda_scan"):
        ck = _forward(ops, out_dtype, states=True)
        return _backward(ops, ck, do)


_recurrence.defvjp(_recurrence_fwd, _recurrence_bwd)


def kda_recurrence(u_v, w, q_in, a_qk, k_out, shrink, *, out_dtype):
    """The chunk recurrence of the module docstring. ``u_v`` [B, H, N, C,
    dv] float32; ``w``, ``q_in``, ``k_out`` [B, H, N, C, dk] and ``a_qk``
    [B, H, N, C, C] in the matmuls' dtype; ``shrink`` [B, H, N, dk]
    float32. Returns ``o`` [B, H, N, C, dv] in ``out_dtype``."""
    b, h = w.shape[:2]
    flat = lambda x: x.reshape(b * h, *x.shape[2:])  # noqa: E731
    o = _recurrence(*(flat(x) for x in (u_v, w, q_in, a_qk, k_out, shrink)),
                    jnp.dtype(out_dtype))
    return o.reshape(b, h, *o.shape[1:])

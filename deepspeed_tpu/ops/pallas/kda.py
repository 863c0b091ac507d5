"""Chunked Kimi Delta Attention as two Pallas kernel pairs: the
preparation of a chunk, and the recurrence over the chunks.

**The preparation** (``_prepare_forward`` / ``_prepare_backward``; its
backward needs its five inputs and nothing else) makes, chunk by chunk of
``C`` tokens and
head by head, what does not need the state: with ``G`` the chunk's running
sum of the log-decays ``g``, the score matrices ``a_kk`` (strictly lower)
and ``a_qk`` (lower, exact diagonal), ``T = (I + a_kk)^-1``, and from them
``u_v = T V``, ``w = T (K e^G)``, ``q_in = q e^G``, ``a_qk``, ``k_out = k
beta e^(G_C - G)`` and ``shrink = e^(G_C)``.

- **Forward** (``ds_kda_prep_fwd``): grid (batch, blocks of ``PREP_HEADS``
  heads, blocks of ``NCK`` chunks), every axis parallel. q, k, v (the
  matmuls' dtype) and g (float32) arrive through BlockSpecs of the model's
  own [B, S, H d] layout, beta as rows; a grid step loops over its chunks
  and builds everything of ``_Chunk`` in VMEM and registers. Matmul
  operands are in the inputs' dtype with float32 accumulation; ``G``, the
  decays and the inverse are float32 (products at ``HIGHEST``: six bf16
  passes). The inverse is ten dependent [C, C] products, each waiting on
  the one before. Two heads take a product TOGETHER (``_pdot``: ``[x1 |
  x2]`` [C, 2 C] against ``y1``, ``y2`` on the diagonal of a [2 C, 2 C]
  operand, a latch that fills the MXU's width and depth and half the rows
  streamed; the zero blocks add exact zeros, so a head's numbers are what
  they are alone), and the pairs of a grid step take them IN STEP
  (``_inverse_unit_lower``), which is what fills the wait. Pairing is read
  from the shape: at least two heads a grid step and ``2 C <= 128``; a
  lone head goes through the same functions as a list of one [C, C].
- **Backward** (``ds_kda_prep_bwd``): the same grid and blocks, plus the
  six cotangents ``ds_kda_bwd`` makes; rebuilds the chunk's forward, then
  ``dT = du_v V^T + dw (K e^G)^T``, ``da_kk = -T^T dT T^T`` kept strictly
  lower (its two float32 products by pairs too: ``[T1^T | T2^T]``, the pair
  transposed and its heads put side by side again, takes ``dT T^T`` from
  the right, each factor on the side it has alone, so the sums are the
  lone product's to the last bit), the score blocks' cotangents through
  the same factoring (a
  clamped factor passes no gradient), the decay products, and the running
  sum's transpose; writes dq, dk, dv, dg in the model's layout and dbeta.

**A gate a head** (Gated DeltaNet, PR 46): ``g`` [B, S, H], one log-decay a
token, reaches both kernels as rows [1, C] the way beta does, and a chunk
is a ``_HeadChunk``: the decay is ONE mask ``D_ij = exp(G_i - G_j)`` (j <=
i) on the plain ``K K^T`` and ``Q K^T`` (one [2 C, dk] x [dk, C] product),
no row blocks, no factoring, no clamp, exact at any decay; ``decay``,
``fade`` and ``beta`` ride down a column and broadcast along the lanes,
``shrink`` is the chunk's one number on every channel; the inverse, ``T
[V, K e^G]`` and their backward are the shared code, and the backward
returns ``dg`` as rows. The recurrence's kernels do not know the gate.

**Fewer key heads than value heads** (Qwen3-Next: 16 to 32; PR 53): q and
k arrive at their own head count ``Hk``, v, g and beta at ``H``; ``rep = H
/ Hk`` is read from the shapes. A grid step of ``heads`` value heads reads
``heads / rep`` key heads (q's and k's BlockSpecs are that many ``dk``
lanes of [B, S, Hk dk] at the same block index), head ``h`` slices them at
key head ``h // rep``, and the backward sums the ``rep`` heads' ``dq`` and
``dk`` in float32 before the one store into [B, S, Hk dk]: no repeated
copy of q or k exists, and no sum over pairs after the kernel. At ``rep``
1 every slice, block and store is what it was. The six operands, and so
the recurrence's kernels, are per value head either way.

**The recurrence.** What is left is serial in the chunks: with the float32
state ``S`` [dk, dv] of one head, ``S = 0`` before the first chunk::

    u  = u_v - w S
    o  = q_in S + a_qk u
    S' = Diag(shrink) S + k_out^T u

``_forward`` / ``_backward`` run that:

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

  The scan's backward rule (``ops/kda.py`` ``_scan``, one ``custom_vjp``
  over all the head groups) makes a group's six operands again and runs
  the checkpoint form: the forward kernel is not run again. Where the
  groups are more than one the rule's ``o`` is declared kept, so a
  rematted layer's backward reads it back (PR 51) and a layer runs the
  forward kernel once in each form.

**Head groups** (ISSUE 59). ``chunk_kda`` runs the heads in groups, one
after the other, so that the six operands live for one group at a time. A
group is an offset in the four calls' index maps, not a slice: ``grp``,
the group's index, is every call's one scalar-prefetch operand (a loop's
counter or a constant), the preparation's calls take the WHOLE q, k, v, g
[B, S, H d] and the whole rows of beta (and of a gate a head) and read at
the group's first head block (``_prep_specs``), ``_forward`` writes the
group's ``o`` at its rows of the groups' stack [G BH, N, C, dv] and
``_backward`` reads its ``do`` there (``_specs`` ``stack``), and
``_prepare_backward`` writes dq, dk, dv, dg, dbeta where the group's heads
lie in the whole gradients. What several groups write into is carried
from call to call and aliased to the output (``into``,
``input_output_aliases``): buffers made NOT initialised (``lax.empty``:
``AllocateBuffer`` on the chip), since every block is written by exactly
one group: nothing is zeroed and nothing is added. One group carries
nothing: it is offset 0 of the same calls, which then make their own
outputs (``into`` None). ``kda_prepare`` and ``kda_recurrence`` are the
two pairs alone, a ``custom_vjp`` each over the same four builders (tests,
``tools/kda_kernel_bench.py``).

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
import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _bind, _dot, _interpret, _nbytes, _registry

CHUNK = 64      # tokens a chunk: the matmuls are [64, 128] x [128, 128]
SUB = 8         # rows a sub-block of the score matrices
CLAMP = 60.0    # largest exponent a sub-block's own columns may carry
NCK = 8         # chunks a grid step of the preparation
PREP_HEADS = 8  # heads a grid step of the preparation: four pairs in step
SEG = 16        # chunks a segment: a grid step of the recurrence, and a
#                 checkpoint's spacing
HEADS = 4       # heads a grid step of the recurrence: independent chains

_LANES = 128
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


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


def _specs(shapes, heads, seg, stack: int = 0):
    """A segment of ``heads`` heads of each [BH, N, ...] array: a group's
    own array or, with ``stack`` (the head blocks a group), the group's
    rows of the groups' stack [G BH, N, ...], ``grp`` its scalar-prefetch
    operand."""
    return [pl.BlockSpec(
        (heads, seg, *s[2:]),
        lambda b, j, grp, _r=len(s) - 2: (grp[0] * stack + b if stack else b,
                                          j) + (0,) * _r,
        memory_space=pltpu.VMEM) for s in shapes]


def _group(grp):
    """A head group's index, a Python int or a loop's counter, as the one
    scalar-prefetch operand the four calls' index maps read."""
    return jnp.asarray(grp, jnp.int32).reshape(1)


def _with_group(kernel, inputs: int, carried: int):
    """``kernel`` as a call with the group's index prefetched and
    ``carried`` aliased buffers behind its ``inputs`` hands it its refs:
    the index maps alone read the first, and nothing reads the buffers
    the outputs are written into."""
    return lambda grp, *refs: kernel(*refs[:inputs],
                                     *refs[inputs + carried:])


_CARRIED = pl.BlockSpec(memory_space=pl.ANY)


def _stack(bh: int, n: int, c: int, dv: int, dtype):
    """The head groups' stack of ``o`` [G BH, N, C, dv] for ``_forward``
    to write into, of whole segments and NOT initialised."""
    seg = min(SEG, n)
    return jax.lax.empty((bh, -(-n // seg) * seg, c, dv), dtype)


def _forward(ops, out_dtype, *, states: bool, grp=0, into=None):
    """Head group ``grp``: ``o`` in ``out_dtype`` written at the group's
    rows of ``into``, the groups' stack [G BH, N, C, dv] (``_stack``),
    which is returned (None: one group's own ``o``, a new array); or with
    ``states`` the group's float32 checkpoints [BH, N / seg, dv, dk]. Both
    of the padded chunk count."""
    u_v, w = ops[:2]
    bh, n, c, dk, seg, heads = _geometry(w)
    dv = u_v.shape[-1]
    _check_chip_shapes(c, dk, dv)
    ops = _pad_chunks(ops, -n % seg)
    nseg = ops[0].shape[1] // seg
    one = (bh, nseg * seg, c, dv)
    carried = () if into is None else (into,)
    if states:
        out_shape = jax.ShapeDtypeStruct((bh, nseg, dv, dk), jnp.float32)
        out_spec = pl.BlockSpec((heads, 1, dv, dk),
                                lambda b, j, grp: (b, j, 0, 0),
                                memory_space=pltpu.VMEM)
    else:
        out_shape = jax.ShapeDtypeStruct(
            one if into is None else into.shape, out_dtype)
        out_spec, = _specs([one], heads, seg, stack=bh // heads)
    mm = 2 * c * dk * dv
    flops = bh * nseg * seg * (2 * mm if states else 3 * mm + 2 * c * c * dv)
    # a group's own: its rows of the stack
    nbytes = sum(x.size * x.dtype.itemsize for x in ops) + (
        np.prod(out_shape.shape if states else one)
        * jnp.dtype(out_shape.dtype).itemsize)
    call = pl.pallas_call(
        _with_group(functools.partial(_fwd_kernel, heads=heads, seg=seg,
                                      states=states), len(ops), len(carried)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh // heads, nseg),
            in_specs=_specs([x.shape for x in ops], heads, seg)
            + [_CARRIED] * len(carried),
            out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)]),
        out_shape=out_shape,
        input_output_aliases={1 + len(ops): 0} if carried else {},
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(flops=int(flops), transcendentals=0,
                                      bytes_accessed=int(nbytes)),
        interpret=_interpret(),
        name="ds_kda_fwd",
    )
    # the scope and the kernel's name are all a device trace shows of this
    # call (telemetry/scopes.py)
    return _bind(call, "ds.kda_fwd",
                 ("kda_fwd", states, heads, seg, into is None),
                 _group(grp), *ops, *carried)[0]


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


def _backward(ops, ck, do, grp=0):
    """The six cotangents of head group ``grp``, each in its operand's
    shape and dtype; ``do`` the groups' stack [G BH, N, C, dv], read at
    the group's rows."""
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
        lambda b, j, grp, _m=spec.index_map: _m(b, last - j, grp),
        memory_space=pltpu.VMEM)
    shapes = [x.shape for x in ops]
    ck_spec = pl.BlockSpec((heads, 1, dv, dk),
                           lambda b, j, grp: (b, j, 0, 0),
                           memory_space=pltpu.VMEM)
    mm = 2 * c * dk * dv
    flops = bh * nseg * seg * (8 * mm + 4 * c * c * dv)
    nbytes = (2 * sum(x.size * x.dtype.itemsize for x in ops)
              + ck.size * 4 + u_v.size * do.dtype.itemsize)
    call = pl.pallas_call(
        _with_group(functools.partial(_bwd_kernel, heads=heads, seg=seg),
                    len(ops) + 2, 0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh // heads, nseg),
            in_specs=[rev(s) for s in
                      (*_specs(shapes, heads, seg), ck_spec,
                       *_specs([do.shape], heads, seg, stack=bh // heads))],
            out_specs=[rev(s) for s in _specs(shapes, heads, seg)],
            scratch_shapes=[pltpu.VMEM((heads, seg, dv, dk), jnp.float32),
                            pltpu.VMEM((heads, seg, c, dv), w.dtype),
                            pltpu.VMEM((heads, dv, dk), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in ops],
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(flops=int(flops), transcendentals=0,
                                      bytes_accessed=int(nbytes)),
        interpret=_interpret(),
        name="ds_kda_bwd",
    )
    grads = _bind(call, "ds.kda_bwd", ("kda_bwd", heads, seg),
                  _group(grp), *ops, ck, do)
    return tuple(x[:, :n] for x in grads)


# ------------------------------------------------------------ preparation
# Everything of a chunk that does not need the state, from q, k, v, g and
# beta: the module docstring's first half.
_HI = jax.lax.Precision.HIGHEST
_PREP_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"),
    vmem_limit_bytes=64 * 1024 * 1024)


def _hdot(a, b, dims=(((1,), (0,)), ((), ()))):
    """A float32 product at float32's precision (six bf16 passes)."""
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=jnp.float32)


def _half_at_tie(x):
    """d min(x, 0) / dx as ``jnp.minimum`` has it: 1 below, 1/2 at 0."""
    return jnp.where(x < 0, 1.0, jnp.where(x == 0, 0.5, 0.0))


def _side_by_side(mats):
    """The list's [C, C] arrays two to a [C, 2 C] array ``[x1 | x2]``
    while that fits a vreg's 128 lanes; an odd one out stays alone."""
    c = mats[0].shape[0]
    paired = len(mats) // 2 * 2 if 2 * c <= _LANES else 0
    return [jnp.concatenate(mats[i:i + 2], axis=1)
            for i in range(0, paired, 2)] + list(mats[paired:])


def _apart(xs):
    """The [C, C] arrays of a list of ``[x1 | x2 ...]``."""
    return [x[:, i:i + x.shape[0]]
            for x in xs for i in range(0, x.shape[1], x.shape[0])]


def _transposed(x):
    """``[x1^T | x2^T]`` of ``[x1 | x2]``: transposed, the heads lie one
    over the other; side by side again."""
    c = x.shape[0]
    return jnp.concatenate(
        [x.T[i:i + c] for i in range(0, x.shape[1], c)], axis=1)


def _unit_masks(a):
    """(I, the mask of the SUB x SUB blocks on the diagonal) in the shape
    of ``a`` [C, C] or ``[a1 | a2]``: a column counts within its head."""
    c, w = a.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, w), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, w), 1)
    cols = jnp.where(cols >= c, cols - c, cols)
    shift = SUB.bit_length() - 1
    return (jnp.where(rows == cols, 1.0, 0.0).astype(jnp.float32),
            jax.lax.shift_right_logical(rows, shift)
            == jax.lax.shift_right_logical(cols, shift))


def _pdot(x, y, *dims):
    """``[x1 y1 | x2 y2]`` of ``[x1 | x2]`` and ``[y1 | y2]`` [C, 2 C] as
    ONE float32 product against ``y1`` and ``y2`` on the diagonal of a
    [2 C, 2 C] operand: a latch that fills the MXU's 128 x 128 where two
    [C, C] latches fill a quarter each, half the rows streamed. The zero
    blocks add exact zeros to a float32 sum, so a head's numbers are those
    of the product taken alone, which is what a lone [C, C] gets."""
    c, w = y.shape
    if w > c:
        rows = jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)
        y = jnp.where((rows >= c) == (cols >= c),
                      jnp.concatenate([y, y], axis=0), 0.0)
    return _hdot(x, y, *dims)


def _neumann(xs, eye, order: int):
    """(I + x)^-1 = (I - x)(I + x^2)(I + x^4)... for ``x^order = 0``, of
    each x of the list (a head, or two side by side), in step."""
    invs = [e - x for e, x in zip(eye, xs)]
    while order > 2:
        xs = [_pdot(x, x) for x in xs]
        invs = [inv + _pdot(inv, x) for inv, x in zip(invs, xs)]
        order //= 2
    return invs


def _inverse_unit_lower(mats):
    """(I + a)^-1 for each strictly lower triangular ``a`` [C, C] of the
    list, float32, exact, in two finite Neumann products: with d the SUB x
    SUB blocks on the diagonal and low the rest, I + a = (I + d)(I + (I +
    d)^-1 low); d^SUB = 0 and the second factor's strictly block-lower
    part is nilpotent of order C / SUB. Ten dependent products. The heads
    take them two to a product (``_side_by_side``, ``_pdot``: half the
    passes through the MXU), and the result comes back so, ``[T1 | T2]``.
    A product waits a few hundred cycles for the one before it, so the
    pairs of a list advance IN STEP: each step's products are independent
    and fill the wait."""
    mats = _side_by_side(mats)
    eye, same_block = zip(*(_unit_masks(a) for a in mats))
    ds = [jnp.where(same, a, 0.0) for same, a in zip(same_block, mats)]
    ts = _neumann(ds, eye, SUB)
    ms = [_pdot(t, a - d) for t, a, d in zip(ts, mats, ds)]
    return [_pdot(n, t) for n, t in zip(
        _neumann(ms, eye, mats[0].shape[0] // SUB), ts)]


class _Chunk:
    """What both kernels build of one chunk of one head, in VMEM and
    registers: q, k [C, dk] in the matmuls' dtype ``dt``, v [C, dv], g
    [C, dk] float32, beta [1, C] float32 (a ROW: it scales the score
    matrices' columns; ``beta_col`` is the same numbers down a column).

    The score matrices are built by row blocks of ``SUB`` rows, each
    factored about the block's own first row ``G_f``: rows carry
    ``exp(G_i - G_f) <= 1``, earlier columns ``exp(G_f - G_j) <= 1`` and
    the block's own columns at most ``exp((SUB - 1) |g|)``, held to
    ``exp(CLAMP)``; the diagonal needs no decay and is exact. ``blocks``
    keeps each block's factors for the backward."""

    def __init__(self, q, k, v, g, beta):
        f32 = jnp.float32
        self.dt = dt = q.dtype
        self.c, self.dk = c, dk = k.shape
        self.v, self.beta = v.astype(dt), beta
        self.qf, self.kf = qf, kf = q.astype(f32), k.astype(f32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        self.low, self.diag = rows > cols, rows == cols
        # the running sum of g within the chunk, exact in six passes (a
        # one is one bf16 piece, g three)
        self.upto = jnp.where(rows >= cols, 1.0, 0.0).astype(f32)
        self.G = G = _hdot(self.upto, g)
        self.blocks, kk, qk = [], [], []
        for r0 in range(0, c, SUB):
            r1 = r0 + SUB
            n = min(c, -(-r1 // 16) * 16)   # whole bf16 tiles of columns
            ref = G[r0:r0 + 1]
            shrink = jnp.exp(G[r0:r1] - ref)
            left = jnp.concatenate(
                [kf[r0:r1] * shrink, qf[r0:r1] * shrink], axis=0).astype(dt)
            grow = jnp.exp(jnp.minimum(ref - G[:n], CLAMP))
            right = (kf[:n] * grow).astype(dt)
            if n < c:
                right = jnp.concatenate(
                    [right, jnp.zeros((c - n, dk), dt)], axis=0)
            s = _dot(left, right, _NT)                      # [2 SUB, C]
            kk.append(s[:SUB])
            qk.append(s[SUB:])
            self.blocks.append((r0, n, shrink, left, grow, right))
        # before beta scales their columns
        self.s_kk = jnp.concatenate(kk, axis=0)
        self.s_qk = jnp.concatenate(qk, axis=0)
        self.own = jnp.sum(qf * kf, axis=1, keepdims=True)  # <q_i, k_i>
        self.a_kk = jnp.where(self.low, self.s_kk, 0.0) * beta
        self.a_qk = jnp.where(
            self.low, self.s_qk, jnp.where(self.diag, self.own, 0.0)) * beta
        # a log-decay is never positive: the clamp only says so
        self.decay = jnp.exp(jnp.minimum(G, 0.0))
        self.tail = G[c - 1:c]                              # G_C
        self.kd = (kf * self.decay).astype(dt)              # K e^G
        self.beta_col = jnp.sum(jnp.where(self.diag, beta, 0.0), axis=1,
                                keepdims=True)
        self.fade = jnp.exp(self.tail - G)                  # e^(G_C - G)
        self.shrink = jnp.exp(jnp.minimum(self.tail, 0.0))

    @staticmethod
    def invert(chunks):
        """T = (I + a_kk)^-1 of each chunk of the list: side by side as
        ``_inverse_unit_lower`` returns them (the backward's float32
        products take them so), and apart in the matmuls' dtype (``tb``)."""
        ts = _inverse_unit_lower([x.a_kk for x in chunks])
        for chunk, t in zip(chunks, _apart(ts)):
            chunk.tb = t.astype(chunk.dt)
        return ts

    def operands(self):
        """u_v, w, q_in, a_qk, k_out, shrink as ``kda_recurrence`` takes
        them."""
        dt, dv = self.dt, self.v.shape[1]
        uw = _dot(self.tb, jnp.concatenate([self.v, self.kd], axis=1))
        return (uw[:, :dv], uw[:, dv:].astype(dt),
                (self.qf * self.decay).astype(dt), self.a_qk.astype(dt),
                (self.kf * self.beta_col * self.fade).astype(dt),
                self.shrink)

    @staticmethod
    def gradients(chunks, ts, cts):
        """(dq, dk, dv, dg [C, .], dbeta [1, C]), all float32, of each
        chunk of the list from ``invert``'s ``ts`` and its six cotangents
        (du_v, dw, dq_in, da_qk, dk_out, dshrink); the float32 products
        two heads to a product and in step, as the inverse's."""
        dt = chunks[0].dt
        # u_v = T V, w = T (K e^G)
        duws = [jnp.concatenate([ct[0].astype(dt), ct[1].astype(dt)], axis=1)
                for ct in cts]
        d_ts = _side_by_side(
            [_dot(duw, jnp.concatenate([x.v, x.kd], axis=1), _NT)
             for x, duw in zip(chunks, duws)])
        dvks = [_dot(x.tb, duw, _TN) for x, duw in zip(chunks, duws)]
        # T = (I + a_kk)^-1: da_kk = -T^T (dT T^T), strictly lower; a pair
        # takes its own head's factor from the right
        d_as = [_pdot(d_t, t, _NT) for t, d_t in zip(ts, d_ts)]
        d_as = [_pdot(_transposed(t), d_a) for t, d_a in zip(ts, d_as)]
        d_as = [jnp.where(x.low, -d_a, 0.0)
                for x, d_a in zip(chunks, _apart(d_as))]
        parts = [x._scores_and_decays(d_a, dvk, *ct[2:])
                 for x, d_a, dvk, ct in zip(chunks, d_as, dvks, cts)]
        # the running sum's transpose: a reversed sum within the chunk
        return [(dq, dk, d_v, x._sum_back(d_g), dbeta)
                for x, (dq, dk, d_v, d_g, dbeta) in zip(chunks, parts)]

    def _sum_back(self, d_g):
        return _hdot(self.upto, d_g, _TN)

    def _scores_and_decays(self, d_a, dvk, dq_in, da_qk, dk_out, dshrink):
        """dq, dk, dv, dG, dbeta from da_kk and the other cotangents."""
        f32 = jnp.float32
        dt, c, dv = self.dt, self.c, self.v.shape[1]
        qf, kf, G, beta = self.qf, self.kf, self.G, self.beta
        d_v, dkd = dvk[:, :dv], dvk[:, dv:]
        da = da_qk.astype(f32)
        da_low = jnp.where(self.low, da, 0.0)
        da_own = jnp.where(self.diag, da, 0.0)
        dbeta = jnp.sum(d_a * self.s_kk + da_low * self.s_qk
                        + da_own * self.own, axis=0, keepdims=True)
        ds_kk, ds_qk = d_a * beta, da_low * beta
        d_own = jnp.sum(da_own * beta, axis=1, keepdims=True)
        # per row block of SUB rows
        nb = c // SUB
        block = lambda x, b: x[b * SUB:(b + 1) * SUB]  # noqa: E731
        dq = [block(d_own, b) * block(kf, b) for b in range(nb)]
        dk = [block(d_own, b) * block(qf, b) for b in range(nb)]
        d_g = [None] * nb                                   # dG
        first = jax.lax.broadcasted_iota(
            jnp.int32, (SUB, self.dk), 0) == 0
        for b, (r0, n, shrink, left, grow, right) in enumerate(self.blocks):
            r1 = r0 + SUB
            ds = jnp.concatenate([ds_kk[r0:r1], ds_qk[r0:r1]],
                                 axis=0).astype(dt)          # [2 SUB, C]
            dleft = _dot(ds, right)                          # [2 SUB, dk]
            dright = _dot(ds, left, _TN)[:n]                 # [n, dk]
            dlk, dlq = dleft[:SUB] * shrink, dleft[SUB:] * shrink
            dk[b] = dk[b] + dlk
            dq[b] = dq[b] + dlq
            lean = dlk * kf[r0:r1] + dlq * qf[r0:r1]        # d(G_i - G_f)
            p = dright * grow
            # a clamped factor passes no gradient
            pull = jnp.where(G[r0:r0 + 1] - G[:n] < CLAMP, p * kf[:n], 0.0)
            dref = (jnp.sum(pull, axis=0, keepdims=True)
                    - jnp.sum(lean, axis=0, keepdims=True))
            lean = lean + jnp.where(first, dref, 0.0)
            for j in range(n // SUB):
                dk[j] = dk[j] + block(p, j)
                mine = -block(pull, j) + (lean if j == b else 0.0)
                d_g[j] = mine if d_g[j] is None else d_g[j] + mine
        dq, dk, d_g = (jnp.concatenate(x, axis=0) for x in (dq, dk, d_g))
        # the decay products
        dqi, dko = dq_in.astype(f32), dk_out.astype(f32)
        ddecay = dkd * kf + dqi * qf
        dq = dq + dqi * self.decay
        dkb = dko * self.fade                                # d(k beta)
        dk = dk + dkd * self.decay + dkb * self.beta_col
        dbeta = dbeta + jnp.sum(
            jnp.where(self.diag,
                      jnp.sum(dkb * kf, axis=1, keepdims=True), 0.0),
            axis=0, keepdims=True)
        dfade = dkb * kf * self.beta_col                     # d(G_C - G)
        dtail = (jnp.sum(dfade, axis=0, keepdims=True)
                 + dshrink * self.shrink * _half_at_tie(self.tail))
        last = jax.lax.broadcasted_iota(
            jnp.int32, (c, self.dk), 0) == c - 1
        d_g = (d_g + ddecay * self.decay * _half_at_tie(G) - dfade
               + jnp.where(last, dtail, 0.0))
        return dq, dk, d_v, d_g, dbeta


class _HeadChunk(_Chunk):
    """... of a gate a HEAD (Gated DeltaNet): g [1, C] float32, a ROW as
    beta is, one log-decay a token. ``Diag(exp(g_t)) = exp(g_t) I``, so a
    chunk's decay is ONE mask ``D_ij = exp(G_i - G_j)`` (j <= i, else 0)
    on the plain score matrices ``K K^T`` and ``Q K^T``: its exponent is
    never positive, so there are no row blocks, no factoring and no clamp,
    and it is exact at ANY decay (a factored block loses a near-diagonal
    ``exp(g_i)`` where the rows before it in the block decay past what a
    float32 exponent holds: -16 softplus(.) a token is this family's
    init). What a row carries down a column (``G``, ``decay``, ``fade``,
    ``beta_col``) is [C, 1] and broadcasts along the lanes."""

    def __init__(self, q, k, v, g, beta):
        f32 = jnp.float32
        self.dt = dt = q.dtype
        self.c, self.dk = c, dk = k.shape
        self.v, self.beta = v.astype(dt), beta
        self.qf, self.kf = qf, kf = q.astype(f32), k.astype(f32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        self.low, self.diag = rows > cols, rows == cols
        # the running sum along the row: G_j = sum_{r <= j} g_r
        self.upto = jnp.where(rows <= cols, 1.0, 0.0).astype(f32)
        g_row = _hdot(g, self.upto)                         # [1, C]
        self.G = G = self._col(g_row)                       # [C, 1]
        self.D = D = jnp.where(
            rows >= cols, jnp.exp(jnp.minimum(G - g_row, 0.0)), 0.0)
        self.kq = jnp.concatenate([k.astype(dt), q.astype(dt)], axis=0)
        s = _dot(self.kq, k.astype(dt), _NT)                # [2 C, C]
        self.s_kk, self.s_qk = s[:c], s[c:]
        self.a_kk = jnp.where(self.low, self.s_kk * D, 0.0) * beta
        self.a_qk = self.s_qk * D * beta
        self.decay = jnp.exp(jnp.minimum(G, 0.0))           # [C, 1]
        self.tail = jnp.sum(g, axis=1, keepdims=True)       # G_C, [1, 1]
        self.kd = (kf * self.decay).astype(dt)              # K e^G
        self.beta_col = self._col(beta)
        self.fade = jnp.exp(jnp.minimum(self.tail - G, 0.0))
        self.shrink_one = jnp.exp(jnp.minimum(self.tail, 0.0))
        self.shrink = jnp.broadcast_to(self.shrink_one, (1, dk))

    def _col(self, row):
        """[1, C] down a column [C, 1]."""
        return jnp.sum(jnp.where(self.diag, row, 0.0), axis=1, keepdims=True)

    def _row(self, col):
        return jnp.sum(jnp.where(self.diag, col, 0.0), axis=0, keepdims=True)

    def _sum_back(self, d_g):
        """The running sum's transpose of a column, as the row dg is."""
        return _hdot(self._row(d_g), self.upto, _NT)

    def _scores_and_decays(self, d_a, dvk, dq_in, da_qk, dk_out, dshrink):
        """dq, dk, dv, dG [C, 1], dbeta from da_kk and the other
        cotangents."""
        f32 = jnp.float32
        dt, c, dv = self.dt, self.c, self.v.shape[1]
        qf, kf, G, beta, D = self.qf, self.kf, self.G, self.beta, self.D
        d_v, dkd = dvk[:, :dv], dvk[:, dv:]
        da = da_qk.astype(f32)
        sd_kk, sd_qk = self.s_kk * D, self.s_qk * D     # zero above the diag
        dbeta = jnp.sum(d_a * sd_kk + da * sd_qk, axis=0, keepdims=True)
        p_kk, p_qk = d_a * beta, da * beta              # of the masked scores
        ds = jnp.concatenate([p_kk * D, p_qk * D], axis=0).astype(dt)
        dleft = _dot(ds, self.kq[:c])                   # [2 C, dk]
        dk = dleft[:c] + _dot(ds, self.kq, _TN)
        dq = dleft[c:]
        # the mask: d(G_i - G_j) = dD D
        lean = p_kk * sd_kk + p_qk * sd_qk
        d_g = (jnp.sum(lean, axis=1, keepdims=True)
               - self._col(jnp.sum(lean, axis=0, keepdims=True)))
        # the decay products
        dqi, dko = dq_in.astype(f32), dk_out.astype(f32)
        ddecay = jnp.sum(dkd * kf + dqi * qf, axis=1, keepdims=True)
        dq = dq + dqi * self.decay
        dkb = dko * self.fade                               # d(k beta)
        dk = dk + dkd * self.decay + dkb * self.beta_col
        pull = jnp.sum(dkb * kf, axis=1, keepdims=True)     # [C, 1]
        dbeta = dbeta + self._row(pull)
        dfade = pull * self.beta_col                        # d(G_C - G)
        dtail = (jnp.sum(dfade, axis=0, keepdims=True)
                 + jnp.sum(dshrink, axis=1, keepdims=True) * self.shrink_one
                 * _half_at_tie(self.tail))
        last = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
        d_g = (d_g + ddecay * self.decay * _half_at_tie(G) - dfade
               + jnp.where(last, dtail, 0.0))
        return dq, dk, d_v, d_g, dbeta


def _key_lanes(h, rep, dk):
    """The lanes of value head ``h``'s key head in q's and k's blocks."""
    return slice(h // rep * dk, (h // rep + 1) * dk)


def _chunks(q_ref, k_ref, v_ref, g_ref, b_ref, i, heads, c, dk, dv,
            head_gate=False, rep=1):
    """(the rows of chunk ``i`` in the inputs' blocks, that chunk of each
    head of the grid step, their inverses side by side). ``head_gate``:
    ``g_ref`` holds rows as ``b_ref`` does, a number a token. ``rep``: the
    value heads a key head serves; q's and k's blocks hold ``heads // rep``
    key heads, and head ``h`` reads the lanes of key head ``h // rep``."""
    rows = pl.ds(pl.multiple_of(i * c, c), c)
    make = _HeadChunk if head_gate else _Chunk
    gate = (lambda h: g_ref[h, i]) if head_gate else (
        lambda h: g_ref[0, rows, h * dk:(h + 1) * dk])
    chunks = [make(q_ref[0, rows, _key_lanes(h, rep, dk)],
                   k_ref[0, rows, _key_lanes(h, rep, dk)],
                   v_ref[0, rows, h * dv:(h + 1) * dv],
                   gate(h).astype(jnp.float32),
                   b_ref[h, i].astype(jnp.float32)) for h in range(heads)]
    return rows, chunks, _Chunk.invert(chunks)


def _prep_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, uv_ref, w_ref,
                     qi_ref, a_ref, ko_ref, sh_ref, *, heads, nck, c, dk,
                     dv, head_gate=False, rep=1):
    """``nck`` chunks of ``heads`` heads: the inputs' blocks are [1,
    nck C, heads d] of the model's [B, S, H d] (q's and k's ``heads //
    rep`` key heads wide), beta's (and a gate a head's) [heads, nck, 1,
    C]; the operands' [heads, nck, C, .]."""
    def chunk(i, carry):
        _, chunks, _ = _chunks(q_ref, k_ref, v_ref, g_ref, b_ref, i, heads,
                               c, dk, dv, head_gate, rep)
        for h, chunk in enumerate(chunks):
            for ref, x in zip((uv_ref, w_ref, qi_ref, a_ref, ko_ref,
                               sh_ref), chunk.operands()):
                ref[h, i] = x.astype(ref.dtype)
        return carry

    jax.lax.fori_loop(0, nck, chunk, 0)


def _prep_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, duv_ref, dw_ref,
                     dqi_ref, da_ref, dko_ref, dsh_ref, dq_ref, dk_ref,
                     dv_ref, dg_ref, db_ref, *, heads, nck, c, dk, dv,
                     head_gate=False, rep=1):
    """The same blocks; rebuilds each chunk's forward, then its backward.
    The ``rep`` value heads of a key head lie in one grid step: their
    ``dq`` and ``dk`` are summed here in float32 and written once."""
    def chunk(i, carry):
        rows, chunks, ts = _chunks(q_ref, k_ref, v_ref, g_ref, b_ref, i,
                                   heads, c, dk, dv, head_gate, rep)
        cts = [tuple(ref[h, i] for ref in (duv_ref, dw_ref, dqi_ref, da_ref,
                                           dko_ref, dsh_ref))
               for h in range(heads)]
        for h, grads in enumerate(_Chunk.gradients(chunks, ts, cts)):
            dqk = grads[:2] if h % rep == 0 else [
                a + x for a, x in zip(dqk, grads[:2])]
            if h % rep == rep - 1:
                for ref, x in zip((dq_ref, dk_ref), dqk):
                    ref[0, rows, _key_lanes(h, rep, dk)] = x.astype(
                        ref.dtype)
            dv_ref[0, rows, h * dv:(h + 1) * dv] = grads[2].astype(
                dv_ref.dtype)
            # dg in its input's layout: rows a head, or the model's own
            at = (h, i) if head_gate else (0, rows,
                                           slice(h * dk, (h + 1) * dk))
            dg_ref[at] = grads[3].astype(dg_ref.dtype)
            db_ref[h, i] = grads[4].astype(db_ref.dtype)
        return carry

    jax.lax.fori_loop(0, nck, chunk, 0)


def _prep_geometry(q, v, chunk, groups: int = 1):
    """(B, chunks, the VALUE heads, the value heads a key head serves, dk,
    dv, chunks and heads a grid step). The head count is ``v``'s; q and k
    may hold fewer heads, each serving ``rep`` consecutive value heads, and
    a grid step then takes whole key heads of ONE of the ``groups`` head
    groups."""
    b, s, hk, dk = q.shape
    h = v.shape[2]
    if s % chunk or chunk % SUB:
        raise ValueError(
            f"chunk_kda: sequence {s} must be a multiple of the chunk "
            f"{chunk}, and the chunk of {SUB}")
    if h % hk:
        raise ValueError(
            f"kda kernels: {h} value heads are no multiple of q's and k's "
            f"{hk} heads")
    rep = h // hk
    n = s // chunk
    nck = next(d for d in range(min(NCK, n), 0, -1) if n % d == 0)
    heads = next((d for d in range(min(PREP_HEADS, h // groups), 0, -1)
                  if h // groups % d == 0 and d % rep == 0), None)
    if heads is None:
        raise ValueError(
            f"kda kernels: a key head serves {rep} value heads, more than "
            f"the {PREP_HEADS} heads a grid step of the preparation holds")
    return b, n, h, rep, dk, v.shape[-1], nck, heads


class _Prep(typing.NamedTuple):
    """What the preparation's two calls are built from: ``_prep_geometry``'s
    eight, the chunk, whether the gate is a head's, and the head groups."""
    b: int
    n: int
    h: int
    rep: int
    dk: int
    dv: int
    nck: int
    heads: int
    c: int
    head_gate: bool
    groups: int


def _prep_specs(p: _Prep):
    """(the five inputs' specs, the six operands' specs and shapes): a
    grid step (batch, head block of the group, chunk block). The inputs
    are the WHOLE arrays: a head group is an offset in their index maps,
    the group's first head block (``grp``, the scalar-prefetch operand,
    times the head blocks a group), the same in q's and k's blocks of the
    ``heads // rep`` key heads of the step's value heads. The operands
    are the group's own."""
    heads, nck, c, dk, dv = p.heads, p.nck, p.c, p.dk, p.dv
    hb = p.h // heads               # head blocks of the whole arrays,
    gb = hb // p.groups             # of a group
    wide = lambda d, n=heads: pl.BlockSpec(  # noqa: E731
        (1, nck * c, n * d), lambda i, j, l, grp: (i, l, grp[0] * gb + j),
        memory_space=pltpu.VMEM)
    rows = lambda *d: pl.BlockSpec(  # noqa: E731
        (heads, nck, *d),
        lambda i, j, l, grp: (i * hb + grp[0] * gb + j, l, 0, 0),
        memory_space=pltpu.VMEM)
    flat = lambda *d: pl.BlockSpec(  # noqa: E731
        (heads, nck, *d), lambda i, j, l, grp: (i * gb + j, l, 0, 0),
        memory_space=pltpu.VMEM)
    ins = [wide(dk, heads // p.rep), wide(dk, heads // p.rep), wide(dv),
           rows(1, c) if p.head_gate else wide(dk), rows(1, c)]
    ops = [flat(c, dv), flat(c, dk), flat(c, dk), flat(c, c), flat(c, dk),
           flat(1, dk)]
    shapes = [(p.b * p.h // p.groups, p.n, *spec.block_shape[2:])
              for spec in ops]
    return ins, ops, shapes


def _prep_inputs(q, k, v, g, beta, chunk, groups: int = 1):
    """(q, k, v, g, beta as the preparation's calls read them, what the
    calls are built from): [B, S, H, d] as [B, S, H d] (no copy); beta
    [B, S, H], and a gate a head, as [B H, N, 1, C] (one small
    transpose). Made ONCE for all the head groups' calls: a loop over the
    groups carries these and nothing of the model's own shapes."""
    p = _Prep(*_prep_geometry(q, v, chunk, groups), chunk, g.ndim == 3,
              groups)
    wide = lambda x: x.reshape(p.b, p.n * chunk, -1)  # noqa: E731
    rows = lambda x: jnp.moveaxis(  # noqa: E731
        x, 2, 1).reshape(p.b * p.h, p.n, 1, chunk)
    return (wide(q), wide(k), wide(v),
            rows(g) if p.head_gate else wide(g), rows(beta)), p


def _gauge_heads(key: int, value: int, groups: int):
    """Trace time, host only: whether q and k reach the kernels at fewer
    heads than v, and in how many head groups the scan runs them, is a
    function of shapes and of the caller's count, so it is said where the
    preparation's kernel is built."""
    reg = _registry()
    if reg is None:
        return
    g = reg.gauge("ds_kda_heads",
                  "heads of q and k (key) and of v, g, beta (value) of the "
                  "delta-rule scan's preparation kernel last built, and "
                  "the head groups it runs them in (groups)")
    g.set(key, kind="key")
    g.set(value, kind="value")
    g.set(groups, kind="groups")


def _prep_grid(p: _Prep, kernel, inputs: int, carried: int, **specs):
    """The preparation's grid and kernel, either direction."""
    return dict(
        kernel=_with_group(functools.partial(
            kernel, heads=p.heads, nck=p.nck, c=p.c, dk=p.dk, dv=p.dv,
            head_gate=p.head_gate, rep=p.rep), inputs, carried),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(p.b, p.h // p.groups // p.heads, p.n // p.nck), **specs),
        compiler_params=_PREP_PARAMS, interpret=_interpret())


def _prepare_forward(args, p: _Prep, grp=0):
    """The six operands of head group ``grp``, [B H / G, N, C, .]
    (``shrink`` [B H / G, N, dk]), read from the whole q, k, v, g and beta
    (``args``, ``p``: ``_prep_inputs``) at the group's heads."""
    _check_chip_shapes(p.c, p.dk, p.dv)
    _gauge_heads(p.h // p.rep, p.h, p.groups)
    ins, ops, shapes = _prep_specs(p)
    f32, dt = jnp.float32, args[0].dtype
    out_shape = [jax.ShapeDtypeStruct(s, d) for s, d in zip(
        shapes, (f32, dt, dt, dt, dt, f32))]
    call = pl.pallas_call(
        **_prep_grid(p, _prep_fwd_kernel, len(args), 0, in_specs=ins,
                     out_specs=ops),
        out_shape=out_shape,
        cost_estimate=_prep_cost(p, _nbytes(*args) // p.groups
                                 + _nbytes(*out_shape), backward=False),
        name="ds_kda_prep_fwd",
    )
    u_v, w, q_in, a_qk, k_out, shrink = _bind(
        call, "ds.kda_prep_fwd", ("kda_prep_fwd", p), _group(grp), *args)
    return u_v, w, q_in, a_qk, k_out, shrink.reshape(-1, p.n, p.dk)


def _prepare_backward(args, cts, p: _Prep, grp=0, into=None):
    """dq, dk, dv, dg and dbeta of head group ``grp`` written where the
    group's heads lie in the WHOLE gradients, in ``args``' layout
    (``_prep_gradients`` is the way back to the model's): into ``into``,
    the five buffers the groups carry along, which are returned, or with
    one group into new ones (None). Every block is written by exactly one
    group, so nothing is zeroed and nothing is added."""
    ins, ops, _ = _prep_specs(p)
    *mats, dshrink = cts
    cts = (*mats, dshrink.reshape(-1, p.n, 1, p.dk))
    carried = () if into is None else tuple(into)
    first = 1 + len(args) + len(cts)        # behind the group's index
    call = pl.pallas_call(
        **_prep_grid(p, _prep_bwd_kernel, len(args) + len(cts),
                     len(carried),
                     in_specs=ins + ops + [_CARRIED] * len(carried),
                     out_specs=ins),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in args],
        input_output_aliases={first + i: i for i in range(len(carried))},
        cost_estimate=_prep_cost(p, 2 * _nbytes(*args) // p.groups
                                 + _nbytes(*cts), backward=True),
        name="ds_kda_prep_bwd",
    )
    return tuple(_bind(
        call, "ds.kda_prep_bwd", ("kda_prep_bwd", p, into is None),
        _group(grp), *args, *cts, *carried))


def _prep_gradients(grads, q, k, v, g, beta):
    """``_prepare_backward``'s five in the model's shapes: the rows of
    dbeta, and of a gate a head's dg, back to [B, S, H]."""
    dq, dk_, dv_, dg, dbeta = grads
    tokens = lambda x: jnp.moveaxis(  # noqa: E731
        x.reshape(*beta.shape[::2], beta.shape[1]), 1, 2)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            tokens(dg) if g.ndim == 3 else dg.reshape(g.shape),
            tokens(dbeta))


def _prep_cost(p: _Prep, nbytes, *, backward: bool):
    """Matmul passes as bf16 FLOPs (a float32 product is six), the
    exponentials, and every operand's one trip. The FLOPs are the
    mathematics': a [C, C] product counts ``2 C^3`` a pass whether it runs
    alone or beside another head's, where the pair takes half the passes
    through the MXU (and multiplies as many zeros)."""
    c, dk, dv = p.c, p.dk, p.dv
    chunks = p.b * p.h // p.groups * p.n
    square = 2 * c * c * c
    flops = (6 * 10 * square + 2 * c * c * dk       # the inverse; G
             + 2 * 2 * c * c * dk                   # the score blocks
             + 2 * c * c * (dk + dv))               # T [V, K e^G]
    if backward:
        flops += (6 * 2 * square + 6 * 2 * c * c * dk
                  + 2 * 2 * c * c * (dk + dv) + 2 * 4 * c * c * dk)
    return pl.CostEstimate(
        flops=int(chunks * flops), bytes_accessed=int(nbytes),
        transcendentals=int(chunks * (2 if backward else 1) * c * dk
                            * (c // SUB + 5) // 2))


# ---------------------------------------------------------------- public
@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _recurrence(*ops_and_dtype):
    return _recurrence_fwd(*ops_and_dtype)[0]


def _recurrence_fwd(u_v, w, q_in, a_qk, k_out, shrink, out_dtype):
    ops = (u_v, w, q_in, a_qk, k_out, shrink)
    return _forward(ops, out_dtype, states=False)[:, :w.shape[1]], ops


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _prepare(q, k, v, g, beta, chunk):
    return _prepare_forward(*_prep_inputs(q, k, v, g, beta, chunk))


def _prepare_fwd(q, k, v, g, beta, chunk):
    return _prepare(q, k, v, g, beta, chunk), (q, k, v, g, beta)


def _prepare_bwd(chunk, inputs, cts):
    # opened here, as _recurrence_bwd opens it
    with jax.named_scope("ds.kda_scan"):
        args, p = _prep_inputs(*inputs, chunk)
        return _prep_gradients(_prepare_backward(args, cts, p), *inputs)


_prepare.defvjp(_prepare_fwd, _prepare_bwd)


def kda_prepare(q, k, v, g, beta, *, chunk: int):
    """The six operands of ``kda_recurrence``, each [B, H, N, C, .]
    (``shrink`` [B, H, N, dk]), from q, k [B, S, Hk, dk], v [B, S, H, dv]
    (the matmuls run in ``q``'s dtype), g [B, S, H, dk] and beta [B, S, H]
    (float32 in the kernels), or a gate a HEAD, g [B, S, H] (the kernels
    then build a chunk's decay as one [C, C] mask: ``_HeadChunk``). ``H``
    is a multiple of ``Hk``: key head ``j`` serves the value heads ``j H /
    Hk`` and on, read from the shapes; the kernels index q and k there and
    never repeat them, and dq, dk come back at ``Hk`` heads, the value
    heads' parts summed in float32. ``S`` must be a multiple of ``chunk``
    and ``chunk`` of ``SUB``."""
    b, _, h, _ = v.shape
    return tuple(x.reshape(b, h, *x.shape[1:])
                 for x in _prepare(q, k, v, g, beta, chunk))

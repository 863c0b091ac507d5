"""The chunked Mamba-2 scan (state-space duality) as a Pallas kernel pair
under one ``jax.custom_vjp``; ``ops/ssd.py`` has the mathematics and is the
caller.

Per head (width ``P``), chunk of ``Q`` tokens, running sum ``a`` of ``dt A``
within the chunk (float32), incoming float32 state ``S``::

    m    = (C B^T) * exp(a_i - a_j)   (j <= i, else 0)        [Q, Q]
    y    = m xd + exp(a) * (C S^T)          xd = dt x, in x's dtype
    S'   = exp(a_Q) S + (xd * exp(a_Q - a))^T B

- **Forward** (``ds_ssd_fwd``): grid (batch, chunks, blocks of heads), the
  chunks in order and a chunk's head blocks one after the other. Every
  operand arrives in the model's own layout: ``x`` and ``y`` as blocks of
  [B, S, H P], ``dt`` of [B, S, H], ``B`` and ``C`` of [B, S, G N]. A
  chunk's block of ``dt`` is fetched once, and its first grid step makes
  every head's running sum from it (one product with a triangle of ones,
  ``dt A`` in three bf16 pieces: float32 to the last bit); a chunk's ``B``
  and ``C`` are fetched once for the heads of their group, and ``C B^T``
  is made once for them into VMEM. A head's masked decay matrix and ``m``
  exist only in VMEM. Every head's state lives in one VMEM scratch across
  the chunk axis (zeroed at chunk 0) and never reaches HBM; the kernel
  writes ``y`` alone. Its **second form** (``states``) makes no ``y`` (no
  decay matrix, no ``C B^T``) and writes the state each chunk STARTS
  from: the backward's checkpoints, float32.
- **Backward** (``ds_ssd_bwd``): the chunks last to first, ``dS`` of every
  head carried in VMEM. A grid step rebuilds its chunk's ``m`` from the
  inputs and the chunk's checkpoint, and writes ``dx`` in the model's
  layout and ``dB``, ``dC`` summed over the heads that share them
  (float32, accumulated in the output block while a group's head blocks
  pass); a chunk's last grid step turns the heads' cotangents of ``a``
  into ``ddt`` [B, S, H] (the running sum's transpose is the same
  product, reversed) and adds the chunk's share of ``dA``::

      dm  = dy xd^T          dxd = m^T dy + exp(a_Q - a) * (B dS'^T)
      dC  = (sum_h dm * decay) B + (exp(a) dy) S
      dB  = (sum_h dm * decay)^T C + xe dS'
      dS  = exp(a_Q) dS' + (exp(a) dy)^T C
      da  = rowsum(dy * y) - rowsum(xd * dxd);  da_Q += <dS', S'>

  (``rowsum(dy * y)`` is the row sums of ``dm * m`` and of the carried
  term at once, ``rowsum(xd * dxd)`` the column sums: nothing of size
  [Q, Q] is reduced.) Residuals of the ``custom_vjp``: the five inputs.

**Row blocks.** ``m`` is taken by row blocks of ``_row_block(Q)`` rows
(128 of the cell's 256). The blocks above the diagonal are never built.
Only a diagonal block takes the masked exponential, a head at a time. A
block below the diagonal factors about its own first row ``r`` as
``exp(a_i - a_r) exp(a_r - a_j)`` with BOTH exponents <= 0 (j < r <= i):
a row scale of ``xd``, one product with ``C B^T`` (in ``x``'s dtype,
shared by the heads) for the whole block of heads, and a row scale of
the result. Nothing is clamped and nothing can overflow; ``a_r`` drops
out of the gradient.

**Layout.** A grid step's heads lie side by side along the lanes, [Q,
heads P], as the model holds them, and everything but the diagonal
blocks' three products a head is one wide operation for all of them. The
states are held TRANSPOSED, ``St`` [N, heads P]: the products with ``B``
and ``C`` are then one matmul for the block at the MXU's full width even
where ``P`` is half a lane tile. What is a number a head and token
(``dt``, ``a``, the decays) is held by ROW, [heads, Q]; the MXU turns rows
into the columns the elementwise work needs and sums a head's lanes back
into rows (``_Chunk``): as lane broadcasts and lane reductions the same
work held the kernels to the XLU (PR 37: 9.2k bundles a grid step of the
backward against 7.0k).

**One trace a shape** (``_bind``): a step holds each kernel many times
(a layer unrolled, a scan's body, remat's rerun); they share one jaxpr
and so one lowering, and each keeps its own scope.

Every exponent is a difference ``a_i - a_j`` (i >= j) or ``a`` itself,
taken before the exponential in float32; matmul operands are in ``x``'s
dtype with float32 accumulation; the state is float32.

On the chip a head block times ``P``, ``N`` and ``Q`` must be multiples of
128 (Mosaic's lane tiles); interpret mode (any other backend, the tests)
takes any shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _bind, _dot, _interpret, _nbytes, _pieces

HEADS = 16      # heads a grid step, at most

_LANES = 128
_SPREAD = 8     # heads a one-hot product of ``_Chunk.spread``
_NEG = -1e30    # the exponent above the diagonal: exp gives 0
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def _row_block(q: int) -> int:
    """Rows a block of ``m``: a lane tile where the chunk is whole lane
    tiles, else half the chunk."""
    if q % _LANES == 0:
        return _LANES
    return q // 2 if q % 2 == 0 else q


def _geometry(h: int, g: int, p: int):
    """(heads a block, heads a lane tile, head blocks a group) from what
    the input shows: the most heads, up to ``HEADS``, that divide a group
    and fill whole lane tiles. With ``p`` it is the kernels' ``geo``."""
    r = h // g
    fits = [d for d in range(min(HEADS, r), 0, -1) if r % d == 0]
    hb = next((d for d in fits if d * p % _LANES == 0), fits[0])
    pack = _LANES // p if p < _LANES and _LANES % p == 0 else 1
    if hb % pack:
        pack = 1
    return hb, pack, r // hb


def _check_chip_shapes(q, hb, p, n):
    if not _interpret() and (hb * p % _LANES or n % _LANES or q % _LANES):
        raise ValueError(
            f"ssd kernels: on the chip a head block's width (heads x head "
            f"size), the state size and the chunk must be multiples of "
            f"{_LANES}, not {hb} x {p}, {n} and {q}")


def _cat(parts, axis):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


def _running_sum(v, *, reverse: bool):
    """``sum_{j<=i} v[:, j]`` (``reverse``: ``j >= i``) along the lanes of
    ``v`` [rows, Q], float32: products of bf16 pieces with a triangle of
    ones, accumulated in float32."""
    q = v.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    ones = jnp.where(rows >= cols if reverse else rows <= cols, 1.0,
                     0.0).astype(jnp.bfloat16)
    return sum(_dot(piece, ones) for piece in _pieces(v, jnp.bfloat16))


def _mine(x, k, pack, p):
    """``x`` [rows, pack p] on the lanes of head ``k`` of its lane tile, 0
    on the others."""
    if pack == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= k * p) & (lane < (k + 1) * p), x,
                     jnp.zeros_like(x))


def _one_hot(rows, hb, width, dt):
    """[rows, hb width]: 1 where the row, modulo ``hb``, is the head the
    lane belongs to (``width`` lanes a head); rows past ``3 hb`` are 0."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, hb * width), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, hb * width), 1)
    hit = (row % hb == lane // width) & (row < 3 * hb)
    return jnp.where(hit, 1.0, 0.0).astype(dt)


class _Chunk:
    """What both kernels hold of one chunk of one block of ``hb`` heads.
    Everything a head and token (``dt``, the running sum ``a``, the decays
    they give) is held by ROW, [hb, Q]: two vregs each at 8 heads. The MXU
    turns a row into the columns the elementwise work needs, spread over
    its head's lanes (``spread``: a product of its three bf16 pieces with
    a one-hot matrix, exact), and sums a head's lanes back into a row
    (``head_sums``); the lane broadcasts and lane reductions they replace
    were what bounded the kernels (the XLU)."""

    def __init__(self, dt_ref, a_ref, dtrow_ref, arow_ref, geo, p):
        self.hb, self.pack, self.per_group = geo
        self.p, hb = p, self.hb
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _():        # every head's rows, once a chunk
            dt_row = dt_ref[0].T                            # [H, Q]
            dtrow_ref[:] = dt_row
            arow_ref[:] = _running_sum(dt_row * a_ref[:], reverse=False)

        self.rows = pl.ds(pl.multiple_of(j * hb, hb), hb)
        self.a = a = arow_ref[self.rows, :]
        self.dt = dtrow_ref[self.rows, :]
        self.q = q = a.shape[1]
        self.rb = _row_block(q)
        # a running sum of log-decays is never positive: the clamps only
        # say so
        self.last = last = a[:, q - 1:]                     # [hb, 1]
        self.grow = jnp.exp(jnp.minimum(a, 0.0))            # exp(a)
        self.fade = jnp.exp(last - a)                       # exp(a_Q - a)
        self.low = (jax.lax.broadcasted_iota(jnp.int32, (self.rb,) * 2, 0)
                    >= jax.lax.broadcasted_iota(jnp.int32, (self.rb,) * 2,
                                                1))
        # heads a one-hot product
        self.sg = _SPREAD if hb % _SPREAD == 0 else hb
        self._hot = {}

    def hot(self, width, dt=jnp.bfloat16, rows=None):
        """The one-hot matrix of a group of ``sg`` heads (``_one_hot``)."""
        key = (width, dt, rows)
        if key not in self._hot:
            self._hot[key] = _one_hot(rows or 4 * self.sg, self.sg, width, dt)
        return self._hot[key]

    def spread(self, v, width=None):
        """[r, hb width] from the rows ``v`` [hb, r] (float32): each
        head's row down its own ``width`` lanes (``P`` if not given); a
        product a group of ``sg`` heads, so the work grows with the heads
        and not with their square."""
        f32, sg = jnp.float32, self.sg
        hot = self.hot(width or self.p)
        out = []
        for g in range(0, self.hb, sg):
            parts = [x.astype(f32)
                     for x in _pieces(v[g:g + sg], jnp.bfloat16)]
            stack = jnp.concatenate(parts + [jnp.zeros_like(parts[0])],
                                    axis=0)
            out.append(_dot(stack.astype(jnp.bfloat16), hot, _TN))
        return _cat(out, 1)

    def shrink(self):
        """exp(a_Q) of each head over its own lanes, [1, hb P]."""
        hot = self.hot(self.p, jnp.float32)[:self.sg]
        ends = jnp.exp(jnp.minimum(self.last, 0.0))
        return _cat([jnp.sum(hot * ends[g:g + self.sg], axis=0, keepdims=True)
                     for g in range(0, self.hb, self.sg)], 1)

    def head_sums(self, x, pieces):
        """[hb, r] from ``x`` [r, hb P] (float32): the sum over each
        head's lanes, from the ``pieces`` leading bf16 pieces of ``x``."""
        sg, w = self.sg, self.sg * self.p
        hot = self.hot(self.p, rows=-(-sg // 16) * 16)
        parts = _pieces(x, jnp.bfloat16)[:pieces]
        out = [sum(_dot(hot, part[:, g * w:(g + 1) * w], _NT)
                   for part in parts)[:sg] for g in range(self.hb // sg)]
        return _cat(out, 0)

    def ends(self, x):
        """[hb, 1] from ``x`` [1, hb P]: the sum over each head's lanes."""
        sg, w = self.sg, self.sg * self.p
        hot = self.hot(self.p, jnp.float32)[:sg]
        out = [jnp.sum(hot * x[:, g * w:(g + 1) * w], axis=1, keepdims=True)
               for g in range(self.hb // sg)]
        return _cat(out, 0)

    def blocks(self):
        """(rows of the block, its first row) a row block of ``m``."""
        return [(slice(r0, r0 + self.rb), r0)
                for r0 in range(0, self.q, self.rb)]

    def tiles(self, acols_ref, arows_ref, body):
        """``body(lanes, heads)`` for every lane tile of the block, one
        after the other under ONE loop, unrolled only when it is lowered
        (the kernel's jaxpr, and the seconds a warm set-up spends tracing
        it, then do not grow with the heads a step; a rolled loop measured
        20% slower): ``lanes`` the tile's lanes of a [., hb P]
        array, ``heads`` its (k, decay) pairs, ``decay(rows)`` the head's
        diagonal block exp(a_i - a_j) for j <= i, else 0. ``acols_ref``
        [Q, hb rb] is filled with each head's column ``a_i`` over a row
        block's lanes, ``arows_ref`` [8 hb, Q] with its row ``a_j``."""
        acols_ref[:] = self.spread(self.a, self.rb)
        for h in range(self.hb):    # a row a sublane tile: aligned loads
            arows_ref[8 * h:8 * h + 8, :] = jnp.broadcast_to(
                self.a[h:h + 1], (8, self.q))
        w, rb = self.pack * self.p, self.rb

        def tile(t, carry):
            def decay(h):
                def block(rows):
                    a_i = acols_ref[rows, pl.ds(pl.multiple_of(h * rb, rb),
                                                rb)]
                    a_j = arows_ref[pl.ds(pl.multiple_of(h * 8, 8), 8),
                                    rows][:1]
                    return jnp.exp(jnp.where(self.low, a_i - a_j, _NEG))
                return block

            body(pl.ds(pl.multiple_of(t * w, w), w),
                 [(k, decay(t * self.pack + k)) for k in range(self.pack)])
            return carry

        jax.lax.fori_loop(0, self.hb // self.pack, tile, 0, unroll=True)

    def about(self, rows, r0):
        """A block below the diagonal about its first row ``r0``: (exp(a_r
        - a_j) for the columns j < r0, exp(a_i - a_r) for its rows), each
        spread over its head's lanes."""
        ref = self.a[:, r0:r0 + 1]
        return (self.spread(jnp.exp(ref - self.a[:, :r0])),
                self.spread(jnp.exp(self.a[:, rows] - ref)))

    def first_of_group(self):
        return pl.program_id(2) % self.per_group == 0

    def last_of_group(self):
        return pl.program_id(2) % self.per_group == self.per_group - 1


# ---------------------------------------------------------------- forward
def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, out_ref,
                st_ref, dtrow_ref, arow_ref, bt_ref, cb_ref, cbb_ref,
                xd_ref, acols_ref, arows_ref, y_ref, *, geo, p, states):
    """One chunk of ``hb`` heads. ``states``: ``out_ref`` is the state the
    chunk starts from [1, 1, 1, N, hb P] and no ``y`` is made; else it is
    ``y`` [1, Q, hb P]."""
    f32 = jnp.float32
    j = pl.program_id(2)
    mm = x_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[j] = jnp.zeros(st_ref.shape[1:], f32)

    ch = _Chunk(dt_ref, a_ref, dtrow_ref, arow_ref, geo, p)
    bm, cm = b_ref[0], c_ref[0]

    @pl.when(ch.first_of_group())
    def _():
        bt_ref[:] = bm.T
        if not states:
            cb = _dot(cm, bm, _NT)
            cb_ref[:] = cb
            cbb_ref[:] = cb.astype(mm)

    st = st_ref[j]                                  # [N, hb P]
    xd = (x_ref[0].astype(f32) * ch.spread(ch.dt)).astype(mm)   # dt_j x_j
    xd32 = xd.astype(f32)
    # from the rounded xd, as the jax.numpy form had it
    xe = (xd32 * ch.spread(ch.fade)).astype(mm)
    if states:
        out_ref[0, 0, 0] = st
    else:
        cs = _dot(cm, st.astype(mm))                # C S^T, every head
        for rows, r0 in ch.blocks():
            y = cs[rows] * ch.spread(ch.grow[:, rows])
            if r0:
                left, right = ch.about(rows, r0)
                xs = (xd32[:r0] * left).astype(mm)
                y = y + right * _dot(cbb_ref[rows, :r0], xs)
            y_ref[rows, :] = y
        xd_ref[:] = xd

        def tile(lanes, heads):     # the diagonal blocks, a head's own
            for rows, _ in ch.blocks():
                y_ref[rows, lanes] += sum(
                    _mine(_dot((cb_ref[rows, rows] * decay(rows)).astype(mm),
                               xd_ref[rows, lanes]), k, ch.pack, p)
                    for k, decay in heads)

        ch.tiles(acols_ref, arows_ref, tile)
        out_ref[0] = y_ref[:].astype(out_ref.dtype)
    st_ref[j] = st * ch.shrink() + _dot(bt_ref[:], xe)


def _blocks(q, h, p, n, hb, per_group, *, chunk_of):
    """The BlockSpecs of a grid step (batch, chunk, head block): x-like
    [B, S, H P], dt-like [B, S, H], A [H, 1], B-like [B, S, G N],
    checkpoints [B, C, HB, N, hb P]. ``chunk_of`` maps the grid's chunk
    index to the chunk."""
    vm = pltpu.VMEM
    wide = pl.BlockSpec((1, q, hb * p), lambda i, l, j: (i, chunk_of(l), j),
                        memory_space=vm)
    steps = pl.BlockSpec((1, q, h), lambda i, l, j: (i, chunk_of(l), 0),
                         memory_space=vm)
    rates = pl.BlockSpec((h, 1), lambda i, l, j: (0, 0), memory_space=vm)
    shared = pl.BlockSpec(
        (1, q, n), lambda i, l, j: (i, chunk_of(l), j // per_group),
        memory_space=vm)
    ck = pl.BlockSpec(
        (1, 1, 1, n, hb * p), lambda i, l, j: (i, chunk_of(l), j, 0, 0),
        memory_space=vm)
    return wide, steps, rates, shared, ck


def _forward(x, dt, A, B, C, dims, *, states: bool):
    """``y`` [B, S, H P] in ``x``'s dtype, or with ``states`` the float32
    checkpoints [B, C, HB, N, hb P]. ``dims`` = (Q, H, P, G, N)."""
    q, h, p, g, n = dims
    b, s, _ = x.shape
    c = s // q
    geo = hb, _, per_group = _geometry(h, g, p)
    _check_chip_shapes(q, hb, p, n)
    wide, steps, rates, shared, ck = _blocks(
        q, h, p, n, hb, per_group, chunk_of=lambda l: l)
    f32 = jnp.float32
    if states:
        out_shape = jax.ShapeDtypeStruct((b, c, h // hb, n, hb * p), f32)
        out_spec = ck
    else:
        out_shape, out_spec = jax.ShapeDtypeStruct(x.shape, x.dtype), wide
    head_chunks = b * c * h
    rb = _row_block(q)
    flops = head_chunks * 4 * q * n * p
    if not states:
        flops += head_chunks * q * (q + rb) * p + b * c * g * 2 * q * q * n
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, geo=geo, p=p, states=states),
        grid=(b, c, h // hb),
        in_specs=[wide, steps, rates, shared, shared],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((h // hb, n, hb * p), f32),
                        pltpu.VMEM((h, q), f32), pltpu.VMEM((h, q), f32),
                        pltpu.VMEM((n, q), x.dtype),
                        pltpu.VMEM((q, q), f32),
                        pltpu.VMEM((q, q), x.dtype),
                        pltpu.VMEM((q, hb * p), x.dtype),
                        pltpu.VMEM((q, hb * rb), f32),
                        pltpu.VMEM((8 * hb, q), f32),
                        pltpu.VMEM((q, hb * p), f32)],
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=int(flops),
            transcendentals=int(head_chunks * (3 * q if states
                                               else q * rb + 5 * q)),
            bytes_accessed=int(_nbytes(x, dt, A, B, C, out_shape))),
        interpret=_interpret(),
        name="ds_ssd_fwd",
    )
    # the scope and the kernel's name are all a device trace shows of this
    # call (telemetry/scopes.py)
    return _bind(call, "ds.ssd_fwd", ("fwd", dims, geo, states),
                 x, dt, A, B, C)[0]


# ---------------------------------------------------------------- backward
def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, ck_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                ds_ref, dtrow_ref, arow_ref, ddtrow_ref, darow_ref, ct_ref,
                cb_ref, cbb_ref, cbt_ref, dcb_ref, xd_ref, acols_ref,
                arows_ref, y_ref, dxd_ref, *, geo, p):
    """One chunk of ``hb`` heads, the chunks arriving last to first.
    ``ds_ref`` carries every head's ``dS`` (transposed, float32) to the
    chunk before; ``dcb_ref`` gathers a group's cotangent of ``C B^T``;
    ``ddtrow_ref`` and ``darow_ref`` a chunk's heads' rows [H, Q] of the
    direct part of ``ddt`` and of the cotangent of ``a``."""
    f32 = jnp.float32
    j = pl.program_id(2)
    mm = x_ref.dtype
    q, n = b_ref.shape[1:]

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[j] = jnp.zeros(ds_ref.shape[1:], f32)

    ch = _Chunk(dt_ref, a_ref, dtrow_ref, arow_ref, geo, p)
    bm, cm = b_ref[0], c_ref[0]

    @pl.when(ch.first_of_group())
    def _():
        cb = _dot(cm, bm, _NT)
        cb_ref[:] = cb
        cbb_ref[:] = cb.astype(mm)
        cbt_ref[:] = _dot(bm, cm, _NT).astype(mm)
        ct_ref[:] = cm.T
        dcb_ref[:] = jnp.zeros_like(dcb_ref)
        db_ref[0] = jnp.zeros((q, n), f32)
        dc_ref[0] = jnp.zeros((q, n), f32)

    st, dst = ck_ref[0, 0, 0], ds_ref[j]            # [N, hb P]
    sb, dsb = st.astype(mm), dst.astype(mm)
    xw = x_ref[0].astype(f32)
    dt_w, fade, grow = (ch.spread(v) for v in (ch.dt, ch.fade, ch.grow))
    shrink = ch.shrink()
    xd = (xw * dt_w).astype(mm)
    xd32 = xd.astype(f32)
    xe32 = xd32 * fade
    dy = dy_ref[0].astype(mm)
    dy32 = dy.astype(f32)
    cs = _dot(cm, sb)                               # C S^T, every head
    dxe = _dot(bm, dsb)                             # B dS'^T
    ys = [cs[rows] * grow[rows] for rows, _ in ch.blocks()]
    dxds = [dxe[rows] * fade[rows] for rows, _ in ch.blocks()]
    for i, (rows, r0) in enumerate(ch.blocks()):
        if r0:
            left, right = ch.about(rows, r0)
            xs = (xd32[:r0] * left).astype(mm)
            ys[i] = ys[i] + right * _dot(cbb_ref[rows, :r0], xs)
            dz = (dy32[rows] * right).astype(mm)
            dcb_ref[rows, :r0] += _dot(dz, xs, _NT)
            dxs = _dot(cbt_ref[:r0, rows], dz) * left
            for b in range(i):
                dxds[b] = dxds[b] + dxs[b * ch.rb:(b + 1) * ch.rb]
    y_ref[:], dxd_ref[:], xd_ref[:] = _cat(ys, 0), _cat(dxds, 0), xd

    def tile(lanes, heads):         # the diagonal blocks, a head's own
        for rows, _ in ch.blocks():
            xd_t = xd_ref[rows, lanes]
            dy_t = dy_ref[0, rows, lanes].astype(mm)
            for k, decay in heads:
                decay = decay(rows)
                m = (cb_ref[rows, rows] * decay).astype(mm)
                dy_k = _mine(dy_t, k, ch.pack, p)
                dcb_ref[rows, rows] += _dot(dy_k, xd_t, _NT) * decay
                dxd_ref[rows, lanes] += _dot(m, dy_k, _TN)
                y_ref[rows, lanes] += _mine(_dot(m, xd_t), k, ch.pack, p)

    ch.tiles(acols_ref, arows_ref, tile)
    y, dxd = y_ref[:], dxd_ref[:]
    dys = (dy32 * grow).astype(mm)                  # exp(a) dy
    dc_ref[0] += _dot(dys, sb, _NT)
    db_ref[0] += _dot(xe32.astype(mm), dsb, _NT)
    ds_ref[j] = dst * shrink + _dot(ct_ref[:], dys)
    dx_ref[0] = (dxd * dt_w).astype(dx_ref.dtype)
    # <dS', S'> a head: exp(a_Q) <dS', S> + <B dS'^T, xe>
    ends = (jnp.sum(st * dst, axis=0, keepdims=True) * shrink
            + jnp.sum(dxe * xe32, axis=0, keepdims=True))
    ends = ch.ends(ends)
    # sums of products of bf16 operands need no third piece
    pieces = 2 if mm == jnp.bfloat16 else 3
    ddt = ch.head_sums(dxd * xw, pieces)
    tail = jax.lax.broadcasted_iota(jnp.int32, ddt.shape, 1) == q - 1
    ddtrow_ref[ch.rows, :] = ddt
    darow_ref[ch.rows, :] = (ch.head_sums(dy32 * y, pieces) - ch.dt * ddt
                             + jnp.where(tail, ends, 0.0))

    @pl.when(ch.last_of_group())
    def _():
        dcb = dcb_ref[:].astype(mm)
        dc_ref[0] += _dot(dcb, bm)
        db_ref[0] += _dot(dcb, cm, _TN)

    first_chunk = pl.program_id(1) == 0

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        # the running sum's transpose: a reversed sum within the chunk
        ddta = _running_sum(darow_ref[:], reverse=True)     # [H, Q]
        ddt_ref[0] = (ddtrow_ref[:] + ddta * a_ref[:]).T
        share = jnp.sum(ddta * dtrow_ref[:], axis=1, keepdims=True)
        da_ref[0] = jnp.where(first_chunk, 0.0, da_ref[0]) + share


def _backward(x, dt, A, B, C, ck, dy, dims):
    """dx [B, S, H P] in ``x``'s dtype; ddt [B, S, H], a batch row's dA
    [B, H, 1] and dB, dC [B, S, G N], float32."""
    q, h, p, g, n = dims
    b, s, _ = x.shape
    c = s // q
    geo = hb, _, per_group = _geometry(h, g, p)
    wide, steps, rates, shared, ckpt = _blocks(
        q, h, p, n, hb, per_group, chunk_of=lambda l: c - 1 - l)
    f32 = jnp.float32
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype),
                 jax.ShapeDtypeStruct(dt.shape, f32),
                 jax.ShapeDtypeStruct((b, h, 1), f32),
                 jax.ShapeDtypeStruct(B.shape, f32),
                 jax.ShapeDtypeStruct(C.shape, f32)]
    whole = pl.BlockSpec((1, h, 1), lambda i, l, j: (i, 0, 0),
                         memory_space=pltpu.VMEM)
    head_chunks = b * c * h
    rb = _row_block(q)
    mm = x.dtype
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, geo=geo, p=p),
        grid=(b, c, h // hb),
        in_specs=[wide, steps, rates, shared, shared, ckpt, wide],
        out_specs=[wide, steps, whole, shared, shared],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((h // hb, n, hb * p), f32)]
        + [pltpu.VMEM((h, q), f32)] * 4
        + [pltpu.VMEM((n, q), mm), pltpu.VMEM((q, q), f32),
           pltpu.VMEM((q, q), mm), pltpu.VMEM((q, q), mm),
           pltpu.VMEM((q, q), f32), pltpu.VMEM((q, hb * p), mm),
           pltpu.VMEM((q, hb * rb), f32), pltpu.VMEM((8 * hb, q), f32),
           pltpu.VMEM((q, hb * p), f32), pltpu.VMEM((q, hb * p), f32)],
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=int(head_chunks * (3 * q * (q + rb) * p + 10 * q * n * p)
                      + b * c * g * 8 * q * q * n),
            transcendentals=int(head_chunks * (q * rb + 5 * q)),
            bytes_accessed=int(_nbytes(x, dt, A, B, C, ck, dy, *out_shape))),
        interpret=_interpret(),
        name="ds_ssd_bwd",
    )
    return _bind(call, "ds.ssd_bwd", ("bwd", dims, geo),
                 x, dt, A, B, C, ck, dy)


# ---------------------------------------------------------------- public
def _operands(x, dt, A, B, C, chunk):
    """The kernels' five operands and ``dims``: the model's arrays with
    their last two axes merged (no copy), B and C in ``x``'s dtype, dt and
    A float32."""
    f32 = jnp.float32
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    return (x.reshape(b, s, h * p), dt.astype(f32),
            A.astype(f32).reshape(h, 1),
            B.reshape(b, s, g * n).astype(x.dtype),
            C.reshape(b, s, g * n).astype(x.dtype)), (chunk, h, p, g, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd(x, dt, A, B, C, chunk):
    return _ssd_fwd(x, dt, A, B, C, chunk)[0]


def _ssd_fwd(x, dt, A, B, C, chunk):
    ops, dims = _operands(x, dt, A, B, C, chunk)
    y = _forward(*ops, dims, states=False)
    return y.reshape(x.shape), (x, dt, A, B, C)


def _ssd_bwd(chunk, inputs, dy):
    x, dt, A, B, C = inputs
    b, s, h, p = x.shape
    # opened here: a custom_vjp's backward function is traced outside the
    # scope its forward was called under
    with jax.named_scope("ds.ssd"):
        ops, dims = _operands(x, dt, A, B, C, chunk)
        ck = _forward(*ops, dims, states=True)
        dx, ddt, dA, dB, dC = _backward(
            *ops, ck, dy.reshape(b, s, h * p).astype(x.dtype), dims)
        dA = jnp.sum(dA, axis=0).reshape(h)
    return (dx.reshape(x.shape), ddt.astype(dt.dtype), dA.astype(A.dtype),
            dB.reshape(B.shape).astype(B.dtype),
            dC.reshape(C.shape).astype(C.dtype))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, B, C, *, chunk: int):
    """The chunked scan of the module docstring. x [B, S, H, P]; dt
    [B, S, H]; A [H]; B, C [B, S, G, N]; ``S`` a multiple of ``chunk`` and
    ``H`` of ``G``. Returns y [B, S, H, P] in ``x``'s dtype."""
    return _ssd(x, dt, A, B, C, chunk)

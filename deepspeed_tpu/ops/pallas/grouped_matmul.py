"""The held experts' FFN as a grouped matmul: a Pallas kernel pair over
rows that lie in EXPERT ORDER (``moe/sharded_moe.py`` ``held_experts_ffn``
gathers them so, a chunk at a time, and is the one caller). The experts'
``body`` is a static choice of ``BODIES``: ``swiglu`` (three weights:
``h = silu(x Wg) * (x Wu)``) or ``relu2`` (two, no gate: ``h = relu(x
Wu)^2``), in one tile walk, one set of tables and carries.

A chunk is ``m`` row tiles (``row_tile`` rows each), each tile inside ONE
expert's run (a run's last tile part empty, and the chunk's last tiles
wholly where the rows end before it); a kernel's grid walks the chunk's
tiles and reads, per tile, four numbers from scalar-prefetched tables
(``tile_tables``): its expert, how many of its rows are live, the tile
whose blocks it names, and what to do with the expert's ``dW`` before it.
Consecutive tiles of one expert name the same weight blocks, so the
pipeline fetches an expert's weights (``w_gate``, ``w_up``, ``w_down``, or
the two of ``relu2``) ONCE and they stay in VMEM while its tiles pass. A
tile with no live row names the blocks of the last live tile before it
and runs nothing: no fetch, no product, no store.

``ds_moe_gmm_fwd`` (``forward``): ``gate`` and ``up`` in float32 from the
bf16 rows, ``h = silu(gate) * up`` (``relu2``: ``relu(up)^2``) rounded once
to the rows' dtype, the down projection in float32, times the row's
routing weight (0 past an expert's count), all in VMEM: ``h`` never goes
to HBM, and the rows leave in float32, as the caller's add to tokens takes
them.

``ds_moe_gmm_bwd`` (``backward``): the same walk. ``gate`` and ``up`` are
made again; ``dh = (dy @ w_down^T) * weight`` in float32 (``relu2``:
``d(x Wu) = 2 relu(x Wu) dh``); the row's
``dy . y`` (the routing weight's gradient, only where ``router_grad``) is
``sum(dy @ w_down^T * h)``, so ``y`` is not made again; ``dx`` a tile; the
expert's ``dW`` are summed in float32 IN their output blocks, which
stay in VMEM across the expert's tiles and go to HBM once when the expert
changes. The outputs alias the float32 carries the caller's loop holds: an
expert the chunk does not reach keeps what it had, and one that began in
an earlier chunk takes its sums up from the carry by one copy.

``ds_moe_add_rows`` (``add_rows``): the add of a chunk's float32 rows to
their tokens. The rows come in TOKEN order (the caller's one gather), so a
token's rows are consecutive; the grid walks the pairs (tile of tokens, row
tile) that meet, from tables as above (``add_tables``), and a pair's
segment sum is an exact 0/1 product on the MXU: ``onehot[T, R] @ rows[R,
D]`` with the float32 rows in three bf16 pieces and float32 sums. A tile
of the float32 ``[N, D]`` carry stays in VMEM while its pairs pass and is
written ONCE; the output aliases the carry, so a tile that no row of the
chunk reaches stays in place, and a sweep's first chunk does not read the
carry at all (it is zeros).

**One trace a shape** (``_common._bind``). Operands in the rows' dtype,
float32 accumulation. On the chip the widths are multiples of 128 and the
tile of 8 (16 in bf16); interpret mode (any other backend, the tests)
takes any shape.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _bind, _dot, _interpret, _nbytes, _pieces

ROW_TILE = 256      # rows a grid step, at most
ADD_TILE = 128      # ``add_rows``: tokens a tile of the carry and rows a
#                     row tile, at most. Its blocks and temporaries fit the
#                     16 MiB of VMEM every XLA op may use, so the kernel
#                     asks for no more: a kernel that does, as the last op
#                     of the sweep's loop body, keeps XLA from bringing
#                     ``x`` back into VMEM behind it for the next chunk's
#                     row gather (7 ns a row from there, 36 from HBM:
#                     PERF.md section 6, PR 48)

_RESIDENT_MAX = 100 << 20   # ``backward``: an expert's blocks in VMEM
_VMEM_MAX = 126 << 20       # what a kernel may ask

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_ZERO, _CARRY = 1, 2                # a tile's ``init``: the dW before it

# an expert's body -> its weights' names, the down projection last (the
# others are [D, F], cut by columns; it is [F, D], cut by rows)
BODIES = {"swiglu": ("w_gate", "w_up", "w_down"),
          "relu2": ("w_up", "w_down")}


def row_tile(block: int) -> int:
    """Rows a grid step: the largest multiple of 128 up to ``ROW_TILE``
    that divides the block, else the block."""
    return next((t for t in range(ROW_TILE, 0, -128) if block % t == 0),
                block)


def tile_tables(expert, live, begun, m: int):
    """The kernels' tables [tiles] (int32) from the layout's row tiles
    (whole chunks of ``m``): the ``expert`` held (any value where ``live``
    is 0), the ``live`` rows of the tile, and whether the expert's run had
    ``begun`` before it. Returns (expert of the tile, the tile OF ITS
    CHUNK whose blocks it names, its live rows, init: ``_ZERO`` at an
    expert's first tile, ``_CARRY`` at a later one that opens a chunk,
    else 0)."""
    i32 = jnp.int32
    n = expert.shape[0]
    t = jnp.tile(jnp.arange(m, dtype=i32), n // m)
    src = jax.lax.cummax(jnp.where(live > 0, t, 0).reshape(-1, m),
                         axis=1).reshape(-1)
    of_tile = expert[jnp.arange(n, dtype=i32) - t + src]
    opens = ((t == 0) | ~begun) & (live > 0)
    init = jnp.where(opens, jnp.where(begun, _CARRY, _ZERO), 0)
    return (of_tile.astype(i32), src.astype(i32), live.astype(i32),
            init.astype(i32))


def _params(resident_bytes: int, tile: int, row_bytes: int = 64 << 10,
            axes: int = 1):
    # what stays in VMEM (weights and dW blocks, two buffers each) and
    # room for the row tiles and the float32 temporaries (``row_bytes`` a
    # row: 32 B a channel at hidden 2048)
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * axes,
        vmem_limit_bytes=int(min(
            resident_bytes + (16 << 20) + tile * row_bytes, _VMEM_MAX)))


def _rows_spec(tile, width):
    return pl.BlockSpec((tile, width), lambda t, e, src, *_: (src[t], 0))


def _expert_spec(shape, **kw):
    return pl.BlockSpec((1, *shape), lambda t, e, *_: (e[t], 0, 0), **kw)


# ---------------------------------------------------------------- forward
def _fwd_kernel(e_ref, src_ref, live_ref, xs_ref, scale_ref, *refs,
                body: str):
    del e_ref, src_ref
    *w_refs, wd_ref, y_ref = refs

    @pl.when(live_ref[pl.program_id(0)] > 0)
    def _():
        x = xs_ref[...]
        if body == "swiglu":
            wg_ref, wu_ref = w_refs
            gate = _dot(x, wg_ref[0])
            h = (jax.nn.silu(gate) * _dot(x, wu_ref[0])).astype(x.dtype)
        else:
            wu_ref, = w_refs
            h = jnp.square(jnp.maximum(_dot(x, wu_ref[0]), 0.0)).astype(
                x.dtype)
        y_ref[...] = (_dot(h, wd_ref[0]) * scale_ref[...]).astype(
            y_ref.dtype)


def forward(xs, scale, tables, experts, tile: int, body: str = "swiglu"):
    """``scale * E(xs)`` [C, D] float32 for a chunk's rows
    ``xs`` [C, D] in expert order, ``scale`` [C, 1] float32 (the routing
    weight; 0 where the row is not the expert's), ``tables`` =
    ``tile_tables``' first three, ``experts`` the held weights
    ``[E_h, ...]`` of ``BODIES[body]``. The rows of a tile that did not run
    are not written."""
    c, d = xs.shape
    w = [experts[n] for n in BODIES[body]]
    f = w[0].shape[-1]
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, body=body),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(c // tile,),
            in_specs=[_rows_spec(tile, d), _rows_spec(tile, 1),
                      *(_expert_spec((d, f)) for _ in w[:-1]),
                      _expert_spec((f, d))],
            out_specs=_rows_spec(tile, d)),
        out_shape=jax.ShapeDtypeStruct((c, d), jnp.float32),
        compiler_params=_params(2 * _nbytes(*w) // w[0].shape[0], tile),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * len(w) * c * d * f),
            transcendentals=int(c * f) if body == "swiglu" else 0,
            bytes_accessed=int(3 * _nbytes(xs) + _nbytes(scale, *w))),
        interpret=_interpret(),
        name="ds_moe_gmm_fwd",
    )
    # the scope and the kernel's name are all a device trace shows of this
    # call (telemetry/scopes.py)
    return _bind(call, "ds.moe_gmm_fwd", ("fwd", tile, body), *tables, xs,
                 scale, *w)[0]


# --------------------------------------------------------------- backward
def _bwd_kernel(e_ref, src_ref, live_ref, init_ref, xs_ref, dys_ref,
                scale_ref, *refs, router_grad: bool, cols: int, body: str):
    del src_ref
    n = len(BODIES[body])
    w_refs, c_refs = refs[:n], refs[n:2 * n]
    dxs_ref, dwt_ref, *d_refs, sem = refs[2 * n:]
    wd_ref, dd_ref = w_refs[-1], d_refs[-1]
    # the row tiles are the grid's last axis; in front of it, where the
    # expert's columns are cut, the cut (``backward``)
    t = pl.program_id(0 if cols == 1 else 1)
    run = pl.program_id(0) if cols > 1 else 0
    # (carry, block, the carry's axis the cut runs along)
    sums = tuple(zip(c_refs, d_refs, (2,) * (n - 1) + (1,)))

    @pl.when(init_ref[t] == _ZERO)
    def _():
        for _, ref, _ in sums:
            ref[...] = jnp.zeros(ref.shape, ref.dtype)

    @pl.when(init_ref[t] == _CARRY)
    def _():
        def part(carry, ref, axis):
            if cols == 1:
                return carry.at[pl.ds(e_ref[t], 1)]
            at = [pl.ds(e_ref[t], 1), slice(None), slice(None)]
            width = ref.shape[axis]
            at[axis] = pl.ds(run * width, width)
            return carry.at[tuple(at)]

        copies = [pltpu.make_async_copy(part(carry, ref, axis), ref,
                                        sem.at[i])
                  for i, (carry, ref, axis) in enumerate(sums)]
        for copy in copies:
            copy.start()
        for copy in copies:
            copy.wait()

    @pl.when(live_ref[t] > 0)
    def _():
        x, dy, scale = xs_ref[...], dys_ref[...], scale_ref[...]
        if body == "swiglu":
            wg_ref, wu_ref = w_refs[:2]
            dg_ref, du_ref = d_refs[:2]
            gate, up = _dot(x, wg_ref[0]), _dot(x, wu_ref[0])
            sg = jax.nn.sigmoid(gate)
            act = gate * sg
            h = act * up
        else:
            wu_ref, du_ref = w_refs[0], d_refs[0]
            up = jnp.maximum(_dot(x, wu_ref[0]), 0.0)
            h = up * up
        dh = _dot(dy, wd_ref[0], _NT)
        if router_grad:
            dwt_ref[...] = jnp.sum(dh * h, axis=1, keepdims=True)
        dh = dh * scale
        if body == "swiglu":
            d_up = (dh * act).astype(x.dtype)
            d_gate = (dh * up * (sg * (1 + gate * (1 - sg)))).astype(x.dtype)
            dxs_ref[...] = (_dot(d_gate, wg_ref[0], _NT)
                            + _dot(d_up, wu_ref[0], _NT)).astype(
                                dxs_ref.dtype)
            dg_ref[0] += _dot(x, d_gate, _TN)
        else:
            d_up = (2.0 * up * dh).astype(x.dtype)
            dxs_ref[...] = _dot(d_up, wu_ref[0], _NT).astype(dxs_ref.dtype)
        du_ref[0] += _dot(x, d_up, _TN)
        dd_ref[0] += _dot((h * scale).astype(x.dtype), dy, _TN)


def backward_geometry(d: int, f: int, tile: int, itemsize: int,
                      mats: int = 3):
    """How ``backward`` holds ONE expert of ``mats`` matrices of ``d`` by
    ``f`` in VMEM, from the shapes alone: (column runs the expert is cut
    into, buffers its weights take, bytes resident). An expert's weights and its float32
    ``dW`` blocks take two buffers each; past ``_RESIDENT_MAX`` (hidden
    2048 by an expert of 1536: 113 MiB, and the kernel asked 127.3 of the
    126 it may have) the weights take ONE: the next expert's are fetched
    when its first tile arrives and not behind the last tile before it (23
    us an expert at 819 GB/s). Where even that, with the row tiles' share,
    is more than a kernel may ask (hidden 3584 by 1024: 105 MiB and 44
    more), the expert is cut by COLUMNS of ``f`` (``w_gate``, ``w_up`` and
    their ``dW`` by columns, ``w_down`` and its ``dW`` by rows), into the
    fewest runs, a power of two of whole 128-lane blocks, that fit: every
    product of the backward but ``dx`` and the row's ``dy . y`` is a
    column's own, so a run walks the chunk's row tiles as the whole expert
    would, and the caller sums the runs' ``dx`` and ``dy . y``."""
    def held(cols):
        weights = mats * d * f * itemsize // cols
        sums = mats * d * f * 4 // cols
        resident, buffers = 2 * (weights + sums), 2
        if resident > _RESIDENT_MAX:
            resident, buffers = resident - weights, 1
        return buffers, resident

    cols = 1
    while (held(cols)[1] + (16 << 20) + tile * 32 * d > _VMEM_MAX
           and f % (256 * cols) == 0):   # what ``_params`` would ask
        cols *= 2
    return (cols, *held(cols))


def backward(xs, dys, scale, tables, experts, sums, tile: int,
             router_grad: bool, body: str = "swiglu"):
    """The chunk's part of the backward: (``dxs`` [C, D] float32 (the
    add to tokens takes float32 rows: a kernel's own sums, not rounded on
    the way), ``dwt`` [C, 1] float32: the row's ``dy . E(xs)`` where
    ``router_grad``, else not written, and the float32 ``dW`` sums
    ``[E_h, ...]`` of ``BODIES[body]``: ``sums`` with this chunk's rows
    added). ``dys`` [C, D] is the result's cotangent by row; the rest as
    ``forward``, ``tables`` all four."""
    c, d = xs.shape
    w = [experts[n] for n in BODIES[body]]
    n = len(w)
    f = w[0].shape[-1]
    carry = pl.BlockSpec(memory_space=pl.ANY)
    cols, buffers, resident = backward_geometry(
        d, f, tile, jnp.dtype(w[0].dtype).itemsize, n)
    once = {"pipeline_mode": pl.Buffered(1)} if buffers == 1 else {}
    fc = f // cols
    # index maps take (column run j, row tile t, the tables); the whole
    # expert's grid has no axis for j
    step = (lambda fn: fn) if cols > 1 else (
        lambda fn: lambda t, *tables: fn(0, t, *tables))
    rows = lambda width: pl.BlockSpec(  # noqa: E731
        (tile, width), step(lambda j, t, e, src, *_: (src[t], 0)))
    by_columns = lambda **kw: pl.BlockSpec(  # noqa: E731
        (1, d, fc), step(lambda j, t, e, *_: (e[t], 0, j)), **kw)
    by_rows = lambda **kw: pl.BlockSpec(  # noqa: E731
        (1, fc, d), step(lambda j, t, e, *_: (e[t], j, 0)), **kw)
    if cols == 1:
        grid, slab, lead = (c // tile,), rows, ()
        params = _params(resident, tile)
    else:
        # a run's dx and dy . y go to a slab of their own
        grid, lead = (cols, c // tile), (cols,)
        slab = lambda width: pl.BlockSpec(  # noqa: E731
            (None, tile, width), lambda j, t, e, src, *_: (j, src[t], 0))
        params = _params(resident, tile, 32 * d, 2)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, router_grad=router_grad, cols=cols,
                          body=body),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=grid,
            in_specs=[rows(d), rows(d), rows(1),
                      *(by_columns(**once) for _ in w[:-1]), by_rows(**once),
                      *(carry for _ in w)],
            out_specs=[slab(d), slab(1), *(by_columns() for _ in w[:-1]),
                       by_rows()],
            scratch_shapes=[pltpu.SemaphoreType.DMA((n,))]),
        out_shape=[jax.ShapeDtypeStruct((*lead, c, d), jnp.float32),
                   jax.ShapeDtypeStruct((*lead, c, 1), jnp.float32),
                   *(jax.ShapeDtypeStruct(s.shape, s.dtype) for s in sums)],
        # the carries (behind the 4 tables, the 3 row operands and the
        # weights) alias the dW outputs (behind dxs and dwt)
        input_output_aliases={7 + n + i: 2 + i for i in range(n)},
        compiler_params=params,
        cost_estimate=pl.CostEstimate(
            # the input matmuls again, then two products a matrix
            flops=int((6 * n - 2) * c * d * f),
            transcendentals=int(c * f) if body == "swiglu" else 0,
            bytes_accessed=int(4 * _nbytes(xs) + _nbytes(scale, *w)
                               + 2 * _nbytes(*sums))),
        interpret=_interpret(),
        name="ds_moe_gmm_bwd",
    )
    dxs, dwt, *sums = _bind(call, "ds.moe_gmm_bwd",
                            ("bwd", tile, router_grad, body), *tables, xs,
                            dys, scale, *w, *sums)
    if cols > 1:
        dxs, dwt = dxs.sum(axis=0), dwt.sum(axis=0)
    return dxs, dwt, sums


# ------------------------------------------------------ the add to tokens
def add_tables(tokens, n: int, t: int, r: int, fresh):
    """``add_rows``' tables [n / t + C / r] (int32) from a chunk's
    ``tokens`` [C] in ascending order (``n`` or more where a row is no
    one's: last): a grid step is one pair (tile of ``t`` tokens, row tile
    of ``r`` rows) that holds rows of those tokens, the pairs of a token
    tile one after another and the token tiles ascending (a merge: each
    step opens a token tile or a row tile, so there are fewer pairs than
    steps). Returns (the pair's token tile, its row tile, the tile of the
    carry it READS, init: ``_ZERO`` or ``_CARRY`` at a token tile's first
    pair, whether it is live). The steps past the last pair name its
    blocks and run nothing; ``fresh`` (a traced bool: the carry is zeros)
    makes every step read the carry's tile 0, which is fetched once and
    not looked at."""
    i32 = jnp.int32
    n_t, n_r = n // t, tokens.shape[0] // r
    edges = jnp.arange(n_t + 1, dtype=i32) * t
    lo = jnp.sum(tokens[None, :] < edges[:, None], axis=1, dtype=i32)
    first = lo[:-1] // r
    pairs = jnp.where(lo[1:] > lo[:-1], (lo[1:] - 1) // r - first + 1, 0)
    ends = jnp.cumsum(pairs)
    s = jnp.arange(n_t + n_r, dtype=i32)
    live = s < ends[-1]
    at = jnp.maximum(jnp.minimum(s, ends[-1] - 1), 0)
    tt = jnp.minimum(jnp.sum(at[:, None] >= ends[None, :], axis=1,
                             dtype=i32), n_t - 1)
    begin = ends[tt] - pairs[tt]
    rt = jnp.minimum(first[tt] + at - begin, n_r - 1)
    # step 0 opens its tile whatever the chunk holds: the output block of
    # a grid is written back even where no step stored to it
    opens = (live & (at == begin)) | (s == 0)
    init = jnp.where(opens, jnp.where(fresh, _ZERO, _CARRY), 0)
    return tt, rt, jnp.where(fresh, 0, tt), init, live.astype(i32)


def _add_kernel(tt_ref, rt_ref, ct_ref, init_ref, live_ref, rows_ref,
                tok_ref, carry_ref, out_ref):
    del rt_ref, ct_ref
    s = pl.program_id(0)

    @pl.when(init_ref[s] == _ZERO)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(init_ref[s] == _CARRY)
    def _():
        out_ref[...] = carry_ref[...]

    @pl.when(live_ref[s] > 0)
    def _():
        t, r = out_ref.shape[0], rows_ref.shape[0]
        token = tt_ref[s] * t + jax.lax.broadcasted_iota(jnp.int32, (t, r), 0)
        onehot = jnp.where(tok_ref[0] == token, 1.0, 0.0).astype(jnp.bfloat16)
        # 0/1 times a bf16 piece is exact, the sums are float32: the three
        # pieces' products add up to the rows' own float32 sum
        hi, mid, low = _pieces(rows_ref[...], jnp.bfloat16)
        out_ref[...] += (_dot(onehot, low) + _dot(onehot, mid)
                         + _dot(onehot, hi))


def add_rows(acc, rows, tokens, fresh):
    """``acc`` [N, D] float32 with ``rows[i]`` added to ``acc[tokens[i]]``:
    ``rows`` [C, D] float32 in TOKEN order (``tokens`` [C] int32 ascending;
    ``N`` or more where a row is no one's, which come last and may hold
    anything finite), ``fresh``: a traced bool, ``acc`` is zeros and is
    not read. ``acc`` is given up to the result (an aliased output): each
    tile of it that the rows reach is written once, the others stay. The
    tiles come from the shapes: ``ADD_TILE`` tokens and rows at most, the
    largest that divide ``N`` and ``C``."""
    n, d = acc.shape
    c = rows.shape[0]
    t, r = math.gcd(n, ADD_TILE), math.gcd(c, ADD_TILE)
    tables = add_tables(tokens, n, t, r, fresh)
    call = pl.pallas_call(
        _add_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(n // t + c // r,),
            in_specs=[
                pl.BlockSpec((r, d), lambda s, tt, rt, *_: (rt[s], 0)),
                pl.BlockSpec((1, 1, r), lambda s, tt, rt, *_: (rt[s], 0, 0)),
                pl.BlockSpec((t, d), lambda s, tt, rt, ct, *_: (ct[s], 0))],
            out_specs=pl.BlockSpec((t, d), lambda s, tt, *_: (tt[s], 0))),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=int(6 * (n // t + c // r) * t * r * d), transcendentals=0,
            bytes_accessed=int(_nbytes(rows) + 2 * _nbytes(acc))),
        interpret=_interpret(),
        name="ds_moe_add_rows",
    )
    return _bind(call, "ds.moe_add_rows", ("add", t, r), *tables, rows,
                 tokens.reshape(c // r, 1, r), acc)[0]

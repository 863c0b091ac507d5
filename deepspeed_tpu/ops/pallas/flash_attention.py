"""Pallas flash attention for TPU (causal, GQA-aware).

TPU-native replacement for the reference's fused attention CUDA kernels
(csrc/transformer/softmax_kernels.cu + inference blocked_flash): one
kernel streams k/v blocks through VMEM with online-softmax accumulation,
never materializing the [S, S] score matrix; a custom VJP recomputes
probabilities blockwise in the backward (flash-attention-2 style).

Design notes (why this beats the stock two-pass kernel at model shapes):

- **One-pass backward**: dq, dk and dv are produced in a single sweep
  over (kv-block, q-block) pairs, so the score matrix is recomputed once
  per block pair instead of twice (the stock dq-then-dkv design runs the
  s/p matmuls in both passes). The TPU Pallas grid executes sequentially
  on the core, so the full [S, D] dq for the current (batch, head) stays
  resident in VMEM as an output block whose index map depends only on
  the batch*head grid axis, accumulating across every step.
- **Inner loop in-kernel**: the grid iterates (bh, block); the opposing
  operand (k/v in forward, q/do in backward) is VMEM-resident for the
  whole row and swept with a `lax.fori_loop` whose trip count starts at
  the causal boundary — no wasted grid steps, and Mosaic pipelines the
  per-block DMAs against the loop body.
- **bf16 MXU operands** with f32 accumulation (`preferred_element_type`);
  p/ds are cast back to the input dtype before their dots (upcasting
  operands to f32 would halve the MXU rate).
- **Tile classes**: with bq == bk a score tile's class depends only on
  its block distance d = q block - kv block (`tile_bands`): *skipped*
  (no live pair: above the causal diagonal, or wholly past the window's
  far edge; the sweep's trip count leaves it out), *masked* (cut by the
  diagonal, d == 0, or by the window's far edge) and *unmasked* (every
  pair live). Each sweep is two or three `fori_loop` ranges over one
  body parametrised at trace time, so an unmasked tile carries no mask
  code at all (no iota, compare or select). At seq 8192, block 512,
  window 4096 a head runs 108 tiles: 24 masked, 84 unmasked, 28 skipped
  under the diagonal (gauge `ds_flash_tiles`). `where(True, s, NEG_INF)`
  is `s`, so the result is bit-equal to masking every tile. On a v5e the
  mask is a small part of a tile (4% of the forward, 1% of the backward:
  the vector unit has slots to spare); what a tile waits for is the
  serial chain matmul, row statistics, exp, matmul, and its relayouts.
- **The band**: square tiles follow a window badly once it is as narrow
  as they are. At a window of one block (512 at seq 8192) every query
  block meets two tiles and both are masked: 31 tiles, 8.1 M pairs swept
  a head for the 4.06 M the mask leaves live. So where the window is at
  most two blocks wide (`band_rows`; both are known at trace time) a grid
  step runs no loop: its block goes by groups of ``t`` rows, straight-line
  code, and a group meets ONE span of ``round_up(window, t) + t`` rows of
  the opposing operand (`band_span`: keys ``(r0 - window, r0 + t)`` of a
  query group, queries ``[c0, c0 + t + window - 1)`` of a key group),
  held inside the row at its ends. It is one call of the SAME body, with
  the span in the place of a tile and the one mask `_cut` gives from the
  two groups' distance, from the empty carry: the forward's online
  rescale degenerates to one softmax pass. 5.2 M pairs a head at seq
  8192, window 512; 18.9 M for 24.4 M at seq 16384, window 1024 (gauge
  `ds_flash_pairs`). The grid, the BlockSpecs and the `custom_vjp` are
  the loops'; a wider window, or none, traces to the loops' program.
- **No lane-by-lane relayouts**: the row statistics live as columns
  ([bq, 1]) in the forward and are stored lane-dense ([1, S]); the
  forward turns them once a q block through the transpose unit, and the
  backward holds its tile transposed ([bk, bq]) so that lse and delta
  broadcast as the rows they are, p and ds are already the left operands
  of the dv and dk matmuls, and one in-loop transpose (for dq) is left
  of two. `jnp.dot(a, b.T)` needs no rewriting: Mosaic lowers it to a
  matmul that latches b transposed.

Layout: wrapper takes [B, S, H, D] (model convention), kernels run on
[B*H, S, D]. The log-sum-exp is carried as [BH, 1, S] so every block
spec is TPU-legal ((1, 1, bq) blocks). VMEM residency caps the row the
kernels hold whole (`_resident_max_seq`, by the bytes the backward holds
at the key and value widths). Past it a windowless call runs the SAME two
kernels on equal spans of the row that fit (`segments`, `_spans_fwd`,
`_spans_bwd`): a query span meets every key span at or under it (the
diagonal pairs causal, the others whole), a span's partial outputs are
merged by their log-sum-exp, and the backward runs a pair at a time
against the row's own ``lse`` and ``delta``, which makes ``dq`` additive
over key spans and ``dk``, ``dv`` over query spans. The pairs sweep what
one long row would (the same tiles, `span_counts`); what is added is the
merge, under scope ``ds.flash_merge``. A WINDOW past the cap is the one
call left to the exact form (no configuration has one).

On non-TPU backends the kernels run in Pallas interpret mode (tests), so
the same code path is exercised everywhere.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _interpret, _keep, _registry

NEG_INF = -1e30
_LANES = 128          # a vector register's minor dimension

# k/v (fwd) and q/do/dq (bwd) are VMEM-resident per (batch*head) row. The
# reason for the resident form is one pass over k/v where the stock kernel
# makes two; a run before this round's records read 1.38x the stock
# kernel's training throughput at seq 32768 x d128. What a row costs is
# what the BACKWARD holds of it (the forward holds k and v alone): q and do
# in their own dtype and the float32 dq slab, each at its lanes PADDED to
# whole 128-lane tiles (a head of 64 takes a head of 128's VMEM, one of 192
# one of 256's) and each in the pipeline's two buffers, beside the two
# float32 rows of statistics. Mosaic's default 16 MiB scoped ceiling trips
# at any long row: the kernels ask for `_VMEM_LIMIT` (v5e / v5p have 128
# MiB), and a row may take that less `_VMEM_REST`, what a grid step holds
# beside it (the k / v / dk / dv blocks in two buffers, the [512, 512]
# float32 tiles of the body). Compiled for a described v5e at bf16 (PR 64):
# a key of 64 or 128 compiles to 57344 rows and not 58368, 192 / 128 to
# 31744 and not 32768, 256 to 28672 and not 29696; the caps here are
# 47662, 27594 and 24197. The rule this replaces, s x d <= 32768 x 128
# unpadded, admitted 65536 x 64, which asks 132 of 128 MiB.
_VMEM_LIMIT = 100 * 1024 * 1024
_VMEM_REST = 4 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _row_vmem_bytes(d: int, dv: int, itemsize: int = 2) -> int:
    """VMEM bytes a position of a row costs the backward kernel."""
    pad = lambda w: -(-max(w, 1) // _LANES) * _LANES  # noqa: E731
    slabs = itemsize * (pad(d) + pad(dv)) + 4 * pad(d)      # q, do; dq
    return 2 * slabs + 64       # lse and delta: 16 B a position a buffer


def _resident_max_seq(d: int, dv: int | None = None,
                      itemsize: int = 2) -> int:
    """The longest row the kernels hold whole at a key of ``d`` and a value
    of ``dv`` (``d``'s where not given)."""
    return (_VMEM_LIMIT - _VMEM_REST) // _row_vmem_bytes(
        d, d if dv is None else dv, itemsize)


def segments(s: int, d: int, dv: int | None = None, itemsize: int = 2) -> int:
    """Equal spans a row of ``s`` is run in: 1 at or under the cap; past it
    the fewest that fit (2 at 32768 x 192 / 128), more where the spans
    would not be whole 128-row blocks."""
    n = -(-s // _resident_max_seq(d, dv, itemsize))
    while n > 1 and s % (n * 128):
        n += 1
    return n


def _gauge_segments(s: int, d: int, n: int):
    reg = _registry()
    if reg is not None:
        reg.gauge("ds_flash_segments",
                  "equal spans a (batch x head) row of the flash call last "
                  "built is run in: 1 where the kernels hold the row whole, "
                  "more past the residency cap (every pair of spans the "
                  "mask leaves is one call of the same kernels)"
                  ).set(n, s=str(s), d=str(d))


def _block(s: int) -> int:
    """Largest of 512/256/128 dividing s (wrapper guarantees s % 128 == 0
    or s <= 128)."""
    for b in (512, 256, 128):
        if s % b == 0:
            return b
    return s


# ------------------------------------------------------------ tile classes
TILE_KINDS = ("masked", "unmasked", "skipped")


def tile_bands(s: int, b: int, window: int | None):
    """``(edge, dead)``: the class of a [b, b] score tile from its block
    distance ``d = q block - kv block`` (its pairs' ``row - col`` span
    ``d*b - (b-1) .. d*b + (b-1)``). Skipped (no live pair) iff
    ``causal and d < 0`` or ``d >= dead``; masked (cut) iff ``causal and
    d == 0`` (the diagonal) or ``edge <= d < dead`` (the window's far
    edge: some ``row - col >= window``); every other tile is wholly live.
    Without a window both are ``s // b``, past every tile."""
    if window is None:
        return s // b, s // b
    return window // b, (window - 2) // b + 2


def tile_kind(d: int, edge: int, dead: int, causal: bool) -> str:
    if (causal and d < 0) or d >= dead:
        return "skipped"
    if (causal and d == 0) or d >= edge:
        return "masked"
    return "unmasked"


def tile_counts(s: int, b: int, window: int | None, causal: bool) -> dict:
    """Tiles of each kind that one (batch x head) row's sweep meets; the
    triangle above a causal diagonal was never swept and is not counted,
    so ``skipped`` is what the window saves."""
    n = s // b
    edge, dead = tile_bands(s, b, window)
    out = dict.fromkeys(TILE_KINDS, 0)
    for d in range(0 if causal else 1 - n, n):
        out[tile_kind(d, edge, dead, causal)] += n - abs(d)
    return out


def _fwd_ranges(i, edge: int, dead: int):
    """kv sweep of q block ``i`` (causal): ``[lo, a)`` is cut by the
    window's far edge, ``[a, i)`` is wholly live, tile ``i`` is on the
    diagonal."""
    lo = jnp.maximum(0, i - dead + 1)
    return lo, jnp.clip(i - edge + 1, lo, i)


def _bwd_ranges(j, n: int, edge: int, dead: int):
    """q sweep of kv block ``j`` (causal): tile ``j`` is on the diagonal,
    ``(j, c)`` is wholly live, ``[c, hi)`` is cut by the window's far
    edge."""
    hi = jnp.minimum(n, j + dead)
    return jnp.clip(j + edge, j + 1, hi), hi


def _cut(s, off, window, q_axis: int):
    """Scores of a tile the mask cuts. Queries run along ``q_axis`` of the
    tile, keys along the other; ``off`` is the first query's position less
    the first key's, so ``rel`` is each pair's ``query - key``, live in
    ``[0, window)``. The iotas are left to the compiler: the vector unit
    has slots to spare in these loops and vector stores have none, so a
    position array built once per invocation and kept in VMEM measured
    slower than building it a tile."""
    rel = (jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
           - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)) + off
    live = rel >= 0
    if window is not None:
        live &= rel < window
    return jnp.where(live, s, NEG_INF)


# ------------------------------------------------------------------ the band
# rows of a group: the fewest pairs swept past the mask at whole 128-lane
# rows of statistics. On a v5e, forward + backward a head against the loops:
# -30.6% at seq 8192, window 512 and -28.5% at 16384, 1024; at 256 rows the
# score tile spills twice as often and the two shapes read 4.6% and 0.4% behind
# (PERF.md section 6, PR 61)
_BAND_ROWS = 128


def band_rows(b: int, window: int | None, causal: bool) -> int | None:
    """Rows ``t`` of a band group (queries in the forward, keys in the
    backward), or None where the sweep is the loops' (no window, or one
    past two blocks). A group of ``t`` rows meets ONE span of ``band_span``
    rows of the opposing operand under one mask, in place of the block's
    two or three [b, b] tiles, every one of them masked."""
    if not causal or window is None or window > 2 * b:
        return None
    return min(b, _BAND_ROWS)


def band_span(s: int, t: int, window: int) -> int:
    """Rows of the opposing operand a group meets: a query of the group
    sees keys in ``(r0 - window, r0 + t)`` and a key is seen by queries in
    ``[c0, c0 + t + window - 1)``; both fit ``t``-aligned in this many."""
    return min(s, -(-window // t) * t + t)


def _fwd_span(g, t: int, span: int):
    """First key group of the span of query group ``g`` (units of ``t``):
    the span ends with the group's own keys, held at the row's start."""
    return jnp.maximum(0, g + 1 - span // t)


def _bwd_span(g, s: int, t: int, span: int):
    """First query group of the span of key group ``g``: the span starts
    with the group's own queries, held at the row's end."""
    return jnp.minimum(g, (s - span) // t)


def pair_counts(s: int, b: int, window, causal: bool) -> dict:
    """Pairs a (batch x head) row's sweep computes (``swept``: the loops'
    tiles, or the band's groups by their spans) and pairs the mask leaves
    ``live``."""
    t = band_rows(b, window, causal)
    if t is None:
        tiles = tile_counts(s, b, window, causal)
        swept = (tiles["masked"] + tiles["unmasked"]) * b * b
    else:
        swept = s * band_span(s, t, window)
    w = s if window is None else min(window, s)
    live = w * (w + 1) // 2 + (s - w) * w if causal else s * s
    return {"swept": swept, "live": live}


def span_counts(count, s: int, span: int, causal: bool) -> dict:
    """``count(span, causal)`` (a dict of numbers a row) summed over the
    pairs of spans a row of ``s`` cut in spans of ``span`` runs
    (`_span_pairs`: the diagonal pairs causal, the others whole)."""
    total = dict.fromkeys(count(span, causal), 0)
    for _, _, diagonal in _span_pairs(s // span, causal):
        for kind, n in count(span, diagonal).items():
            total[kind] += n
    return total


def _gauge_tiles(kernel: str, s: int, b: int, window, causal: bool,
                 span: int | None = None):
    """Trace time, host only: how often the maskless body engages, and how
    many of the pairs a sweep computes the mask leaves live, are functions
    of shapes, so they are counted where the kernel is built. The band's
    groups count as masked tiles. A row cut in spans of ``span``
    (windowless: `_spans_fwd`) counts what its pairs of spans sweep, all
    of them together."""
    reg = _registry()
    if reg is None:
        return
    g = reg.gauge("ds_flash_tiles",
                  "score tiles a (batch x head) row of the flash kernel "
                  "last built runs masked / unmasked / skips by the window")
    t = band_rows(b, window, causal)
    if span is not None:
        tiles = span_counts(lambda n, c: tile_counts(n, b, None, c), s, span,
                            causal)
    else:
        tiles = (tile_counts(s, b, window, causal) if t is None else
                 dict(zip(TILE_KINDS, (s // t, 0, 0))))
    for kind, n in tiles.items():
        g.set(n, kernel=kernel, kind=kind)
    g = reg.gauge("ds_flash_pairs",
                  "query-key pairs a (batch x head) row of the flash kernel "
                  "last built computes (swept) / the mask leaves live")
    pairs = (pair_counts(s, b, window, causal) if span is None else
             span_counts(lambda n, c: pair_counts(n, b, None, c), s, span,
                         causal))
    for kind, n in pairs.items():
        g.set(n, kernel=kernel, kind=kind)


# ---------------------------------------------------------------- forward
def _flash_fwd(q, k, v, *, causal: bool, sc: float,
               window: int | None = None, rep: int = 1, part: bool = False):
    """``rep``: GQA group size — q rows are [B*Hq, S, D], k/v rows
    [B*Hkv, S, D]; the kv index maps divide the q-head grid index by
    ``rep`` instead of materializing repeated k/v. ``part``: one pair of
    spans of a longer row (`_spans_fwd`): ``o`` stays float32 for the
    merge, and the row's gauges are the caller's."""
    bh, s, d = q.shape
    dv = v.shape[-1]        # the value width may differ from the key's
    bq = bk = _block(s)
    grid = (bh, s // bq)
    if not part:
        _gauge_tiles("fwd", s, bq, window, causal)
    kernel = functools.partial(_fwd_kernel, sc=sc, bq=bq, bk=bk,
                               nk=s // bk, causal=causal, window=window)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s, d), lambda b, i: (b // rep, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s, dv), lambda b, i: (b // rep, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
        name="ds_flash_fwd",
    )
    # the scope and the kernel's name are all a device trace shows of this
    # call (telemetry/scopes.py); both are HLO metadata and cost no time
    with jax.named_scope("ds.flash_fwd"):
        o, lse = call(q, k, v)
    return (o if part else o.astype(q.dtype)), lse


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sc, bq, bk, nk,
                causal, window):
    """Online-softmax forward: q block vs the VMEM-resident k/v row.
    ``window`` (Mistral SWA): query r sees keys in (r - window, r] — the
    kv sweep starts at the window's first live block, and only the tiles
    the diagonal or the window's far edge cuts carry the mask. Under a
    narrow window (`band_rows`) the block's rows go by groups, each
    against its one key span."""
    i = pl.program_id(1)
    d = v_ref.shape[-1]

    def body(j, carry, *, q, qi, step, n, masked):
        # keys [j * step, j * step + n) against the rows ``q``, whose first
        # is row ``qi * step``: a [bk, bk] tile of the loops (step = n =
        # bk), or a group's span of the band (step = t, n = span)
        o_acc, m, l = carry
        kj = k_ref[0, pl.ds(j * step, n), :]
        vj = v_ref[0, pl.ds(j * step, n), :]
        s = jnp.dot(q, kj.T, preferred_element_type=jnp.float32) * sc
        if masked:
            s = _cut(s, (qi - j) * step, window, q_axis=0)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_acc = o_acc * corr + jnp.dot(p.astype(q.dtype), vj,
                                       preferred_element_type=jnp.float32)
        return o_acc, m_new, l

    def empty(rows):
        return (jnp.zeros((rows, d), jnp.float32),
                jnp.full((rows, 1), NEG_INF, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32))

    def finish(carry, at):
        o_acc, m, l = carry
        l = jnp.maximum(l, 1e-30)
        o_ref[0, at, :] = o_acc / l
        # the statistics are a column ([rows, 1], a row a sublane) and the
        # output is lane-dense ([1, rows]): through the transpose unit, not
        # lane by lane
        lse = jnp.broadcast_to(m + jnp.log(l), (o_acc.shape[0], _LANES))
        lse_ref[0, :, at] = lse.T[:1]

    t = band_rows(bq, window, causal)
    if t is not None:
        # straight-line code: a group's one softmax pass starts from the
        # empty carry (``exp(NEG_INF - m)`` is 0), and nothing orders the
        # groups among themselves
        span = band_span(nk * bk, t, window)
        for g in range(bq // t):
            rows = pl.ds(g * t, t)
            qi = i * (bq // t) + g
            finish(body(_fwd_span(qi, t, span), empty(t), q=q_ref[0, rows, :],
                        qi=qi, step=t, n=span, masked=True), rows)
        return
    tile = functools.partial(body, q=q_ref[0], qi=i, step=bk, n=bk)
    cut = functools.partial(tile, masked=True)
    whole = functools.partial(tile, masked=False)
    carry = empty(bq)
    if causal:
        # q block i attends kv blocks [lo, i] (bq == bk), in that order
        edge, dead = tile_bands(nk * bk, bk, window)
        lo, a = _fwd_ranges(i, edge, dead)
        if edge < nk:           # else the window reaches past every tile
            carry = jax.lax.fori_loop(lo, a, cut, carry)
        carry = jax.lax.fori_loop(a, i, whole, carry)
        carry = cut(i, carry)
    else:
        carry = jax.lax.fori_loop(0, nk, whole, carry)
    finish(carry, slice(None))


# ---------------------------------------------------------------- backward
def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, sc, bq, bk, nq, causal,
                      window):
    """One-pass backward: kv block j vs the VMEM-resident q/do row. dq
    accumulates into the full-[S, D] VMEM-resident output slab (index map
    depends only on the bh grid axis; the sequential grid makes the
    accumulation race-free)."""
    # dk/dv are emitted PER Q-HEAD (summed over the GQA group outside —
    # cheap XLA reduce); k/v rows are indexed b // rep by the caller
    j = pl.program_id(1)
    k = k_ref[0]
    v = v_ref[0]
    nt = (((1,), (1,)), ((), ()))       # a @ b.T: contract the minor dims

    @pl.when(j == 0)
    def _():
        dq_ref[:] = jnp.zeros_like(dq_ref)

    def body(i, carry, *, k, v, kj, step, n, masked):
        # queries [i * step, i * step + n) against the keys ``k``, whose
        # first is key ``kj * step``: a [bq, bq] tile of the loops (step =
        # n = bq), or a group's span of the band (step = t, n = span).
        # The tile is held TRANSPOSED (keys by queries): lse and
        # delta broadcast as the lane-dense rows they are stored as, p and
        # ds are already the left operands dv and dk need, and only dq
        # contracts over the tile's major dimension (one in-loop transpose
        # where the [bq, bk] form has two, plus two relayouts of a row
        # into a column); same products, same order of accumulation
        dk_acc, dv_acc = carry
        rows = (0, pl.ds(i * step, n), slice(None))
        qi = q_ref[rows]
        doi = do_ref[rows]
        lse = lse_ref[0, :, pl.ds(i * step, n)]               # [1, n]
        delta = delta_ref[0, :, pl.ds(i * step, n)]
        s = jax.lax.dot_general(k, qi, nt,
                                preferred_element_type=jnp.float32) * sc
        if masked:
            s = _cut(s, (i - kj) * step, window, q_axis=1)
        p = jnp.exp(s - lse).astype(k.dtype)
        dv_acc += jnp.dot(p, doi, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, doi, nt,
                                 preferred_element_type=jnp.float32)
        ds = (p.astype(jnp.float32) * (dp - delta)).astype(k.dtype)
        dk_acc += jnp.dot(ds, qi, preferred_element_type=jnp.float32) * sc
        dq_ref[rows] += jax.lax.dot_general(
            ds, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sc
        return dk_acc, dv_acc

    def empty(rows):
        return (jnp.zeros((rows, k.shape[-1]), jnp.float32),
                jnp.zeros((rows, v.shape[-1]), jnp.float32))

    t = band_rows(bk, window, causal)
    if t is not None:
        # the forward's band transposed: the block's keys go by groups,
        # each against its one query span
        span = band_span(nq * bq, t, window)
        for g in range(bk // t):
            rows = slice(g * t, (g + 1) * t)
            kj = j * (bk // t) + g
            dk_ref[0, rows, :], dv_ref[0, rows, :] = body(
                _bwd_span(kj, nq * bq, t, span), empty(t), k=k[rows],
                v=v[rows], kj=kj, step=t, n=span, masked=True)
        return
    tile = functools.partial(body, k=k, v=v, kj=j, step=bq, n=bq)
    cut = functools.partial(tile, masked=True)
    whole = functools.partial(tile, masked=False)
    carry = empty(bk)
    if causal:
        # kv block j is attended by q blocks [j, hi) (bq == bk), in that
        # order
        edge, dead = tile_bands(nq * bq, bq, window)
        c, hi = _bwd_ranges(j, nq, edge, dead)
        carry = cut(j, carry)
        carry = jax.lax.fori_loop(j + 1, c, whole, carry)
        if edge < nq:           # else the window reaches past every tile
            carry = jax.lax.fori_loop(c, hi, cut, carry)
    else:
        carry = jax.lax.fori_loop(0, nq, whole, carry)
    dk_ref[0], dv_ref[0] = carry


def _flash_bwd_call(q, k, v, do, lse, delta, *, causal: bool, sc: float,
                    window: int | None = None, rep: int = 1):
    """The backward kernel on one row, or on one pair of spans of a longer
    one (``lse`` and ``delta`` are then the WHOLE row's, cut to the query
    span): float32 (dq, dk, dv), dk and dv a QUERY head."""
    bh, s, d = q.shape
    bq = bk = _block(s)
    # q, k and their gradients at the key width ``d``; v, do and dv at the
    # value width (latent attention: 192 beside 128; equal elsewhere)
    dv = v.shape[-1]
    rowfull = lambda w: pl.BlockSpec(  # noqa: E731
        (1, s, w), lambda b, j: (b, 0, 0), memory_space=pltpu.VMEM)
    kin = lambda w: pl.BlockSpec(  # noqa: E731
        (1, bk, w), lambda b, j: (b // rep, j, 0), memory_space=pltpu.VMEM)
    kout = lambda w: pl.BlockSpec(  # noqa: E731
        (1, bk, w), lambda b, j: (b, j, 0), memory_space=pltpu.VMEM)
    rowstat = pl.BlockSpec((1, 1, s), lambda b, j: (b, 0, 0),
                           memory_space=pltpu.VMEM)
    call = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, sc=sc, bq=bq, bk=bk,
                          nq=s // bq, causal=causal, window=window),
        grid=(bh, s // bk),
        in_specs=[rowfull(d), kin(d), kin(dv), rowfull(dv), rowstat,
                  rowstat],
        out_specs=[rowfull(d), kout(d), kout(dv)],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
                   jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
                   jax.ShapeDtypeStruct((bh, s, dv), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
        name="ds_flash_bwd",
    )
    # opened here, inside the custom_vjp's backward function, so that the
    # scope survives shard_map and remat
    with jax.named_scope("ds.flash_bwd"):
        return call(q, k, v, do, lse, delta)


def _row_delta(o, do):
    """``rowsum(do * o)`` as the kernels read it, [BH, 1, S] float32."""
    bh, s, _ = o.shape
    return jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1).reshape(bh, 1, s)


def _key_heads(dq, dk, dv, rep: int, like):
    """The kernel's float32 gradients as the rule returns them: dk and dv
    summed over a key head's ``rep`` query heads (consecutive query heads
    share one), each in its operand's dtype."""
    if rep > 1:
        bh, s, _ = dk.shape
        dk = dk.reshape(bh // rep, rep, s, -1).sum(axis=1)
        dv = dv.reshape(bh // rep, rep, s, -1).sum(axis=1)
    return tuple(g.astype(x.dtype) for g, x in zip((dq, dk, dv), like))


def _flash_bwd(q, k, v, o, lse, do, *, causal: bool, sc: float,
               window: int | None = None, rep: int = 1):
    delta = _row_delta(o, do)
    _gauge_tiles("bwd", q.shape[1], _block(q.shape[1]), window, causal)
    dq, dk, dv = _flash_bwd_call(q, k, v, do, lse, delta, causal=causal,
                                 sc=sc, window=window, rep=rep)
    return _key_heads(dq, dk, dv, rep, (q, k, v))


# ------------------------------------------------- past the residency cap
def _span_pairs(n: int, causal: bool):
    """(query span, key span, is the pair on the diagonal) of a row cut in
    ``n`` spans: under a causal mask a query span meets the key spans at or
    under it, the diagonal pairs masked, the others whole."""
    return [(a, b, causal and a == b) for a in range(n)
            for b in (range(a + 1) if causal else range(n))]


def _spans_of(x, span: int, axis: int = 1) -> list:
    """``x`` cut in its spans along ``axis``: XLA's copies, which the
    kernels' whole-array operands need, so part of what spans cost."""
    with jax.named_scope("ds.flash_merge"):
        return [jax.lax.slice_in_dim(x, i, i + span, axis=axis)
                for i in range(0, x.shape[axis], span)]


def _spans_fwd(q, k, v, *, causal: bool, sc: float, rep: int, span: int):
    """`_flash_fwd` of a row too long to hold (`_resident_max_seq`), as
    ``s // span`` equal spans: the SAME kernel on every pair of a query
    span and a key span the mask leaves anything of, and a query span's
    partial outputs merged by their log-sum-exp (scope ``ds.flash_merge``:
    everything here that is no kernel). Returns the row's (o, lse)."""
    s, n = q.shape[1], q.shape[1] // span
    _gauge_tiles("fwd", s, _block(span), None, causal, span=span)
    qs, ks, vs = (_spans_of(x, span) for x in (q, k, v))
    met = [[] for _ in range(n)]
    for a, b, diagonal in _span_pairs(n, causal):
        met[a].append(_flash_fwd(qs[a], ks[b], vs[b], causal=diagonal,
                                 sc=sc, rep=rep, part=True))
    with jax.named_scope("ds.flash_merge"):
        os_, lses = [], []
        for parts in met:
            o, lse = parts[0]       # a span that met one key span is done
            if len(parts) > 1:
                lse = functools.reduce(jnp.logaddexp, [l for _, l in parts])
                # [BH, 1, span] statistics against [BH, span, dv] outputs
                o = sum(o * jnp.exp(l - lse).transpose(0, 2, 1)
                        for o, l in parts)
            os_.append(o.astype(q.dtype))
            lses.append(lse)
        return jnp.concatenate(os_, axis=1), jnp.concatenate(lses, axis=2)


def _spans_bwd(q, k, v, o, lse, do, *, causal: bool, sc: float, rep: int,
               span: int):
    """`_flash_bwd` by the same pairs: the kernel of a pair reads the
    query span's cut of the WHOLE row's ``lse`` and ``delta``, so its
    probabilities are the row's and ``dq`` adds up over a query span's key
    spans, ``dk`` and ``dv`` over a key span's query spans."""
    s, n = q.shape[1], q.shape[1] // span
    _gauge_tiles("bwd", s, _block(span), None, causal, span=span)
    with jax.named_scope("ds.flash_merge"):
        delta = _row_delta(o, do)
    qs, ks, vs, dos = (_spans_of(x, span) for x in (q, k, v, do))
    lses, deltas = (_spans_of(x, span, axis=2) for x in (lse, delta))
    dqs, dks, dvs = ([[] for _ in range(n)] for _ in range(3))
    for a, b, diagonal in _span_pairs(n, causal):
        dq, dk, dv = _flash_bwd_call(
            qs[a], ks[b], vs[b], dos[a], lses[a], deltas[a],
            causal=diagonal, sc=sc, rep=rep)
        dqs[a].append(dq), dks[b].append(dk), dvs[b].append(dv)
    with jax.named_scope("ds.flash_merge"):
        whole = lambda spans: jnp.concatenate(  # noqa: E731
            [sum(parts) for parts in spans], axis=1)
        return _key_heads(whole(dqs), whole(dks), whole(dvs), rep, (q, k, v))


# ---------------------------------------------------------------- public
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, window, rep, span=None):
    """``span``: None, or the length of the equal spans a windowless row
    too long for the kernels is run in (`segments`)."""
    return _row_fwd(q, k, v, causal, window, rep, span)[0]


def _row_fwd(q, k, v, causal, window, rep, span):
    sc = 1.0 / np.sqrt(q.shape[-1])
    if span is None:
        return _flash_fwd(q, k, v, causal=causal, sc=sc, window=window,
                          rep=rep)
    return _spans_fwd(q, k, v, causal=causal, sc=sc, rep=rep, span=span)


def _flash_fwd_rule(q, k, v, causal, window, rep, span=None):
    o, lse = _row_fwd(q, k, v, causal, window, rep, span)
    # O(S) bytes that cost O(S^2) work to make again: a rematted layer
    # keeps them, and its backward reruns the projections for q, k, v but
    # not this kernel
    o, lse = _keep("flash", o, lse)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, window, rep, span, res, do):
    q, k, v, o, lse = res
    sc = 1.0 / np.sqrt(q.shape[-1])
    if span is None:
        return _flash_bwd(q, k, v, o, lse, do, causal=causal, sc=sc,
                          window=window, rep=rep)
    return _spans_bwd(q, k, v, o, lse, do, causal=causal, sc=sc, rep=rep,
                      span=span)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None, rotary=None, **_kw):
    """Drop-in attn_fn: q [B, S, Hq, D], k/v [B, S, Hkv, D], matches
    ops.layers.dot_product_attention numerics. GQA is native: the
    kernels index the shared kv head per q-head group, so repeated k/v
    are never materialized (and the custom-VJP residuals hold unrepeated
    k/v — rep x smaller than the repeat-then-attend form; under a
    whole-layer ``jax.checkpoint`` only the output and the row
    log-sum-exp are stored, and the forward kernel is not rerun).
    ``window``
    restricts each query to its last `window` positions (Mistral sliding
    window; kernel skips blocks fully outside the band).

    Dispatches to the in-repo one-pass kernels (see module docstring). A
    sequence past the VMEM residency cap runs through the same kernels in
    equal spans (gauge ``ds_flash_segments``; any key and value width,
    GQA, ``rotary``, causal or not). A WINDOW past the cap falls back to
    the exact masked form (O(S^2) memory) with a warning: no configuration
    has one.

    ``rotary`` (``ops.layers.RotaryTables`` with their ``wide`` pair; the
    function says ``applies_rotary``): q and k come UNROTATED and the
    rotation rides the relayout into the kernels' [B x H, S, D], one pass
    over each (``ops/pallas/rope.py``; ``ops.layers.rotary_attention`` is
    who hands the tables over). The exact fallback rotates by
    ``ops.layers.rotate`` first.
    """
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    dv = v.shape[-1]        # latent attention: a value narrower than the key
    if window is not None and not causal:
        raise ValueError("window requires causal=True (Mistral SWA)")
    if hq % hkv != 0:
        raise ValueError(f"q heads {hq} must be a multiple of kv heads "
                         f"{hkv}")
    rep = hq // hkv
    unaligned = (s > 128 and s % 128 != 0) or (
        s < 128 and jax.default_backend() == "tpu")
    n = 1 if unaligned else segments(s, d, dv, q.dtype.itemsize)
    # a window past the cap is the one call left to the exact form: no
    # configuration has one (every window layer of the cells is 8192 or
    # 16384 long), and a band across spans is its own sweep
    exact = unaligned or (n > 1 and window is not None)
    if exact:
        from ..layers import dot_product_attention, rotate, window_bias
        from ...utils.logging import warning_once
        # the blocked kernels require 128-aligned sequence lengths: an
        # unaligned tail would be silently dropped by the grid floor
        # division, and sub-128 blocks fail Mosaic's lane-width lowering
        # on real hardware (interpret mode accepts them, so CPU tests
        # still exercise the kernel at tiny shapes) — use the exact
        # (unfused) path instead
        warning_once(
            f"flash attention falling back to the exact unfused form "
            f"(O(S^2) memory) at seq {s}: "
            + ("the kernel needs a sequence length that is a multiple of "
               "128" if unaligned else
               f"sliding windows are only fused up to seq "
               f"{_resident_max_seq(d, dv, q.dtype.itemsize)} at a key of "
               f"{d} and a value of {dv}"))
        if rotary is not None:
            q, k = rotate(q, k, rotary)
        bias = window_bias(s, window) if window is not None else None
        return dot_product_attention(q, k, v, causal=causal, bias=bias)
    # GQA-native: k/v stay per-kv-head ([B*Hkv, S, D]); the kernels index
    # kv rows at q_head_idx // rep, so repeated k/v are never
    # materialized — and the custom-VJP residuals hold the UNREPEATED k/v
    # (of the five, a rematted layer stores `o` and `lse`,
    # `_flash_fwd_rule`, and makes q, k, v again)
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(  # noqa: E731
        -1, s, x.shape[-1])
    if rotary is not None:
        from .rope import rotate_to_heads
        q, k = (rotate_to_heads(x, rotary.wide, rotary.rotated)
                for x in (q, k))
    else:
        q, k = to_bh(q), to_bh(k)
    return _attend(q, k, to_bh(v), b, causal, window, n)


def _attend(q, k, v, b: int, causal: bool, window, n: int):
    """The kernels over operands laid out for them, q [B x Hq, S, D], k
    [B x Hkv, S, D], v [B x Hkv, S, Dv], in ``n`` spans a row; the output
    as the model takes it, [B, S, Hq, Dv]."""
    from jax.ad_checkpoint import checkpoint_name
    (bh, s, d), dv = q.shape, v.shape[-1]
    _gauge_segments(s, d, n)
    o = _flash(q, k, v, causal, window, bh // k.shape[0],
               None if n == 1 else s // n)
    return checkpoint_name(
        o.reshape(b, bh // b, s, dv).transpose(0, 2, 1, 3), "attn_out")


def latent_flash_attention(q, kv, k_pe, *, rotary=None, pairs: bool = False,
                           causal: bool = True, **_kw):
    """:func:`flash_attention` of latent attention's projections as they
    lie (``ops.layers.latent_attention``'s arguments, which see; its
    ``hands_latent`` is the rule for what comes here): q [B, S, H,
    nope + rope], kv [B, S, H, nope + dv], the shared k_pe [B, S, rope]
    and the ``rope`` channels' tables. The rotation, the key's
    concatenation and the relayout to the kernels' [B x H, S, .] are ONE
    pass over the three (``ops/pallas/rope.py`` ``latent_to_heads``). It is
    ``flash_attention.latent``: how the function says that it takes them."""
    from .rope import latent_to_heads
    b, s, _, d = q.shape
    n = segments(s, d, kv.shape[-1] + k_pe.shape[-1] - d, q.dtype.itemsize)
    q, k, v = latent_to_heads(q, kv, k_pe,
                              None if rotary is None else rotary.wide,
                              pairs=pairs)
    return _attend(q, k, v, b, causal, None, n)


flash_attention.applies_rotary = True
flash_attention.latent = latent_flash_attention


def sharded_flash_attention(mesh, batch_axes, *, tp_axis: str = "tp",
                            window: int | None = None):
    """attn_fn for a multi-device mesh: :func:`flash_attention` per shard
    under a shard_map. GSPMD cannot partition a Mosaic custom call
    ("Mosaic kernels cannot be automatically partitioned" at lowering on
    the chip), and it only lowers when EVERY mesh axis is manual — so
    the map is manual over all axes not already manual in an enclosing
    region (the compiled pipeline's ``pp`` map). Batch is split over
    ``batch_axes`` and heads over ``tp_axis`` where they divide; any
    other axis sees replicated inputs (attention is independent per
    (batch, head), so the per-shard result is exact).

    The returned callable carries ``applies_window = True``: it applies
    the model's sliding window itself, unlike the sequence-parallel
    wrappers; a call may give another ``window`` (None: none), as a stack
    whose layers differ in it does (models/mellum.py). It carries
    ``applies_rotary = True`` too: ``rotary`` tables go to every shard
    whole, and the rotation's kernels run inside the manual region with
    the flash kernels; and ``latent``, which takes latent attention's
    projections as they lie (:func:`latent_flash_attention`)."""
    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import active_mesh
    from ...utils.jax_compat import shard_map

    def axes(batch: int, *heads):
        """(the mesh to map over, its free axes, the axes the batch is
        split over, the axis the heads are)."""
        use, free = active_mesh(mesh)
        b_ax = tuple(a for a in batch_axes
                     if a in free and use.shape[a] > 1)
        if batch % math.prod(use.shape[a] for a in b_ax):
            b_ax = ()       # uneven batch: replicate, still exact
        tp = use.shape.get(tp_axis, 1)
        h_ax = (tp_axis if tp_axis in free and tp > 1
                and not any(h % tp for h in heads) else None)
        return use, free, b_ax or None, h_ax

    def attn(q, k, v, *, causal: bool = True, window=window, rotary=None,
             **_kw):
        use, free, b_ax, h_ax = axes(q.shape[0], q.shape[2], k.shape[2])
        spec = P(b_ax, None, h_ax, None)

        def per_shard(q, k, v, rotary):
            return flash_attention(q, k, v, causal=causal, window=window,
                                   rotary=rotary)

        return shard_map(
            per_shard, mesh=use, axis_names=set(free),
            in_specs=(spec, spec, spec, jax.tree.map(lambda _: P(), rotary)),
            out_specs=spec, check_vma=False)(q, k, v, rotary)

    def latent(q, kv, k_pe, *, rotary=None, pairs: bool = False,
               causal: bool = True, **_kw):
        """:func:`latent_flash_attention` per shard: the shared key goes to
        every shard of the heads whole, and its cotangent is summed over
        them. A shard takes whole PAIRS of heads (the kernels' unit)."""
        use, free, b_ax, h_ax = axes(q.shape[0], q.shape[2] // 2)
        spec = P(b_ax, None, h_ax, None)

        def per_shard(q, kv, k_pe, rotary):
            return latent_flash_attention(q, kv, k_pe, rotary=rotary,
                                          pairs=pairs, causal=causal)

        return shard_map(
            per_shard, mesh=use, axis_names=set(free),
            in_specs=(spec, spec, P(b_ax), jax.tree.map(lambda _: P(),
                                                        rotary)),
            out_specs=spec, check_vma=False)(q, kv, k_pe, rotary)

    attn.applies_window = attn.applies_rotary = True
    attn.latent = latent
    return attn

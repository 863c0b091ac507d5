"""MoE gating + expert-parallel dispatch (reference: deepspeed/moe/sharded_moe.py).

GShard-style static-shape token routing: top-k gate probabilities become a
dense combine tensor [N, E, C] (token x expert x capacity-slot); dispatch is
its boolean support. Tokens beyond an expert's capacity are dropped (the
residual path carries them, as in the reference's capacity semantics,
sharded_moe.py:161). Everything is einsum over static shapes, so XLA maps
dispatch/combine onto the MXU and — with the expert dim sharded over the
``ep`` mesh axis — inserts the all-to-all the reference issues explicitly
(_AllToAll, sharded_moe.py:96).

Gating variants: top1 (Switch), top2 (GShard, with normalization), general
top-k — reference top1gating/top2gating/topkgating (sharded_moe.py:183,
290,374).
"""

from __future__ import annotations

import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas import grouped_matmul, router


def compute_capacity(num_tokens: int, num_experts: int, k: int,
                     capacity_factor: float, min_capacity: int = 4) -> int:
    """reference: sharded_moe.py:161 _capacity."""
    cap = math.ceil(num_tokens * k / num_experts * capacity_factor)
    return max(cap, min_capacity)


def _top_k_xla(select, scores, k: int):
    """:func:`top_k_of` as XLA's ops: ``lax.top_k`` (on the chip a full
    stable sort of every row), a gather of the scores, a ``bincount``."""
    w, idx = lax.top_k(select, k)
    if scores is not None:
        w = jnp.take_along_axis(scores, idx, axis=-1)
    load = jnp.bincount(idx.reshape(-1), length=select.shape[-1])
    return idx.astype(jnp.int32), w, load.astype(jnp.int32)


def top_k_of(select: jax.Array, scores: jax.Array | None, k: int):
    """The ``k`` largest of each row of ``select`` [N, E] float32, the
    lower index first among equals (``lax.top_k``'s order), and those of
    ``scores`` [N, E] (None: ``select``'s own values): (experts chosen
    [N, k] int32, their scores [N, k], the rows that chose each expert
    [E] int32). The weights' gradient goes to the scores they are; the
    choice gets none. ONE pass of a kernel pair over the scores
    (``ops/pallas/router.py``) where the shape allows (``router.fits``: a
    step's worth of rows in whole grid tiles, experts in whole sublane
    tiles), else :func:`_top_k_xla`: the same numbers, element for
    element."""
    n, e = select.shape
    form = "kernel" if router.fits(n, e, k) else "xla"
    router.count_router(form, e, k)
    if form == "xla":
        return _top_k_xla(select, scores, k)
    # experts first: XLA hands the scores over in that layout, no copy
    idx, w, load = router.top_k_rows(
        select.T, None if scores is None else scores.T, k)
    return idx.T, w.T, load


def softmax_top_k(logits: jax.Array, k: int, *, renormalise: bool = True):
    """Softmax routing, once for every path that routes by it: float32
    ``probs = softmax(logits)`` over all experts, the ``k`` largest, their
    probabilities divided by their sum if ``renormalise`` (and k > 1).
    Returns (experts chosen [N, k] int32, weights [N, k] float32, probs
    [N, E], the rows that chose each expert [E] int32)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topk_idx, topk_probs, load = top_k_of(probs, None, k)
    if renormalise and k > 1:
        topk_probs = topk_probs / jnp.sum(topk_probs, axis=-1, keepdims=True)
    return topk_idx, topk_probs, probs, load


def top_k_gating(logits: jax.Array, k: int, capacity_factor: float = 1.0,
                 min_capacity: int = 4, normalize_topk: bool = True,
                 drop_tokens: bool = True):
    """Compute (combine [N,E,C], dispatch [N,E,C], aux_loss, metrics).

    logits: [N, E] router outputs for N tokens.
    """
    n, e = logits.shape
    if drop_tokens:
        capacity = compute_capacity(n, e, k, capacity_factor, min_capacity)
    else:
        # no-drop mode must size capacity to the WORST-CASE expert load:
        # top-k indices are distinct per token, so one expert can claim
        # at most one slot per token — n slots. A fixed capacity_factor
        # capacity here silently one-hots overflow positions past the
        # table into zero rows (they were "kept" but never dispatched)
        capacity = max(n, min_capacity)
    topk_idx, topk_probs, probs, _ = softmax_top_k(
        logits, k, renormalise=normalize_topk)

    # slot-major positions: all slot-0 assignments get capacity positions
    # first (matches reference top2gating's second-expert offset logic)
    masks = jax.nn.one_hot(topk_idx, e, dtype=jnp.int32)  # [N, k, E]
    mask_flat = masks.transpose(1, 0, 2).reshape(k * n, e)
    positions = jnp.cumsum(mask_flat, axis=0) - mask_flat  # pos of each entry
    positions = positions.reshape(k, n, e).transpose(1, 0, 2)  # [N, k, E]
    pos_per_choice = jnp.sum(positions * masks, axis=-1)   # [N, k]

    if drop_tokens:
        keep = pos_per_choice < capacity
    else:
        keep = jnp.ones_like(pos_per_choice, dtype=bool)
    gate_w = topk_probs * keep

    # combine[n, e, c] = sum_k gate_w[n,k] * [idx==e] * [pos==c]
    loc_oh = jax.nn.one_hot(jnp.where(keep, pos_per_choice, capacity),
                            capacity, dtype=jnp.float32)     # [N, k, C]
    combine = jnp.einsum("nk,nke,nkc->nec", gate_w, masks.astype(jnp.float32),
                         loc_oh)
    dispatch = combine > 0

    # load-balance aux loss (reference: l_aux in top1/top2gating)
    me = jnp.mean(probs, axis=0)                       # mean router prob
    ce = jnp.mean(masks[:, 0].astype(jnp.float32), axis=0)  # top1 fraction
    aux = jnp.sum(me * ce) * e

    metrics = {
        "capacity": capacity,
        "drop_fraction": 1.0 - jnp.mean(keep.astype(jnp.float32)),
        "expert_load": ce,
    }
    return combine, dispatch, aux, metrics


def quantize_experts(experts: dict, scale_dtype=None) -> dict:
    """Weight-only int8 quantization of the routed expert weights
    (reference: inference/v2/kernels/cutlass_ops mixed_gemm — fp16
    activations x quantized weights — and the ZeRO-Inference weight-
    quantization serving recipe).

    MoE decode is EXPERT-WEIGHT-READ bound: at small batch every live
    expert's weights stream from HBM for a handful of tokens, so the
    routing overhead vs a dense model has a floor set by bytes, not
    FLOPs (measured r4: 1.99x at bf16, exactly the traffic ratio).
    Per-output-channel int8 halves those bytes; XLA fuses the
    dequant (convert+scale) into the expert GEMM's operand read, so
    the saving is realized without a custom kernel (measured: 1.99x
    -> 1.50x at decode batch 16 on v5e).

    Returns ``{name_q: int8 [..., D, F], name_s: scale [..., 1, F]}``
    per weight; ``dequantize_experts`` restores the GEMM-ready form.
    """
    out = {}
    for name, w in experts.items():
        s = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2,
                    keepdims=True) / 127.0
        s = jnp.maximum(s, 1e-12)
        out[name + "_q"] = jnp.round(
            w.astype(jnp.float32) / s).astype(jnp.int8)
        out[name + "_s"] = s.astype(scale_dtype or w.dtype)
    return out


def dequantize_experts(experts: dict, dtype) -> dict:
    """Inline dequant of a quantize_experts tree; under jit XLA fuses
    this into the consuming GEMM (no bf16 materialization in HBM)."""
    if not any(k.endswith("_q") for k in experts):
        # not a quantized tree (gate-less gelu dicts have no w_up_q
        # either; any *_q key marks the quantize_experts form)
        return experts
    return {k[:-2]: experts[k].astype(dtype)
            * experts[k[:-2] + "_s"].astype(dtype)
            for k in experts if k.endswith("_q")}


def moe_ffn_grouped(x: jax.Array, gate_w: jax.Array, experts: dict, *,
                    k: int = 2, activation: str = "swiglu",
                    normalize_topk: bool = True):
    """Serving-path MoE dispatch: sort-by-expert + grouped GEMM
    (reference: inference/v2/kernels/cutlass_ops moe_gemm +
    ragged_ops moe_gather/moe_scatter).

    The training path's dense [N, E, C] capacity einsum pads every
    expert to its capacity slot count and DROPS over-capacity tokens —
    both wrong for decode, where batches are small and every token's
    output matters. Here tokens sort by expert id and `jax.lax.
    ragged_dot` runs one grouped GEMM over exactly N*k rows: no
    capacity padding, no drops (exact top-k routing), no [N, E, C]
    one-hot materialization. Single-replica serving path (the ep-
    sharded training dispatch stays on the einsum/all-to-all form).

    Returns (out [B, S, D], aux_loss) with the same load-balance aux
    as top_k_gating (so eval parity holds if reused in training).
    """
    b, s, d = x.shape
    n = b * s
    e = gate_w.shape[-1]
    xt = x.reshape(n, d)
    logits = xt @ gate_w                                   # [N, E]
    topk_idx, topk_probs, probs, group_sizes = softmax_top_k(
        logits, k, renormalise=normalize_topk)

    e_flat = topk_idx.reshape(-1)                          # [N*k]
    order = jnp.argsort(e_flat)                            # sorted rows
    rows = order // k                                      # token of row
    xs = jnp.take(xt, rows, axis=0)                        # moe_gather

    if activation == "swiglu":
        gate = lax.ragged_dot(xs, experts["w_gate"], group_sizes)
        up = lax.ragged_dot(xs, experts["w_up"], group_sizes)
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(
            lax.ragged_dot(xs, experts["w_up"], group_sizes),
            approximate=True)
    out_rows = lax.ragged_dot(h, experts["w_down"], group_sizes)

    w = jnp.take(topk_probs.reshape(-1), order).astype(x.dtype)
    out = jnp.zeros((n, d), x.dtype).at[rows].add(       # moe_scatter
        out_rows.astype(x.dtype) * w[:, None])

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(topk_idx[:, 0], e,
                                 dtype=jnp.float32), axis=0)
    aux = jnp.sum(me * ce) * e
    return out.reshape(b, s, d), aux


def _expert_ffn(expert_in: jax.Array, experts: dict,
                activation: str = "swiglu") -> jax.Array:
    """The per-expert FFN on dispatched slots [E, C, D] -> [E, C, D].
    Shared between the global capacity-einsum path and the ep-sharded
    dispatcher's shard_map body (where E and C are the LOCAL extents).
    Bias-free, so zero (padded / unfilled) slots stay exactly zero."""
    if activation == "swiglu":
        gate = jnp.einsum("ecd,edf->ecf", expert_in, experts["w_gate"])
        up = jnp.einsum("ecd,edf->ecf", expert_in, experts["w_up"])
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(
            jnp.einsum("ecd,edf->ecf", expert_in, experts["w_up"]),
            approximate=True)
    return jnp.einsum("ecf,efd->ecd", h, experts["w_down"])


def moe_ffn(x: jax.Array, gate_w: jax.Array, experts: dict, *,
            k: int = 2, capacity_factor: float = 1.0, min_capacity: int = 4,
            activation: str = "swiglu", normalize_topk: bool = True,
            constrain: Callable | None = None, drop_tokens: bool = True,
            dispatcher: Callable | None = None,
            metrics_hook: Callable | None = None):
    """Full MoE FFN for a [B, S, D] block input.

    experts: {"w_up": [E, D, F], "w_down": [E, F, D], ("w_gate": [E, D, F])}.
    With the E dim sharded over the ``ep`` mesh axis, the two einsums below
    become XLA all-to-alls (dispatch/combine) around expert-local GEMMs.
    ``dispatcher`` (moe/dispatch.py EpShardedDispatcher, wired by the
    engine) replaces that implicit form with the explicit hierarchical
    (optionally int8-wire) dispatch/combine exchange; gating stays
    global either way. ``metrics_hook`` receives top_k_gating's metrics
    dict at trace time (telemetry/dispatch publishing).
    Returns (out [B, S, D], aux_loss).
    """
    b, s, d = x.shape
    n = b * s
    xt = x.reshape(n, d)
    logits = xt @ gate_w                                  # [N, E]
    combine, dispatch, aux, metrics = top_k_gating(
        logits, k, capacity_factor, min_capacity,
        normalize_topk=normalize_topk, drop_tokens=drop_tokens)
    if metrics_hook is not None:
        metrics_hook(metrics)
    combine = combine.astype(x.dtype)

    if dispatcher is not None:
        out = dispatcher(xt, combine, dispatch.astype(x.dtype), experts,
                         functools.partial(_expert_ffn,
                                           activation=activation))
        return out.reshape(b, s, d), aux

    expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), xt,
                           preferred_element_type=x.dtype)
    if constrain is not None:
        expert_in = constrain(expert_in)
    h = _expert_ffn(expert_in, experts, activation)
    if constrain is not None:
        h = constrain(h)
    out = jnp.einsum("nec,ecd->nd", combine, h)
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# A held share of routed experts, dropless, with a backward
# ---------------------------------------------------------------------------
def sigmoid_top_k(logits: jax.Array, bias: jax.Array, k: int, *,
                  renormalise: bool = True, scaling: float = 1.0):
    """Bias-corrected sigmoid routing (DeepSeek-V3 / Kimi-Linear, one
    group). ``logits`` [N, E] float32. ``scores = sigmoid(logits)``; the
    top ``k`` of ``scores + bias`` are chosen (the correction ``bias``
    takes part in the SELECTION only and gets no gradient); the weights
    are the chosen experts' own scores, divided by their sum (+1e-20) if
    ``renormalise``, times ``scaling``. Returns (experts chosen [N, k]
    int32, weights [N, k] float32, selection scores [N, E], the rows that
    chose each expert [E] int32)."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    select = scores + lax.stop_gradient(bias.astype(jnp.float32))
    idx, w, load = top_k_of(select, scores, k)
    if renormalise:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * scaling, select, load


def _held_layout(idx, weights, first: int, n_held: int, tile: int):
    """Rows (token, choice) routed to the experts held here, sorted by
    expert and cut into row tiles of ``tile`` rows that each lie in ONE
    expert's run. Returns ``order`` [N*k + tile] (row ids by expert, the
    rows of absent experts last, padded), ``counts`` and ``starts`` [E_h]
    of each held expert's run in it, ``ends`` [E_h]: the number of tiles
    up to and with the expert's own (the last is the total), and
    ``weights`` [N, k] in ``order``'s order (they ride the sort: as a
    gather by ``order`` they cost a millisecond a sweep)."""
    n, k = idx.shape
    local = idx - first
    held = (local >= 0) & (local < n_held)
    e_flat = jnp.where(held, local, n_held).reshape(-1)
    _, order, by_order = lax.sort(
        (e_flat, jnp.arange(n * k, dtype=jnp.int32), weights.reshape(-1)),
        num_keys=1, is_stable=True)
    # a bincount is a scatter-add: a millisecond of its own on the chip
    counts = jnp.sum(e_flat[:, None] == jnp.arange(n_held)[None, :],
                     axis=0, dtype=jnp.int32)
    starts = jnp.cumsum(counts) - counts
    ends = jnp.cumsum((counts + tile - 1) // tile)
    pad = lambda v: jnp.concatenate(  # noqa: E731
        [v, jnp.zeros((tile,), v.dtype)])
    return pad(order), counts, starts, ends, pad(by_order)


def _swiglu_rows(xg, w_gate, w_up):
    gate = xg @ w_gate
    up = xg @ w_up
    return gate, up, jax.nn.silu(gate) * up


def _ffn_rows(xt, p: dict, body: str):
    """One always-on expert of ``body`` (``grouped_matmul.BODIES``) on
    rows ``xt`` [N, D], as XLA's matmuls: the shared expert's form."""
    if body == "swiglu":
        h = _swiglu_rows(xt, p["w_gate"], p["w_up"])[2]
    else:
        h = jnp.square(jax.nn.relu(xt @ p["w_up"]))
    return h @ p["w_down"]


def _held_tiles(layout, tile: int, m: int):
    """Every row tile the layout can hold (the rows' ``N * k / tile`` and
    one part-empty tile an expert, in whole chunks of ``m`` tiles), laid
    end to end: where each tile's rows begin in the layout's ``order`` [t]
    and the kernels' tables, a chunk's tiles after another's
    (``grouped_matmul.tile_tables``: a run's last tile is part empty, and
    the tiles past the layout's last wholly)."""
    order, counts, starts, ends, _ = layout
    held = counts.shape[0]
    tiles = -(-(order.shape[0] // tile + held) // m) * m
    t = jnp.arange(tiles)
    e = jnp.minimum(jnp.sum(t[:, None] >= ends[None, :], axis=1), held - 1)
    at = (t - ends[e]) * tile + (counts[e] + tile - 1) // tile * tile
    live = jnp.where(t < ends[-1], jnp.clip(counts[e] - at, 0, tile), 0)
    return (jnp.where(live > 0, starts[e] + at, 0),
            grouped_matmul.tile_tables(e.astype(jnp.int32), live, at > 0, m))


def _held_sweep(idx, weights, first, n_held, block, chunk, shape, body,
                carry):
    """The sum over tokens, float32 ``shape`` = [N, D], of what
    ``body(rows, tokens, valid, scale, tables, tile, carry)`` gives for
    each chunk of the rows routed to the held experts (``scale`` [C, 1]: a
    row's routing weight, 0 where it is no expert's): ``body`` returns the
    chunk's float32 rows [C, D] in expert order and its own ``carry``.
    ``chunk`` rows in whole row tiles a chunk: as many chunks as this
    batch's routing needs (a loop whose trip count is data;
    ``held_chunk``: ONE where no more rows are held than a balanced router
    sends). An expert's run is padded to ROW TILES here
    (``grouped_matmul.row_tile``: it divides the block, the unit the
    callers count the padding by), so that a chunk holds no tile without a
    live row but its last ones.

    A chunk's rows reach their tokens without a scatter-add (XLA's walks
    the whole float32 [N, D] operand through VMEM a call, and sorts the
    rows first: 4.4 ms at 36,864 rows of 2304; ``PERF.md`` section 6, PRs
    41 and 48): the chunk's row ids are sorted once more, by token (int32
    pairs), ONE gather brings the float32 rows into that order, the rows
    that are no expert's last (they name the chunk's row 0, which ran: a
    dead tile's rows are not written and may hold anything), and
    ``grouped_matmul.add_rows`` sums each token's run into the
    accumulator, writing each tile of it once. The first chunk's call is
    told the accumulator is zeros.

    Returns (the sum, ``body``'s last carry, the sweep's own count):
    ``trips``, the bound ``fori_loop`` is given (int32, data); ``tiles``,
    the row tiles that hold a live row (the last of the layout's
    ``ends``); ``swept``, the tiles the trips held (``trips`` times the
    chunk's), live or not; and ``tile``, the rows of one. The backward's
    sweep walks the same layout, so it takes the same trips."""
    n, k = idx.shape
    tile = grouped_matmul.row_tile(block)
    m = -(-(chunk or n_held * block) // tile)
    layout = _held_layout(idx, weights, first, n_held, tile)
    run, tables = _held_tiles(layout, tile, m)

    def one(c, carry):
        acc, carry = carry
        # the chunk's row ids and routing weights: a slice of the layout
        # a tile, cut here for the chunk's tiles and not before the loop
        # for every tile the layout can hold (PERF.md section 6, PR 41)
        part = lambda a: lax.dynamic_slice_in_dim(a, c * m, m)  # noqa: E731
        cut = lambda v: jax.vmap(  # noqa: E731
            lambda s: lax.dynamic_slice(v, (s,), (tile,)))(part(run))
        mine = tuple(part(t) for t in tables)
        valid = (jnp.arange(tile)[None, :] < mine[2][:, None]).reshape(-1)
        rows = cut(layout[0]).reshape(-1)
        scale = jnp.where(valid, cut(layout[4]).reshape(-1), 0.0)
        ys, carry = body(rows, rows // k, valid, scale[:, None], mine, tile,
                         carry)
        by_token, at = lax.sort(
            (jnp.where(valid, rows, n * k),
             jnp.arange(rows.shape[0], dtype=jnp.int32)), num_keys=1)
        held = by_token < n * k
        acc = grouped_matmul.add_rows(
            acc, ys[jnp.where(held, at, 0)],
            jnp.where(held, by_token // k, n), c == 0)
        return acc, carry

    tiles = layout[3][-1]
    trips = (tiles + m - 1) // m
    acc, carry = lax.fori_loop(0, trips, one,
                               (jnp.zeros(shape, jnp.float32), carry))
    return acc, carry, {"trips": trips, "tiles": tiles, "swept": trips * m,
                        "tile": jnp.int32(tile)}


def _held_forward(x, idx, weights, experts, first, block, chunk, body):
    n_held = experts["w_up"].shape[0]

    def one(rows, tokens, valid, scale, tables, tile, done):
        return (grouped_matmul.forward(x[tokens], scale, tables[:3], experts,
                                       tile, body), done + jnp.sum(valid))

    out, done, sweep = _held_sweep(idx, weights, first, n_held, block, chunk,
                                   x.shape, one, jnp.zeros((), jnp.int32))
    return out.astype(x.dtype), {"done": done, **sweep}


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def held_experts_ffn(x, idx, weights, experts, first, block,
                     router_grad=True, chunk=None, body="swiglu"):
    """The part of a routed layer's result that the experts HELD here
    give: ``sum_j weights[n, j] * E_{idx[n, j]}(x_n)`` over the choices
    ``j`` whose expert lies in ``[first, first + E_h)`` (``experts``: the
    weights ``[E_h, ...]`` of ``body``, one of ``grouped_matmul.BODIES``:
    a SwiGLU, or the non-gated ``relu(.)^2`` FFN). Dropless: the rows
    routed here are sorted by expert, each expert's run padded to row tiles (the largest
    multiple of 128 up to 256 that divides ``block``, the unit the callers
    count the padding by), and swept ``chunk`` rows at a time
    (``held_chunk`` of the shape; left out, a block an expert held), as
    many chunks as this batch's routing needs: the chunk's rows are
    gathered in expert order once, a grouped-matmul kernel
    (``ops/pallas/grouped_matmul.py``) runs the body's matmuls tile by tile
    with an expert's weights held in VMEM across its tiles, and the
    weighted rows are added to their tokens once; no capacity, no
    [N, E, C] table. The work is that of the rows routed here, however
    skewed the router; nothing has the size of the worst case but the row
    index. The backward is one more sweep whose kernel makes ``gate`` and
    ``up`` again (nothing of the forward is kept but its inputs);
    ``router_grad`` False says the routing weights get no gradient
    (zeros), and the kernel leaves that product out.

    x [N, D]; idx [N, k] int32 over ALL experts; weights [N, k] float32.
    Returns (out [N, D], counts): ``done``, the rows computed (fewer than
    the rows routed to the held experts only if rows were dropped), and
    what the FORWARD sweep counted of itself (``_held_sweep``: ``trips``,
    ``tiles``, ``swept``, ``tile``; the backward's sweep walks the same
    layout and takes the same trips, so it is not counted again)."""
    with jax.named_scope("ds.moe_experts"):
        return _held_forward(x, idx, weights, experts, first, block, chunk,
                             body)


def _held_fwd_rule(x, idx, weights, experts, first, block, router_grad,
                   chunk, body):
    with jax.named_scope("ds.moe_experts"):
        out = _held_forward(x, idx, weights, experts, first, block, chunk,
                            body)
    return out, (x, idx, weights, experts)


def _held_bwd_rule(first, block, router_grad, chunk, body, res, cts):
    x, idx, weights, experts = res
    dout = cts[0]
    n, k = idx.shape
    f32 = jnp.float32
    names = grouped_matmul.BODIES[body]
    # opened here: a custom_vjp's backward function is traced outside the
    # scope its forward was called under
    with jax.named_scope("ds.moe_experts"):
        def one(rows, tokens, valid, scale, tables, tile, carry):
            dw, sums = carry
            dxs, dwt, sums = grouped_matmul.backward(
                x[tokens], dout[tokens], scale, tables, experts, sums, tile,
                router_grad, body)
            if router_grad:     # a row a choice: distinct, nothing summed
                dw = dw.at[jnp.where(valid, rows, n * k)].add(
                    dwt[:, 0], mode="drop")
            return dxs, (dw, sums)

        n_held = experts["w_up"].shape[0]
        dx, (dw, sums), _ = _held_sweep(
            idx, weights, first, n_held, block, chunk, x.shape, one,
            (jnp.zeros((n * k,), f32),
             [jnp.zeros(experts[name].shape, f32) for name in names]))
    d_experts = {name: s.astype(experts[name].dtype)
                 for name, s in zip(names, sums)}
    return (dx.astype(x.dtype), None,
            dw.reshape(n, k).astype(weights.dtype), d_experts)


held_experts_ffn.defvjp(_held_fwd_rule, _held_bwd_rule)


_BLOCK_MAX = 1024       # rows: the largest block of the dispatch


def held_block(tokens: int, k: int, n_experts: int) -> int:
    """Rows a block of the held dispatch, from the shape alone (``even``:
    the rows a balanced router sends one expert), in 128s between 128 and
    ``_BLOCK_MAX``. An expert takes ``ceil(load / block)`` blocks, so the
    swept rows step at every multiple of the block.

    - ``even`` under a block: twice ``even``, a padded capacity of two as
      trainers pad for static shapes. An expert at or under it takes ONE
      block, so below it the sweep's time does not follow the load (the
      held-expert roofline, which counts the rows that ran, shows the
      padding); an expert over it takes more blocks.
    - ``even`` of a whole block or more: every expert takes several
      blocks. Twice ``even`` would be capped at ``_BLOCK_MAX``, and where
      the block divides ``even`` a balanced expert sits ON a step: half
      the experts take one block more than the other half, which of them
      by the seed. So: the block that leaves ``even`` farthest, in blocks,
      from a multiple of it, the larger of equals (2048 -> 768: three
      blocks from 1537 to 2304 rows, a ninth of them padding)."""
    even = tokens * k / n_experts
    if even < _BLOCK_MAX:
        return min(_BLOCK_MAX, max(128, 128 * math.ceil(2 * even / 128)))

    def off_a_step(block):
        return min(even % block, block - even % block) / block

    return max(range(128, _BLOCK_MAX + 1, 128),
               key=lambda block: (off_a_step(block), block))


def held_chunk(tokens: int, k: int, n_experts: int, n_held: int,
               block: int) -> int:
    """Rows a chunk of the held sweep, from the shape alone: what a
    balanced router sends the held experts and one row tile (the block's)
    an expert for the part-empty ends of their runs (the Mellum cell:
    32,768 + 16 x 256
    = 36,864; the Kimi cell: 4096 + 8 x 256 = 6144). The runs are padded
    to row tiles, so a chunk of that size holds ANY split of the even
    total between the held experts, however skewed. A chunk costs its
    gathers by the rows it HOLDS, live or not (its rows of ``x``, of the
    cotangent, and of its float32 results into token order: 36 to 44 ns a
    row from HBM), its add to tokens by those rows and the tiles of the
    accumulator they reach (``grouped_matmul.add_rows``: no walk of the
    float32 [N, D] a call), and its matmul kernels by the row tiles that
    hold a live row: so one chunk a sweep is what a share that is sent
    its even total should pay, and no more rows than that. A share that
    is sent MORE than its even total (and the ends of its runs) takes a
    second chunk: its gathers and its add again for the tiles left over
    (``PERF.md`` section 6, PR 48, ``mellum_over`` of
    ``tools/moe_kernel_bench.py``: 3.7 + 5.7 ms a layer at the Mellum
    shape), and no more memory: the loop's body holds
    one chunk's temporaries whatever its trips."""
    return (math.ceil(n_held * tokens * k / n_experts)
            + n_held * grouped_matmul.row_tile(block))


def moe_ffn_held(x: jax.Array, router_w: jax.Array,
                 router_bias: jax.Array | None, experts: dict,
                 shared: dict | None, *, k: int, first_expert: int = 0,
                 renormalise: bool = True, scaling: float = 1.0,
                 block: int | None = None, router: str = "sigmoid",
                 router_grad: bool = True,
                 shared_gate: jax.Array | None = None,
                 body: str = "swiglu", latent: dict | None = None):
    """A routed expert layer that is told which experts it holds (one
    chip's share under expert parallelism, without its exchange): routes
    every token over ALL ``router_w.shape[-1]`` experts, computes what the
    ``experts["w_up"].shape[0]`` experts from ``first_expert`` on give for
    the tokens routed to them (:func:`held_experts_ffn`) and adds the
    always-on ``shared`` expert where there is one, times
    ``sigmoid(x @ shared_gate)`` (``shared_gate`` [D, 1], float32 logits:
    Qwen's gated shared expert) where that is given. ``body`` is the
    experts' and the shared expert's FFN (``grouped_matmul.BODIES``).
    With ``latent`` (``{"w_dn": [D, L], "w_up": [L, D]}``: LatentMoE) the
    router and the shared expert read ``x`` and the routed experts read
    ``x @ w_dn``, L wide; the held experts' weighted sum goes back through
    ``w_up``, which has no bias, so the shares of a layer still add up.
    ``router``:
    ``sigmoid`` (:func:`sigmoid_top_k`, with its selection bias
    ``router_bias``) or ``softmax`` (:func:`softmax_top_k`, no bias); the
    weights are float32 either way, times ``scaling``. A token routed only
    to absent experts gets the shared expert alone (nothing without one);
    what the absent experts would have added is left out. ``block`` (rows
    a block of the dispatch) defaults to :func:`held_block` of the shape;
    nothing is dropped at any block. ``router_grad`` False takes the
    weights as given in the backward: the gradient of a token's weights is
    a sum over ALL the experts it chose, and a share sees only the terms
    of the experts it holds, which pull every token towards them (they
    alone answer); a share whose peers' terms are not summed in leaves
    the routing alone.

    Returns (out [B, S, D], counts): ``counts["load"]`` [E] int32, the
    rows (token, choice) routed to EACH of the router's experts (what the
    trainer's bias update balances, :func:`balance_bias`; its slice
    ``[first_expert, +E_h)`` is what this share was sent), and
    ``counts["done"]``, the rows this share computed: less than that
    slice's sum only if rows were dropped; and the forward sweep's own
    count (``held_experts_ffn``): ``trips`` of the chunk loop, row
    ``tiles`` with a live row, tiles ``swept`` and the ``tile``'s rows."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    n_experts = router_w.shape[-1]
    if block is None:
        block = held_block(b * s, k, n_experts)
    with jax.named_scope("ds.moe_router"):
        logits = jnp.matmul(xt, router_w,
                            preferred_element_type=jnp.float32)
        if router == "sigmoid":
            idx, weights, _, load = sigmoid_top_k(
                logits, router_bias, k, renormalise=renormalise,
                scaling=scaling)
        elif router == "softmax" and router_bias is None:
            idx, weights, _, load = softmax_top_k(logits, k,
                                                  renormalise=renormalise)
            if scaling != 1.0:
                weights = weights * scaling
        else:
            raise ValueError(
                f"router {router!r}, selection bias "
                f"{'given' if router_bias is not None else 'None'}: "
                f"'sigmoid' takes a bias, 'softmax' takes none")
        if not router_grad:
            weights = lax.stop_gradient(weights)
    n_held = experts["w_up"].shape[0]
    rows = xt
    if latent is not None:
        with jax.named_scope("ds.moe_latent"):
            rows = xt @ latent["w_dn"]
    out, counts = held_experts_ffn(
        rows, idx, weights, experts, int(first_expert), int(block),
        bool(router_grad), held_chunk(b * s, k, n_experts, n_held, block),
        body)
    if latent is not None:
        with jax.named_scope("ds.moe_latent"):
            out = out @ latent["w_up"]
    if shared is not None:
        with jax.named_scope("ds.moe_shared"):
            y = _ffn_rows(xt, shared, body)
            if shared_gate is not None:
                y = (y * jax.nn.sigmoid(jnp.matmul(
                    xt, shared_gate, preferred_element_type=jnp.float32))
                     ).astype(y.dtype)
            out = out + y
    return out.reshape(b, s, d), {"load": load, **counts}


BIAS_UPDATE_RATE = 0.001    # DeepSeek-V3's; the sigmoid-routed families follow it


def balance_bias(bias: jax.Array, load: jax.Array,
                 rate: float = BIAS_UPDATE_RATE) -> jax.Array:
    """The trainer's update of a bias-corrected router's selection bias
    (auxiliary-loss-free balancing, DeepSeek-V3 section 2.1.2): after a
    step, an expert that got more than the mean load has its bias lowered
    by ``rate``, one that got less has it raised. ``bias`` [..., E];
    ``load`` [..., E], the rows routed to each expert in the step."""
    load = load.astype(jnp.float32)
    mean = jnp.mean(load, axis=-1, keepdims=True)
    return bias + rate * jnp.sign(mean - load).astype(bias.dtype)

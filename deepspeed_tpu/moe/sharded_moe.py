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


def compute_capacity(num_tokens: int, num_experts: int, k: int,
                     capacity_factor: float, min_capacity: int = 4) -> int:
    """reference: sharded_moe.py:161 _capacity."""
    cap = math.ceil(num_tokens * k / num_experts * capacity_factor)
    return max(cap, min_capacity)


def softmax_top_k(logits: jax.Array, k: int, *, renormalise: bool = True):
    """Softmax routing, once for every path that routes by it: float32
    ``probs = softmax(logits)`` over all experts, the ``k`` largest, their
    probabilities divided by their sum if ``renormalise`` (and k > 1).
    Returns (experts chosen [N, k], weights [N, k] float32, probs
    [N, E])."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topk_probs, topk_idx = lax.top_k(probs, k)          # [N, k]
    if renormalise and k > 1:
        topk_probs = topk_probs / jnp.sum(topk_probs, axis=-1, keepdims=True)
    return topk_idx, topk_probs, probs


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
    topk_idx, topk_probs, probs = softmax_top_k(logits, k,
                                                renormalise=normalize_topk)

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
    topk_idx, topk_probs, probs = softmax_top_k(logits, k,
                                                renormalise=normalize_topk)

    e_flat = topk_idx.reshape(-1)                          # [N*k]
    order = jnp.argsort(e_flat)                            # sorted rows
    rows = order // k                                      # token of row
    xs = jnp.take(xt, rows, axis=0)                        # moe_gather
    group_sizes = jnp.bincount(e_flat, length=e).astype(jnp.int32)

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
    int32, weights [N, k] float32, selection scores [N, E])."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    select = scores + lax.stop_gradient(bias.astype(jnp.float32))
    _, idx = lax.top_k(select, k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalise:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scaling, select


def _held_layout(idx, first: int, n_held: int, block: int):
    """Rows (token, choice) routed to the experts held here, sorted by
    expert and cut into blocks of ``block`` rows that each lie in ONE
    expert's run. Returns ``order`` [N*k + block] (row ids by expert, the
    rows of absent experts last, padded), ``counts`` and ``starts`` [E_h]
    of each held expert's run in it, and ``ends`` [E_h]: the number of
    blocks up to and with the expert's own (the last is the total)."""
    n, k = idx.shape
    local = idx - first
    held = (local >= 0) & (local < n_held)
    e_flat = jnp.where(held, local, n_held).reshape(-1)
    order = jnp.argsort(e_flat, stable=True).astype(jnp.int32)
    counts = jnp.bincount(e_flat, length=n_held + 1)[:n_held].astype(
        jnp.int32)
    starts = jnp.cumsum(counts) - counts
    ends = jnp.cumsum((counts + block - 1) // block)
    order = jnp.concatenate([order, jnp.zeros((block,), jnp.int32)])
    return order, counts, starts, ends


def _block_rows(b, layout, k: int, block: int):
    """Block ``b`` of the layout: its expert, its row ids [block], their
    tokens, and which of the rows are the expert's (the last block of a
    run is part empty)."""
    order, counts, starts, ends = layout
    e = jnp.sum(b >= ends).astype(jnp.int32)
    at = (b - ends[e]) * block + (counts[e] + block - 1) // block * block
    rows = lax.dynamic_slice(order, (starts[e] + at,), (block,))
    valid = jnp.arange(block) < counts[e] - at
    return e, rows, rows // k, valid


def _swiglu_rows(xg, w_gate, w_up):
    gate = xg @ w_gate
    up = xg @ w_up
    return gate, up, jax.nn.silu(gate) * up


def _held_fwd_loop(x, idx, weights, experts, first, block):
    n, d = x.shape
    k = idx.shape[1]
    n_held = experts["w_up"].shape[0]
    layout = _held_layout(idx, first, n_held, block)
    w_flat = weights.reshape(-1)

    def body(b, carry):
        out, done = carry
        e, rows, tokens, valid = _block_rows(b, layout, k, block)
        xg = jnp.where(valid[:, None], x[tokens], 0)
        _, _, h = _swiglu_rows(xg, experts["w_gate"][e], experts["w_up"][e])
        y = (h @ experts["w_down"][e]).astype(jnp.float32)
        scale = jnp.where(valid, w_flat[rows], 0.0)
        return (out.at[tokens].add(y * scale[:, None]),
                done + jnp.sum(valid))

    out, done = lax.fori_loop(
        0, layout[-1][-1], body,
        (jnp.zeros((n, d), jnp.float32), jnp.zeros((), jnp.int32)))
    return out.astype(x.dtype), done


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def held_experts_ffn(x, idx, weights, experts, first, block):
    """The part of a routed layer's result that the experts HELD here
    give: ``sum_j weights[n, j] * E_{idx[n, j]}(x_n)`` over the choices
    ``j`` whose expert lies in ``[first, first + E_h)`` (``experts``:
    SwiGLU weights ``[E_h, ...]``). Dropless: the rows routed here are
    sorted by expert and swept in blocks of ``block`` rows, as many
    blocks as this batch's routing needs (a loop whose trip count is
    data), each block one gather, three matmuls against ONE expert's
    weights and one scatter-add; no capacity, no [N, E, C] table. The
    work is that of the rows routed here, however skewed the router;
    nothing has the size of the worst case but the row index.

    x [N, D]; idx [N, k] int32 over ALL experts; weights [N, k] float32.
    Returns (out [N, D], rows computed): fewer than the rows routed to
    the held experts only if rows were dropped."""
    with jax.named_scope("ds.moe_experts"):
        return _held_fwd_loop(x, idx, weights, experts, first, block)


def _held_fwd_rule(x, idx, weights, experts, first, block):
    with jax.named_scope("ds.moe_experts"):
        out = _held_fwd_loop(x, idx, weights, experts, first, block)
    return out, (x, idx, weights, experts)


def _held_bwd_rule(first, block, res, cts):
    """One more sweep over the same blocks: the expert's two input
    matmuls are run again (nothing of the forward sweep is kept but its
    inputs), then the six of the backward."""
    x, idx, weights, experts = res
    dout = cts[0]
    n, d = x.shape
    k = idx.shape[1]
    n_held = experts["w_up"].shape[0]
    f32 = jnp.float32
    with jax.named_scope("ds.moe_experts"):
        layout = _held_layout(idx, first, n_held, block)
        w_flat = weights.reshape(-1)

        def body(b, carry):
            dx, dw, dg, du, dd = carry
            e, rows, tokens, valid = _block_rows(b, layout, k, block)
            xg = jnp.where(valid[:, None], x[tokens], 0)
            gate, up, h = _swiglu_rows(xg, experts["w_gate"][e],
                                       experts["w_up"][e])
            y = h @ experts["w_down"][e]
            dout_g = jnp.where(valid[:, None], dout[tokens], 0)
            dw = dw.at[rows].add(jnp.where(valid, jnp.sum(
                dout_g.astype(f32) * y.astype(f32), axis=-1), 0.0))
            dy = (dout_g.astype(f32)
                  * jnp.where(valid, w_flat[rows], 0.0)[:, None]
                  ).astype(x.dtype)
            dh = dy @ experts["w_down"][e].T
            sg = jax.nn.sigmoid(gate.astype(f32))
            d_up = (dh * (gate.astype(f32) * sg)).astype(x.dtype)
            d_gate = (dh * up * (sg * (1 + gate.astype(f32) * (1 - sg)))
                      ).astype(x.dtype)
            dxg = (d_gate @ experts["w_gate"][e].T
                   + d_up @ experts["w_up"][e].T)
            acc = lambda t, a, b_: t.at[e].add(  # noqa: E731
                jnp.matmul(a.T, b_, preferred_element_type=f32))
            return (dx.at[tokens].add(dxg.astype(f32)), dw,
                    acc(dg, xg, d_gate), acc(du, xg, d_up), acc(dd, h, dy))

        zeros = lambda w: jnp.zeros(w.shape, f32)  # noqa: E731
        dx, dw, dg, du, dd = lax.fori_loop(
            0, layout[-1][-1], body,
            (jnp.zeros((n, d), f32), jnp.zeros((n * k + block,), f32),
             zeros(experts["w_gate"]), zeros(experts["w_up"]),
             zeros(experts["w_down"])))
    d_experts = {"w_gate": dg.astype(experts["w_gate"].dtype),
                 "w_up": du.astype(experts["w_up"].dtype),
                 "w_down": dd.astype(experts["w_down"].dtype)}
    return (dx.astype(x.dtype), None,
            dw[:n * k].reshape(n, k).astype(weights.dtype), d_experts)


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


def moe_ffn_held(x: jax.Array, router_w: jax.Array,
                 router_bias: jax.Array | None, experts: dict,
                 shared: dict | None, *, k: int, first_expert: int = 0,
                 renormalise: bool = True, scaling: float = 1.0,
                 block: int | None = None, router: str = "sigmoid",
                 router_grad: bool = True):
    """A routed expert layer that is told which experts it holds (one
    chip's share under expert parallelism, without its exchange): routes
    every token over ALL ``router_w.shape[-1]`` experts, computes what the
    ``experts["w_up"].shape[0]`` experts from ``first_expert`` on give for
    the tokens routed to them (:func:`held_experts_ffn`) and adds the
    always-on ``shared`` expert where there is one. ``router``:
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
    slice's sum only if rows were dropped."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    n_experts = router_w.shape[-1]
    if block is None:
        block = held_block(b * s, k, n_experts)
    with jax.named_scope("ds.moe_router"):
        logits = jnp.matmul(xt, router_w,
                            preferred_element_type=jnp.float32)
        if router == "sigmoid":
            idx, weights, _ = sigmoid_top_k(logits, router_bias, k,
                                            renormalise=renormalise,
                                            scaling=scaling)
        elif router == "softmax" and router_bias is None:
            idx, weights, _ = softmax_top_k(logits, k,
                                            renormalise=renormalise)
            idx = idx.astype(jnp.int32)
            if scaling != 1.0:
                weights = weights * scaling
        else:
            raise ValueError(
                f"router {router!r}, selection bias "
                f"{'given' if router_bias is not None else 'None'}: "
                f"'sigmoid' takes a bias, 'softmax' takes none")
        if not router_grad:
            weights = lax.stop_gradient(weights)
        load = jnp.bincount(idx.reshape(-1),
                            length=n_experts).astype(jnp.int32)
    out, done = held_experts_ffn(xt, idx, weights, experts,
                                 int(first_expert), int(block))
    if shared is not None:
        with jax.named_scope("ds.moe_shared"):
            _, _, h = _swiglu_rows(xt, shared["w_gate"], shared["w_up"])
            out = out + h @ shared["w_down"]
    return out.reshape(b, s, d), {"load": load, "done": done}


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

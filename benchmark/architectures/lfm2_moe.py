"""Architecture ``lfm2_moe``: what the harness knows about LFM2-MoE
(LiquidAI ``LFM2-24B-A2B`` ``config.json``, ``model_type`` ``lfm2_moe``): a
pre-norm stack of gated short-convolution layers with a grouped-query
attention layer among every few (``layer_types``), a dense SwiGLU in the
first ``num_dense_layers`` and bias-corrected sigmoid-routed experts after
them, of which THIS CHIP HOLDS A SHARE, with no shared expert. Nothing is
imported from ``deepspeed_tpu``. Three parts, as
``architectures/mistral.py``: ``WIDTHS``, the plain float32 ``reference``,
and the operations and bytes the algorithm requires.

With ``norm(x, w) = x rsqrt(mean x^2 + norm_eps) w`` (a plain RMSNorm, over
the hidden size or an attention head), as the published modeling code
(``modeling_lfm2_moe.py``) has them::

    x <- x + Op_l(norm(x, w_op));   x <- x + FF_l(norm(x, w_ffn))
    logits = norm(x_L, w_emb) E^T        (the final norm, "embedding_norm",
                                          is applied LAST; the head is the
                                          table E)

Gated short convolution (``conv`` layers; C = hidden_size channels, n =
``conv_L_cache`` taps; h the normed input)::

    [B | Cg | X] = h W_in                (C -> 3 C, three equal column runs)
    u = B * X
    c_t = sum_{i<n} w[i] u_{t-(n-1)+i}   causal, depthwise, zeros before the
                                         start, no bias: here n shifted sums
    y = (Cg * c) W_out

with no activation anywhere in it. Attention (``full_attention`` layers; H
query heads on Hkv key heads of D = hidden_size / H)::

    q = norm(h W_q, w_q);  k = norm(h W_k, w_k)     a head of D (QK-norm)
    q, k rotated over the whole head (rotate-half pairs (i, i + D/2),
    inv_freq_i = rope_theta^(-2i/D)), after the norm;   v = h W_v
    y = softmax(q k^T / sqrt(D) + causal) v W_o     plain, by q blocks

Dense FF (layer index < ``num_dense_layers``): ``E(h) = (silu(h Wg) *
(h Wu)) Wd`` at ``intermediate_size``. Routed FF (all others), over the
HELD share (``moe.experts`` hold the first E_h of the router's E)::

    s = sigmoid(h Wr)  (E scores);  T = the k largest of s + b
    w_e = s_e / (sum_{j in T} s_j + 1e-6) * routed_scaling_factor
    y = sum_{e in T, e held} w_e E_e(h)             at moe_intermediate_size

and NOTHING else: a token whose experts all lie on other chips gets
nothing from the layer, here as in the program. Every held expert is
evaluated on every token and weighted by its gate (zero where it was not
chosen or is not held): plain, and exact. The loss is the engine's: the
mean next-token cross-entropy over the vocabulary slice, no auxiliary
term.

Weights come in the program's layout (``models/lfm2_moe.py``):
``layers.lead`` and ``layers.tail`` hold unrolled layers,
``layers.period`` the layers of one period each stacked over the whole
periods (``architectures/kimi_linear.py`` ``layers_in_order`` walks
them); a layer holds ``conv`` or ``attn`` and ``mlp`` or ``moe``.

**The mask** is ``architectures/kimi_linear.py``'s, on the selection
scores ``s + b``: a position is left out iff, in some routed layer, a held
expert's selection score lies within ``check.routing_margin`` (as a share
of that layer's selection-score rms) of the boundary it would have to
cross.

Departures from the published description: none here (the program's
renormalisation adds 1e-20 where this adds the published 1e-6). Not in
``config.json`` and so listed under ``assumed`` in the configuration file:
the tied head, the column order ``[B | Cg | X]``, the final norm's place,
QK-norm before the rotation, the rotation over the whole head, the expert
bias's update (the trainer's; it acts after a step, so nothing compared
here sees it) and every initialisation.

Counts: one multiply-add is 2 FLOPs; training is 3 x forward; remat and a
masked tile's dead half are NOT counted. A token's routed experts count as
``num_experts_per_tok`` times the share held here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from architectures.kimi_linear import _swiglu, layers_in_order
from architectures.mellum import attention, rotate
from architectures.mistral import (LOSS_BLOCK, least_seconds,  # noqa: F401
                                   logits_of, loss_of, rms_norm)

WIDTHS = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "layer_types": "layer_types",
    "num_dense_layers": "num_dense_layers",
    "conv_L_cache": "conv_L_cache",
    "conv_bias": "conv_bias",
    "rope_parameters": "rope_parameters",
    "norm_eps": "norm_eps",
    "num_experts": "moe_held_experts",          # the experts HELD here
    "num_routed_experts": "num_experts",        # the router's width
    "num_experts_per_tok": "moe_top_k",
    "norm_topk_prob": "moe_norm_topk",
    "routed_scaling_factor": "routed_scaling_factor",
    "use_expert_bias": "use_expert_bias",
    "vocab_size": "vocab_size",
    "num_hidden_layers": "num_layers",
    "max_position_embeddings": "max_seq_len",
}
OPTIONAL = ()       # the file states every key
CHECK_KEYS = ("routing_margin", "excluded_share_max")

ROW_BLOCK = 4096    # rows per block of a feed-forward (memory bound only)
RENORM_EPS = 1e-6   # the published renormalisation's


# ---- the plain float32 reference -------------------------------------------
def gated_conv(p, h):
    """One gated short convolution on the normed h [B, S, C]: the taps as
    ``n`` shifted sums of u = B * X."""
    c = h.shape[-1]
    n, s = p["taps"].shape[0], h.shape[1]
    bcx = h @ p["w_in"]
    gate_b, gate_c, x = (bcx[..., r * c:(r + 1) * c] for r in range(3))
    u = jnp.pad(gate_b * x, ((0, 0), (n - 1, 0), (0, 0)))
    conv = sum(u[:, i:i + s] * p["taps"][i] for i in range(n))
    return (gate_c * conv) @ p["w_out"]


def attention_mixer(p, h, *, heads, kv_heads, theta, eps):
    b, s, d = h.shape
    hd = d // heads
    q = rms_norm((h @ p["wq"]).reshape(b, s, heads, hd), p["q_norm"], eps)
    k = rms_norm((h @ p["wk"]).reshape(b, s, kv_heads, hd), p["k_norm"], eps)
    v = (h @ p["wv"]).reshape(b, s, kv_heads, hd)
    freq = float(theta) ** (-2.0 * jnp.arange(hd // 2, dtype=jnp.float32)
                            / hd)
    a = attention(rotate(q, freq, 1.0), rotate(k, freq, 1.0), v, None)
    return a.reshape(b, s, heads * hd) @ p["wo"]


def _by_rows(fn, h):
    """``fn`` on h [N, D] by blocks of ``ROW_BLOCK`` rows."""
    return jnp.concatenate([fn(h[r:r + ROW_BLOCK])
                            for r in range(0, h.shape[0], ROW_BLOCK)])


def routed(p, h, *, top_k, first, renormalise, scaling):
    """The held share of a routed layer on h [N, D] -> (out, the least
    distance [N] of a held expert's selection score from the boundary it
    would have to cross, the rms of the selection scores)."""
    scores = jax.nn.sigmoid(h @ p["router"])
    select = scores + p["router_bias"]
    ordered, idx = jax.lax.top_k(select, top_k + 1)
    idx = idx[:, :top_k]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalise:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + RENORM_EPS)
    e = p["experts"]
    n_held = e["w_up"].shape[0]
    if n_held < scores.shape[-1]:
        # a share takes the weights as given in the backward, as the
        # program's does: of their gradient it has only its own terms
        w = jax.lax.stop_gradient(w)
    gates = jnp.einsum("nk,nke->ne", w * scaling, jax.nn.one_hot(
        idx, scores.shape[-1], dtype=jnp.float32))
    out = jnp.zeros_like(h)
    for j in range(n_held):
        one = {name: e[name][j] for name in ("w_gate", "w_up", "w_down")}
        out = out + gates[:, first + j, None] * _by_rows(
            functools.partial(_swiglu, one), h)
    held = select[:, first:first + n_held]
    kth, nxt = ordered[:, top_k - 1, None], ordered[:, top_k, None]
    dist = jnp.where(held >= kth, held - nxt, kth - held)
    return out, jnp.min(dist, axis=-1), jnp.sqrt(jnp.mean(select * select))


@functools.partial(jax.jit, static_argnames=("static",))
def layer(x, p, *, static):
    """One layer on x [B, S, D] float32 -> (x, relative routing distance
    [B, S]; +inf for a layer without a router). ``p``: the layer's weights
    in the program's layout, upcast here; ``static``: the numbers of ``m``
    a layer needs, as a tuple of pairs."""
    m = dict(static)
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
    eps = m["norm_eps"]
    b, s, d = x.shape
    h = rms_norm(x, p["ln1_scale"], eps)
    if "conv" in p:
        x = x + gated_conv(p["conv"], h)
    else:
        x = x + attention_mixer(
            p["attn"], h, heads=m["num_attention_heads"],
            kv_heads=m["num_key_value_heads"], theta=m["rope_theta"],
            eps=eps)
    h = rms_norm(x, p["ln2_scale"], eps).reshape(b * s, d)
    if "mlp" in p:
        out = _by_rows(functools.partial(_swiglu, p["mlp"]), h)
        return x + out.reshape(b, s, d), jnp.full((b, s), jnp.inf)
    out, dist, rms = routed(
        p["moe"], h, top_k=m["num_experts_per_tok"], first=0,
        renormalise=m["norm_topk_prob"], scaling=m["routed_scaling_factor"])
    return x + out.reshape(b, s, d), (dist / rms).reshape(b, s)


def _static(m: dict) -> tuple:
    keep = ("norm_eps", "num_attention_heads", "num_key_value_heads",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor")
    return tuple((k, m[k]) for k in keep) + (
        ("rope_theta", m["rope_parameters"]["rope_theta"]),)


def head_of(params):
    """The head is the embedding table, transposed."""
    return params["embed"]["tokens"].T


def _forward(params, tokens, m: dict):
    """(final-normed hidden [B, S, D] float32, the least relative routing
    distance over the routed layers [B, S])."""
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    x = x.astype(jnp.float32)
    static = _static(m)
    least = jnp.full(x.shape[:2], jnp.inf)
    for p in layers_in_order(params["layers"]):
        x, dist = layer(x, p, static=static)
        least = jnp.minimum(least, dist)
    hidden = rms_norm(x, params["final_norm"]["scale"].astype(jnp.float32),
                      float(m["norm_eps"]))
    return hidden, least


def reference(params, tokens, targets, m: dict, tail: int):
    """(loss as the engine defines it, a float; logits of the last ``tail``
    positions; which of them count, boolean [B, tail]) from ``params`` in
    the program's layout. ``m`` carries ``routing_margin`` (``CHECK_KEYS``)."""
    hidden, least = _forward(params, tokens, m)
    head = head_of(params)
    loss = float(loss_of(hidden, head, targets))
    counted = least >= m["routing_margin"]
    return loss, logits_of(hidden[:, -tail:], head), counted[:, -tail:]


# ---- required operations and bytes -----------------------------------------
def layer_kinds(m: dict) -> list:
    """(token mixer, channel mixer) of each layer held here."""
    return [("conv" if t == "conv" else "attn",
             "dense" if i < m["num_dense_layers"] else "moe")
            for i, t in enumerate(m["layer_types"])]


def _n(m: dict, position: int, kind: str) -> int:
    return sum(k[position] == kind for k in layer_kinds(m))


def held_share(m: dict) -> float:
    """Routed experts a token computes with HERE: its
    ``num_experts_per_tok`` times the share of the experts held."""
    return (m["num_experts_per_tok"] * m["num_experts"]
            / m["num_routed_experts"])


def forward_flops_per_token(m: dict, seq: int) -> dict:
    """Forward FLOPs per token at sequence length ``seq``, by part, summed
    over the layers of each kind."""
    d = m["hidden_size"]
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // nh
    conv_proj = 2 * (d * 3 * d + d * d)
    conv_mix = (2 * m["conv_L_cache"] + 2) * d      # B * X, the taps, Cg *
    attn_proj = 2 * (2 * d * nh * hd + 2 * d * nkv * hd)
    # QK^T and PV: 2 matmuls x 2 FLOPs x head_dim a live pair and head
    attn_pairs = 4 * hd * nh * (seq + 1) / 2
    n_conv, n_attn = _n(m, 0, "conv"), _n(m, 0, "attn")
    n_moe = _n(m, 1, "moe")
    parts = {"conv_projections": n_conv * conv_proj,
             "conv_mix": n_conv * conv_mix,
             "attn_projections": n_attn * attn_proj,
             "attention": n_attn * attn_pairs,
             "dense_ffn": (len(layer_kinds(m)) - n_moe) * 2 * 3 * d
             * m["intermediate_size"],
             "router": n_moe * 2 * d * m["num_routed_experts"],
             "held_experts": n_moe * 2 * 3 * d * m["moe_intermediate_size"]
             * held_share(m),
             "head": 2 * d * m["vocab_size"]}
    parts["total"] = sum(parts.values())
    return parts


def train_flops_per_token(m: dict, seq: int) -> float:
    """Required forward + backward FLOPs per trained token."""
    return 3.0 * forward_flops_per_token(m, seq)["total"]


def gated_conv_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                         itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the step's gated-convolution passes (ALL the
    conv layers held here; ``per: step``) over ``batch`` sequences, for
    the calls a step cannot do without: ONE forward and ONE backward a
    layer (a rerun under remat is the program's choice and shows as lost
    roofline). Each operand read once, each result written once. Forward:
    B, Cg, X read and y written, 4 C a token; backward: those three and dy
    read, dB, dCg, dX written, 7 C a token; the taps (float32) read and
    their gradient written. A few products a channel: the bytes bound it,
    as ``least_seconds`` names it."""
    c, n = m["hidden_size"], m["conv_L_cache"]
    tokens = batch * seq
    if backward:
        flops = tokens * c * (6 * n + 8)
        nbytes = 7 * tokens * c * itemsize + 2 * n * c * 4
    else:
        flops = tokens * c * (2 * n + 2)
        nbytes = 4 * tokens * c * itemsize + n * c * 4
    layers = _n(m, 0, "conv")
    return {"flops": layers * flops, "bytes": layers * nbytes}


def flash_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                    itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the step's flash-attention calls (the
    attention layers held here; ``per: step``) over ``batch`` sequences,
    full causal at LIVE pairs, at the head width hidden / heads (64) with
    ``num_key_value_heads`` shared 4 : 1. Forward: S = QK^T and O = PV.
    Backward (one pass): S again, dV, dP, dQ, dK (5 matmuls). Each operand
    read once, each result written once (q, o, do, dq at the query heads;
    k, v, dk, dv at the key heads; the float32 log-sum-exp row a head)."""
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // nh
    pairs = batch * nh * seq * (seq + 1) // 2
    q_like = batch * seq * nh * hd * itemsize
    kv_like = batch * seq * nkv * hd * itemsize
    lse = batch * seq * nh * 4
    if backward:
        flops, nbytes = 5 * 2 * hd * pairs, 4 * q_like + 4 * kv_like + lse
    else:
        flops, nbytes = 2 * 2 * hd * pairs, 2 * q_like + 2 * kv_like + lse
    layers = _n(m, 0, "attn")
    return {"flops": layers * flops, "bytes": layers * nbytes}


def moe_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                  itemsize: int = 2, rows: float | None = None) -> dict:
    """FLOPs and HBM bytes of the step's held-expert calls (the routed
    layers held here; ``per: step``) at ``rows`` rows (token, choice) a
    routed layer, as the program counted them; None: a balanced router's,
    a token's ``held_share``. Three matmuls a row forward and six backward:
    the work the mathematics needs, the count of every routed architecture
    here but Laguna's (a roofline is a share of the LEAST time). The
    backward rule keeps nothing of the forward but its inputs and makes
    ``gate`` and ``up`` again before its six products, so eleven units
    run; that rerun is the program's choice, as remat's rerun of the
    forward is, and neither is counted, nor is a tile's padding. Bytes:
    every held expert's weights read once (and their float32 gradients
    written once, backward), a row's input gathered and its output
    scattered."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    if rows is None:
        rows = batch * seq * held_share(m)
    weights = m["num_experts"] * 3 * d * f
    flops = rows * 2 * 3 * d * f
    nbytes = weights * itemsize + 2 * rows * d * itemsize
    if backward:
        flops, nbytes = 2 * flops, nbytes + weights * 4 + rows * d * itemsize
    layers = _n(m, 1, "moe")
    return {"flops": layers * flops, "bytes": layers * nbytes}

"""Architecture ``xing4``: what the harness knows about Xing4.0
(XingChen-AGI ``Xing4.0-29B-A4B`` ``config.json``, ``model_type``
``xing4_0``): a stack whose residual path is ``hc_mult`` streams mixed by
Sinkhorn-constrained hyper-connections (mHC, arXiv:2512.24880, on
arXiv:2409.19606) round two sublayers a layer: rotated, low-rank-query
latent attention (MLA), then a dense SwiGLU in the first
``first_k_dense_replace`` layers and bias-corrected sigmoid-routed experts
with a shared expert after them, of which THIS CHIP HOLDS A SHARE. Nothing
is imported from ``deepspeed_tpu``. Three parts, as
``architectures/mistral.py``: ``WIDTHS``, the plain float32 ``reference``,
and the operations and bytes the algorithm requires.

The state between sublayers is ``X`` in R^{n x C} a token (n streams of
C = hidden_size). For each sublayer ``F`` (its own ``phi`` [n C, n (n +
2)] with columns ``[pre | post | res]``, ``b``, ``alpha`` [3])::

    xv  = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)               no gain
    Hp~ = a_pre  (xv phi_pre)  + b_pre;   Ho~ = a_post (xv phi_post) + b_post
    Hr~ = a_res  mat(xv phi_res) + b_res                       [n, n]
    H_pre = sigmoid(Hp~);   H_post = 2 sigmoid(Ho~)
    M = exp(clip(Hr~, clamp_min, clamp_max));  hc_sinkhorn_iters times:
        M <- M / (rowsum(M) + hc_eps);  M <- M / (colsum(M) + hc_eps)
    H_res = M
    u  = sum_i H_pre[i] X[i];    y = F(rmsnorm(u, g))
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

The embedding is copied to all n streams; after the last layer the streams
are summed, then the final RMSNorm and the untied head. MLA (H heads)::

    cq = rmsnorm(u Wqa);  q = cq Wqb  as H x (nope + rope)
    [c, k_pe] = u Wkva  (kv_lora + rope);  [k_nope, v] = rmsnorm(c) Wkvb
    q_pe, k_pe rotated: rotate-half pairs (i, i + rope / 2) at YaRN's
    frequencies (``architectures/mellum.py`` ``inv_freq``), cos and sin
    times mscale' / mscale_all_dim' (here 1);  k_h = [k_nope_h, k_pe]
    y = softmax_causal(q k^T (nope + rope)^-1/2 m^2) v Wo
    x' = 0.1 x ln(factor) + 1;  m = mscale_all_dim' = 1.4159 at factor 64

Routed layer: ``architectures/kimi_linear.py`` ``routed`` as it is
(sigmoid scores, the top k of scores + bias, the chosen scores over their
sum times ``routed_scaling_factor``, the held experts, plus the shared
expert), and its mask: a position is left out iff, in some routed layer,
a held expert's selection score lies within ``check.routing_margin`` (as a
share of that layer's selection-score rms) of the boundary it would have
to cross. The loss is the engine's: the mean next-token cross-entropy
over the vocabulary slice, no auxiliary term and no multi-token
prediction module (``num_nextn_predict_layers`` 0 here).

Weights come in the program's layout (``models/xing4.py``): a layer holds
``mla``, ``hc1`` and ``hc2`` (the two sublayers' ``phi``, ``b``,
``alpha``) and ``mlp`` or ``moe``; ``layers_in_order`` walks ``lead``,
``period``, ``tail``.

What ``config.json`` does not settle is listed under ``assumed`` in the
configuration file: where mHC wraps, the spread and the fold, the norm
without gain, what ``hc_eps`` guards, the clamp before ``exp``, the
rotation's pairing, every initialisation.

Counts: one multiply-add is 2 FLOPs; training is 3 x forward; remat is
NOT counted. A token's routed experts count as ``num_experts_per_tok``
times the share held here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from architectures.kimi_linear import (_swiglu, causal_attention,
                                       layers_in_order, routed)
from architectures.lfm2_moe import ROW_BLOCK, _by_rows  # noqa: F401
from architectures.mellum import _hashable, inv_freq, rotate
from architectures.mistral import (LOSS_BLOCK, least_seconds,  # noqa: F401
                                   logits_of, loss_of, rms_norm)

WIDTHS = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "rope_scaling": "rope_scaling",
    "attention_bias": "use_bias",
    "tie_word_embeddings": "tie_embeddings",
    "hc_mult": "hc_mult",
    "hc_sinkhorn_iters": "hc_sinkhorn_iters",
    "hc_eps": "hc_eps",
    "mhc_h_res_clamp_min": "mhc_h_res_clamp_min",
    "mhc_h_res_clamp_max": "mhc_h_res_clamp_max",
    "first_k_dense_replace": "first_k_dense_replace",
    "n_routed_experts": "moe_held_experts",     # the experts HELD here
    "num_experts": "moe_held_experts",          # ... under the name
    #                                             reducers/moe.py reads
    "num_routed_experts": "num_experts",        # the router's width
    "n_shared_experts": "moe_num_shared_experts",
    "num_experts_per_tok": "moe_top_k",
    "norm_topk_prob": "moe_norm_topk",
    "routed_scaling_factor": "routed_scaling_factor",
    "scoring_func": "moe_router_activation",
    "vocab_size": "vocab_size",
    "num_hidden_layers": "num_layers",
    "max_position_embeddings": "max_seq_len",
}
OPTIONAL = ("num_experts",)     # the file states every published key
CHECK_KEYS = ("routing_margin", "excluded_share_max")


# ---- the plain float32 reference -------------------------------------------
def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def hyper_coefficients(x, hc, *, eps, clamp, iters):
    """x [T, n, C] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n]) of one
    sublayer, a token at a time as the equations have it."""
    t, n, c = x.shape
    flat = x.reshape(t, n * c)
    xv = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    z = xv @ hc["phi"]
    a, b = hc["alpha"], hc["b"]
    h_pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    raw = a[2] * z[:, 2 * n:].reshape(t, n, n) + b[2 * n:].reshape(n, n)
    m = jnp.exp(jnp.clip(raw, clamp[0], clamp[1]))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)    # rows
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)    # columns
    return h_pre, h_post, m


def hyper_sublayer(x, hc, f, *, eps, clamp, iters):
    """x [B, S, n, C] -> (X', whatever else ``f`` returns beside y)."""
    b, s, n, c = x.shape
    flat = x.reshape(b * s, n, c)
    h_pre, h_post, h_res = hyper_coefficients(flat, hc, eps=eps, clamp=clamp,
                                              iters=iters)
    u = jnp.einsum("tn,tnc->tc", h_pre, flat)
    y, *rest = f(u.reshape(b, s, c))
    out = (jnp.einsum("tij,tjc->tic", h_res, flat)
           + h_post[:, :, None] * y.reshape(b * s, 1, c))
    return (out.reshape(x.shape), *rest)


def mla_mixer(p, h, *, heads, nope, rope, dv, lora, eps, freq, factor,
              mscale):
    b, s, _ = h.shape
    cq = rms_norm(h @ p["wq_a"], p["q_norm"], eps)
    q = (cq @ p["wq_b"]).reshape(b, s, heads, nope + rope)
    kva = h @ p["w_kva"]
    kv = (rms_norm(kva[..., :lora], p["kv_norm"], eps) @ p["w_kvb"]).reshape(
        b, s, heads, nope + dv)
    k_pe = rotate(kva[:, :, None, lora:], freq, factor)
    q = jnp.concatenate(
        [q[..., :nope], rotate(q[..., nope:], freq, factor)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, heads, rope))],
        axis=-1)
    # causal_attention scales by (nope + rope)^-1/2: m^2 rides on q
    a = causal_attention(q * mscale ** 2, k, kv[..., nope:])
    return a.reshape(b, s, heads * dv) @ p["wo"]


def rope_section(m: dict) -> dict:
    """``rope_scaling`` as one section of ``rope_parameters``
    (``architectures/mellum.py`` ``inv_freq``): the table's own factor is
    mscale' / mscale_all_dim'."""
    rs = dict(m["rope_scaling"])
    return {"rope_type": rs["type"], "rope_theta": m["rope_theta"],
            "factor": rs["factor"],
            "original_max_position_embeddings":
                rs["original_max_position_embeddings"],
            "beta_fast": rs["beta_fast"], "beta_slow": rs["beta_slow"],
            "attention_factor": (yarn_mscale(rs["factor"], rs["mscale"])
                                 / yarn_mscale(rs["factor"],
                                               rs["mscale_all_dim"]))}


@functools.partial(jax.jit, static_argnames=("static",))
def layer(x, p, *, static):
    """One layer on x [B, S, n, C] float32 -> (x, relative routing
    distance [B, S]; +inf for a layer without a router). ``p``: the
    layer's weights in the program's layout, upcast here; ``static``: the
    numbers of ``m`` a layer needs, hashable."""
    m = dict(static)
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
    eps = m["rms_norm_eps"]
    b, s, _, d = x.shape
    hyper = dict(eps=m["hc_eps"], iters=m["hc_sinkhorn_iters"],
                 clamp=(m["mhc_h_res_clamp_min"], m["mhc_h_res_clamp_max"]))
    rs = dict(m["rope_scaling"])
    freq, factor = inv_freq(m["qk_rope_head_dim"], rope_section(m))

    def attention(u):
        return (mla_mixer(
            p["mla"], rms_norm(u, p["ln1_scale"], eps),
            heads=m["num_attention_heads"], nope=m["qk_nope_head_dim"],
            rope=m["qk_rope_head_dim"], dv=m["v_head_dim"],
            lora=m["kv_lora_rank"], eps=eps, freq=freq, factor=factor,
            mscale=yarn_mscale(rs["factor"], rs["mscale_all_dim"])),)

    def channel(u):
        h = rms_norm(u, p["ln2_scale"], eps).reshape(b * s, d)
        if "mlp" in p:
            out = _by_rows(functools.partial(_swiglu, p["mlp"]), h)
            return out.reshape(b, s, d), jnp.full((b, s), jnp.inf)
        out, dist, rms = routed(
            p["moe"], h, top_k=m["num_experts_per_tok"], first=0,
            renormalise=m["norm_topk_prob"],
            scaling=m["routed_scaling_factor"])
        return out.reshape(b, s, d), (dist / rms).reshape(b, s)

    x, = hyper_sublayer(x, p["hc1"], attention, **hyper)
    return hyper_sublayer(x, p["hc2"], channel, **hyper)


def _static(m: dict) -> tuple:
    keep = ("rms_norm_eps", "num_attention_heads", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rope_theta",
            "rope_scaling", "hc_eps", "hc_sinkhorn_iters",
            "mhc_h_res_clamp_min", "mhc_h_res_clamp_max",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor")
    return tuple((k, _hashable(m[k])) for k in keep)


def _forward(params, tokens, m: dict):
    """(final-normed hidden [B, S, D] float32, the least relative routing
    distance over the routed layers [B, S])."""
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    x = x.astype(jnp.float32)
    x = jnp.broadcast_to(x[:, :, None, :],
                         (*x.shape[:2], m["hc_mult"], x.shape[-1]))
    static = _static(m)
    least = jnp.full(x.shape[:2], jnp.inf)
    for p in layers_in_order(params["layers"]):
        x, dist = layer(x, p, static=static)
        least = jnp.minimum(least, dist)
    hidden = rms_norm(jnp.sum(x, axis=2),
                      params["final_norm"]["scale"].astype(jnp.float32),
                      float(m["rms_norm_eps"]))
    return hidden, least


def reference(params, tokens, targets, m: dict, tail: int):
    """(loss as the engine defines it, a float; logits of the last ``tail``
    positions; which of them count, boolean [B, tail]) from ``params`` in
    the program's layout. ``m`` carries ``routing_margin`` (``CHECK_KEYS``)."""
    hidden, least = _forward(params, tokens, m)
    loss = float(loss_of(hidden, params["lm_head"], targets))
    counted = least >= m["routing_margin"]
    return (loss, logits_of(hidden[:, -tail:], params["lm_head"]),
            counted[:, -tail:])


# ---- required operations and bytes -----------------------------------------
def layer_kinds(m: dict) -> list:
    """(token mixer, channel mixer) of each layer held here."""
    return [("mla", "dense" if i < m["first_k_dense_replace"] else "moe")
            for i in range(m["num_hidden_layers"])]


def _n(m: dict, position: int, kind: str) -> int:
    return sum(k[position] == kind for k in layer_kinds(m))


def held_share(m: dict) -> float:
    """Routed experts a token computes with HERE: its
    ``num_experts_per_tok`` times the share of the experts held."""
    return (m["num_experts_per_tok"] * m["n_routed_experts"]
            / m["num_routed_experts"])


def forward_flops_per_token(m: dict, seq: int) -> dict:
    """Forward FLOPs per token at sequence length ``seq``, by part, summed
    over the layers."""
    d, n = m["hidden_size"], m["hc_mult"]
    nh = m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    layers = m["num_hidden_layers"]
    n_moe = _n(m, 1, "moe")
    mla_proj = 2 * (d * m["q_lora_rank"] + m["q_lora_rank"] * nh * qk
                    + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
                    + m["kv_lora_rank"] * nh * (m["qk_nope_head_dim"]
                                                + m["v_head_dim"])
                    + nh * m["v_head_dim"] * d)
    mla_attn = 2 * (qk + m["v_head_dim"]) * nh * (seq + 1) / 2
    expert = 2 * 3 * d * m["moe_intermediate_size"]
    # a sublayer: xv phi, then H_pre, H_res and H_post over n C channels
    hyper = 2 * n * d * n * (n + 2) + 2 * n * (n + 2) * d
    parts = {"mla_projections": layers * mla_proj,
             "mla_attention": layers * mla_attn,
             "hyper_connections": 2 * layers * hyper,
             "dense_ffn": (layers - n_moe) * 2 * 3 * d
             * m["intermediate_size"],
             "routed_layers": n_moe * (
                 2 * d * m["num_routed_experts"]
                 + expert * m["n_shared_experts"] + expert * held_share(m)),
             "head": 2 * d * m["vocab_size"]}
    parts["total"] = sum(parts.values())
    return parts


def train_flops_per_token(m: dict, seq: int) -> float:
    """Required forward + backward FLOPs per trained token."""
    return 3.0 * forward_flops_per_token(m, seq)["total"]


def mhc_pre_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                      itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the step's passes IN FRONT of a sublayer
    (two a layer; ``per: step``) over ``batch`` sequences, for the calls a
    step cannot do without: ONE forward and ONE backward a sublayer
    (remat's rerun is the program's choice and shows as lost roofline).
    What the equations need, each operand read once and each result
    written once. Forward: X read (n C a token), ``u`` (C) and the n (n +
    2) float32 coefficients written, ``phi`` read. Backward: X, ``du`` and
    the coefficients' cotangents read, ``dX`` written, ``phi`` read and its
    float32 gradient written. The products are ``xv phi`` (2 n C n (n + 2)
    a token; twice more backward: ``dX`` and ``dphi``), the squares and
    ``u`` (2 n C each; the same again backward). The bytes bound it."""
    d, n = m["hidden_size"], m["hc_mult"]
    tokens, k = batch * seq, n * (n + 2)
    wide = tokens * n * d
    if backward:
        flops = tokens * (4 * n * d * k + 8 * n * d)
        nbytes = (2 * wide + tokens * d) * itemsize + tokens * k * 4 \
            + n * d * k * (itemsize + 4)
    else:
        flops = tokens * (2 * n * d * k + 4 * n * d)
        nbytes = (wide + tokens * d) * itemsize + tokens * k * 4 \
            + n * d * k * itemsize
    calls = 2 * m["num_hidden_layers"]
    return {"flops": calls * flops, "bytes": calls * nbytes}


def mhc_post_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                       itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the step's passes BEHIND a sublayer (two a
    layer; ``per: step``), ``X' = H_res X + H_post^T y``, as
    ``mhc_pre_call_cost`` counts. Forward: X and y read, X' written, the
    n + n n float32 coefficients read. Backward: X, y and dX' read, dX and
    dy written, the coefficients read and their cotangents written; the
    products twice the forward's (dX and dy; dH_res and dH_post). The
    bytes bound it."""
    d, n = m["hidden_size"], m["hc_mult"]
    tokens, k = batch * seq, n * (n + 1)
    wide = tokens * n * d
    if backward:
        flops = tokens * 4 * k * d
        nbytes = (3 * wide + 2 * tokens * d) * itemsize + 2 * tokens * k * 4
    else:
        flops = tokens * 2 * k * d
        nbytes = (2 * wide + tokens * d) * itemsize + tokens * k * 4
    calls = 2 * m["num_hidden_layers"]
    return {"flops": calls * flops, "bytes": calls * nbytes}


def mla_flash_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                        itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the step's flash-attention calls (every
    layer; ``per: step``), full causal, at the PUBLISHED key width (nope +
    rope) and value width, whatever the kernel pads to, as
    ``architectures/kimi_linear.py`` counts them. Forward: S = QK^T at the
    key width and O = PV at the value width. Backward (one pass): S again,
    dQ and dK at the key width; dV and dP at the value width. Each operand
    read once, each result written once, the float32 log-sum-exp row a
    head."""
    nh = m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    dv = m["v_head_dim"]
    pairs = batch * nh * seq * (seq + 1) // 2
    rows = batch * seq * nh
    if backward:
        flops = 2 * pairs * (3 * qk + 2 * dv)
        nbytes = rows * ((4 * qk + 4 * dv) * itemsize + 4)
    else:
        flops = 2 * pairs * (qk + dv)
        nbytes = rows * ((2 * qk + 2 * dv) * itemsize + 4)
    n = m["num_hidden_layers"]
    return {"flops": n * flops, "bytes": n * nbytes}


def moe_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                  itemsize: int = 2, rows: float | None = None) -> dict:
    """FLOPs and HBM bytes of the step's held-expert calls (the routed
    layers held here; ``per: step``) at ``rows`` rows (token, choice) a
    routed layer, as the program counted them; None: a balanced router's,
    a token's ``held_share``. Three matmuls a row forward and six backward
    (the backward's second run of the two input matmuls is its own choice
    and is not counted). Bytes: every held expert's weights read once (and
    their float32 gradients written once, backward), a row's input
    gathered and its output scattered."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    if rows is None:
        rows = batch * seq * held_share(m)
    weights = m["n_routed_experts"] * 3 * d * f
    flops = rows * 2 * 3 * d * f
    nbytes = weights * itemsize + 2 * rows * d * itemsize
    if backward:
        flops, nbytes = 2 * flops, nbytes + weights * 4 + rows * d * itemsize
    n = _n(m, 1, "moe")
    return {"flops": n * flops, "bytes": n * nbytes}

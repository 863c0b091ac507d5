"""Architecture ``qwen3_next``: what the harness knows about Qwen3-Next
(Qwen ``Qwen3-Next-80B-A3B-Instruct`` ``config.json``, ``model_type``
``qwen3_next``): a pre-norm stack of three Gated DeltaNet layers to one
gated softmax attention layer (``full_attention_interval``), each followed
by softmax-routed experts of which THIS CHIP HOLDS A SHARE beside one
gated shared expert. Nothing is imported from ``deepspeed_tpu``. Three
parts, as ``architectures/mistral.py``: ``WIDTHS``, the plain float32
``reference``, and the operations and bytes the algorithm requires.

With ``norm(x, w) = x rsqrt(mean x^2 + rms_norm_eps) (1 + w)`` (the
family's RMSNorm, over the hidden size or an attention head), layer ``l``
is ``full_attention`` where ``(l + 1) % full_attention_interval == 0`` and
``linear_attention`` otherwise::

    x <- x + Mix_l(norm(x, w1));   x <- x + Experts_l(norm(x, w2))

Gated DeltaNet (Hk = ``linear_num_key_heads`` of dk, Hv =
``linear_num_value_heads`` of dv; h the normed input)::

    [q | k | v | z] = h W_qkvz;   [b | a] = h W_ba
    q, k, v = silu(conv4([q | k | v]))   causal, depthwise, no bias: tap i
                                         of w multiplies x_{t-3+i}
    q = l2norm(q) / sqrt(dk),  k = l2norm(k)    a head; l2norm(x) =
                                         x rsqrt(sum x^2 + 1e-6)
    key head j serves value heads j Hv/Hk .. (j + 1) Hv/Hk - 1
    beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t + dt_bias)
                                         one number a value head
    S_t = exp(g_t) S_{t-1};  S_t += beta_t k_t (v_t - S_t^T k_t)^T
    o_t = S_t^T q_t                      (S [dk, dv] float32, S_0 = 0)
    y = (o rsqrt(mean_dv o^2 + eps) w_o * silu(z)) W_out

run here TOKEN BY TOKEN under one ``lax.scan`` (the program runs the
chunked form, a chunk's decay one mask a head). Gated attention
(H query, Hkv key/value heads of ``head_dim`` D, given, NOT hidden / H)::

    [q | gate] = h W_q as H heads of 2 D (first D the query, last D the gate)
    q = norm(q, w_q);  k = norm(h W_k, w_k)             a head of D
    q, k rotated on dimensions 0 .. r-1, r = partial_rotary_factor D
        (rotate-half pairs (i, i + r/2), inv_freq_i = rope_theta^(-2i/r)),
        r .. D-1 untouched
    a = softmax(q k^T / sqrt(D) + causal) v             H / Hkv query heads
                                                        a key head
    y = (a * sigmoid(gate)) W_o

Experts, every layer, over the HELD share (``moe.experts`` hold the first
E_h of the router's E)::

    r = h2 Wr (E logits);  p = softmax(r);  T = the k largest
    w_e = p_e / sum_{j in T} p_j                        (norm_topk_prob)
    y = sum_{e in T, e held} w_e E_e(h2) + sigmoid(h2 w_s) E_shared(h2)

with ``E(h) = (silu(h Wg) * (h Wu)) Wd``. After the last layer ``norm``
and the head over the vocabulary slice; the loss is the engine's, the mean
next-token cross-entropy, with no auxiliary term. Every held expert is
evaluated on every token and weighted by its gate (zero where it was not
chosen or is not held): plain, and exact. What the absent experts would
have added is left out, as in the program.

Weights come in the program's layout (``models/qwen3_next.py``):
``layers.period`` holds the layers of one period each stacked over the
whole periods, ``layers.tail`` what follows them
(``architectures/kimi_linear.py`` ``layers_in_order`` walks them); a layer
holds its mixer's weights under ``gdn`` or ``attn`` and ``moe``.

**The mask** is ``architectures/mellum.py``'s, on the router's logits: a
position is left out iff, in some layer, a held expert's logit lies within
``check.routing_margin`` (as a share of that layer's logits' rms) of the
boundary it would have to cross.

Departures from the published description: the columns of ``W_qkvz`` and
``W_ba`` are runs of whole heads (the checkpoint interleaves them a
key-head group; a permutation of seeded columns). Not in the published
config and so not here: the multi-token prediction module, an auxiliary
loss; ``intermediate_size`` is unused (every layer is routed). The
configuration file lists them, and every line of the above that no key of
``config.json`` states, under ``assumed``.

Counts: one multiply-add is 2 FLOPs; training is 3 x forward; remat, the
chunked form's extra products and a masked tile's dead half are NOT
counted. A token's routed experts count as ``num_experts_per_tok`` times
the share held here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from architectures.kimi_linear import (_conv, _l2norm, _silu, _swiglu,
                                       layers_in_order)
from architectures.mellum import attention, rotate, routed
from architectures.mistral import (LOSS_BLOCK, least_seconds,  # noqa: F401
                                   logits_of, loss_of)

WIDTHS = {
    "hidden_size": "hidden_size",
    "head_dim": "head_dim",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "partial_rotary_factor": "rotary_pct",
    "rope_theta": "rope_theta",
    "full_attention_interval": "full_attention_interval",
    "linear_num_key_heads": "linear_num_key_heads",
    "linear_num_value_heads": "linear_num_value_heads",
    "linear_key_head_dim": "linear_key_head_dim",
    "linear_value_head_dim": "linear_value_head_dim",
    "linear_conv_kernel_dim": "linear_conv_kernel_dim",
    "moe_intermediate_size": "moe_intermediate_size",
    "shared_expert_intermediate_size": "shared_expert_intermediate_size",
    "num_experts": "moe_held_experts",          # the experts HELD here
    "num_routed_experts": "num_experts",        # the router's width
    "num_experts_per_tok": "moe_top_k",
    "norm_topk_prob": "moe_norm_topk",
    "decoder_sparse_step": "decoder_sparse_step",
    "mlp_only_layers": "mlp_only_layers",
    "rms_norm_eps": "norm_eps",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "num_hidden_layers": "num_layers",
    "max_position_embeddings": "max_seq_len",
}
OPTIONAL = ()       # the file states every key
CHECK_KEYS = ("routing_margin", "excluded_share_max")


# ---- the plain float32 reference -------------------------------------------
def norm(x, w, eps):
    """The family's RMSNorm over the last axis: (1 + w)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


def gdn_recurrence(q, k, v, g, beta):
    """Token by token. q, k [B, S, H, dk]; v [B, S, H, dv]; g, beta
    [B, S, H]; the state [B, H, dk, dv] float32 from zero."""
    b, _, h, dk = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        u = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t * b_t[..., None], u)
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    xs = tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(
        step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.swapaxes(o, 0, 1)


def gdn_mixer(p, h, *, hk, hv, dk, dv, eps):
    b, s, _ = h.shape
    kw, vw = hk * dk, hv * dv
    qkvz = h @ p["w_qkvz"]
    qkv = _silu(_conv(qkvz[..., :2 * kw + vw], p["conv"]))
    q = _l2norm(qkv[..., :kw].reshape(b, s, hk, dk)) * dk ** -0.5
    k = _l2norm(qkv[..., kw:2 * kw].reshape(b, s, hk, dk))
    v = qkv[..., 2 * kw:].reshape(b, s, hv, dv)
    z = qkvz[..., 2 * kw + vw:].reshape(b, s, hv, dv)
    q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
    ba = h @ p["w_ba"]
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    o = gdn_recurrence(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * p["o_norm"]
    return (o * _silu(z)).reshape(b, s, vw) @ p["wo"]


def rotary_freq(rot: int, theta: float):
    i = jnp.arange(rot // 2, dtype=jnp.float32)
    return float(theta) ** (-2.0 * i / rot)


def gated_attention(p, h, *, heads, kv_heads, hd, rot, theta, eps):
    b, s, _ = h.shape
    qg = (h @ p["wq"]).reshape(b, s, heads, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (h @ p["wk"]).reshape(b, s, kv_heads, hd)
    v = (h @ p["wv"]).reshape(b, s, kv_heads, hd)
    q, k = norm(q, p["q_norm"], eps), norm(k, p["k_norm"], eps)
    freq = rotary_freq(rot, theta)
    part = lambda x: jnp.concatenate(  # noqa: E731
        [rotate(x[..., :rot], freq, 1.0), x[..., rot:]], axis=-1)
    a = attention(part(q), part(k), v, None) * jax.nn.sigmoid(gate)
    return a.reshape(b, s, heads * hd) @ p["wo"]


def experts(p, h, *, top_k, renormalise):
    """The held share and the gated shared expert on h [N, D] -> (out,
    the least relative distance [N] of a held expert's logit from the
    boundary it would have to cross)."""
    out, dist, rms = routed(p, h, top_k=top_k, first=0,
                            renormalise=renormalise)
    if "shared" in p:
        out = out + jax.nn.sigmoid(h @ p["shared_gate"]) * _swiglu(
            p["shared"], h)
    return out, dist / rms


@functools.partial(jax.jit, static_argnames=("static",))
def layer(x, p, *, static):
    """One layer on x [B, S, D] float32 -> (x, relative routing distance
    [B, S]). ``p``: the layer's weights in the program's layout, upcast
    here; ``static``: the numbers of ``m`` a layer needs, hashable."""
    m = dict(static)
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
    eps = m["rms_norm_eps"]
    b, s, d = x.shape
    h = norm(x, p["ln1_scale"], eps)
    if "gdn" in p:
        x = x + gdn_mixer(p["gdn"], h, hk=m["linear_num_key_heads"],
                          hv=m["linear_num_value_heads"],
                          dk=m["linear_key_head_dim"],
                          dv=m["linear_value_head_dim"], eps=eps)
    else:
        hd = m["head_dim"]
        x = x + gated_attention(
            p["attn"], h, heads=m["num_attention_heads"],
            kv_heads=m["num_key_value_heads"], hd=hd,
            rot=int(hd * m["partial_rotary_factor"]), theta=m["rope_theta"],
            eps=eps)
    h = norm(x, p["ln2_scale"], eps)
    out, dist = experts(p["moe"], h.reshape(b * s, d),
                        top_k=m["num_experts_per_tok"],
                        renormalise=m["norm_topk_prob"])
    return x + out.reshape(b, s, d), dist.reshape(b, s)


_LAYER_KEYS = ("rms_norm_eps", "head_dim", "num_attention_heads",
               "num_key_value_heads", "partial_rotary_factor", "rope_theta",
               "linear_num_key_heads", "linear_num_value_heads",
               "linear_key_head_dim", "linear_value_head_dim",
               "num_experts_per_tok", "norm_topk_prob")


def _forward(params, tokens, m: dict):
    """(final-normed hidden [B, S, D] float32, the least relative routing
    distance over the layers [B, S])."""
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    x = x.astype(jnp.float32)
    static = tuple((k, m[k]) for k in _LAYER_KEYS)
    least = jnp.full(x.shape[:2], jnp.inf)
    for p in layers_in_order(params["layers"]):
        x, dist = layer(x, p, static=static)
        least = jnp.minimum(least, dist)
    hidden = norm(x, params["final_norm"]["scale"].astype(jnp.float32),
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
def layer_kinds(m: dict) -> list[str]:
    n = m["full_attention_interval"]
    return ["full_attention" if (i + 1) % n == 0 else "linear_attention"
            for i in range(m["num_hidden_layers"])]


def _n(m: dict, kind: str) -> int:
    return layer_kinds(m).count(kind)


def held_share(m: dict) -> float:
    """Routed experts a token computes with HERE: its
    ``num_experts_per_tok`` times the share of the experts held."""
    return (m["num_experts_per_tok"] * m["num_experts"]
            / m["num_routed_experts"])


def forward_flops_per_token(m: dict, seq: int) -> dict:
    """Forward FLOPs per token at sequence length ``seq``, by part, summed
    over the layers of each kind."""
    d, hd = m["hidden_size"], m["head_dim"]
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    kw, vw = hk * dk, hv * dv
    gdn_proj = (2 * (d * (2 * kw + 2 * vw) + d * 2 * hv + vw * d)
                + 2 * m["linear_conv_kernel_dim"] * (2 * kw + vw))
    # the recurrence: k^T S, the rank-one write and S^T q, 2 dk dv each
    gdn_state = 6 * hv * dk * dv
    attn_proj = 2 * (d * nh * 2 * hd + 2 * d * nkv * hd + nh * hd * d)
    # QK^T and PV: 2 matmuls x 2 FLOPs x head_dim a live pair and head
    attn_pairs = 4 * hd * nh * (seq + 1) / 2
    expert = 2 * 3 * d * m["moe_intermediate_size"]
    shared = 2 * 3 * d * m["shared_expert_intermediate_size"] + 2 * d
    n, n_gdn = m["num_hidden_layers"], _n(m, "linear_attention")
    parts = {"gdn_projections": n_gdn * gdn_proj,
             "gdn_state": n_gdn * gdn_state,
             "attn_projections": (n - n_gdn) * attn_proj,
             "attention": (n - n_gdn) * attn_pairs,
             "router": n * 2 * d * m["num_routed_experts"],
             "shared_expert": n * shared,
             "held_experts": n * expert * held_share(m),
             "head": 2 * d * m["vocab_size"]}
    parts["total"] = sum(parts.values())
    return parts


def train_flops_per_token(m: dict, seq: int) -> float:
    """Required forward + backward FLOPs per trained token."""
    return 3.0 * forward_flops_per_token(m, seq)["total"]


def gdn_call_cost(m: dict, batch: int, seq: int, *, backward: bool) -> dict:
    """FLOPs and HBM bytes of the step's Gated DeltaNet scans (ALL such
    layers held here; ``per: step``) over ``batch`` sequences. Forward:
    the recurrence's three products a value head and token; q, k, v
    (bf16, at the VALUE heads: the scan reads a key head once for each
    value head it serves), the gate and beta AS [B, S, H] float32 (one
    number a head, whatever the program makes of it) read once, o written
    once. Backward: twice the products; those five and do read, their
    five gradients written. The chunked form's score matrices and its state
    history are its own choice and are not counted."""
    h = m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    tokens = batch * seq * h
    flops = 6 * dk * dv * tokens
    reads = tokens * ((2 * dk + dv) * 2 + 4 + 4)    # q k v, g, beta
    if backward:
        flops, nbytes = 2 * flops, 2 * reads + tokens * dv * 2
    else:
        nbytes = reads + tokens * dv * 2
    n = _n(m, "linear_attention")
    return {"flops": n * flops, "bytes": n * nbytes}


def gattn_flash_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                          itemsize: int = 2) -> dict:
    """The gated attention layers' flash kernels (``per: step``), full
    causal, at LIVE pairs. Forward: S = QK^T and O = PV. Backward (one
    pass): S again, dV, dP, dQ, dK (5 matmuls). Each operand read once,
    each result written once (q, o, do, dq at the query heads; k, v, dk,
    dv at the key heads; the float32 log-sum-exp row a head)."""
    hd, nh, nkv = (m["head_dim"], m["num_attention_heads"],
                   m["num_key_value_heads"])
    pairs = batch * nh * seq * (seq + 1) // 2
    q_like = batch * seq * nh * hd * itemsize
    kv_like = batch * seq * nkv * hd * itemsize
    lse = batch * seq * nh * 4
    if backward:
        flops, nbytes = 5 * 2 * hd * pairs, 4 * q_like + 4 * kv_like + lse
    else:
        flops, nbytes = 2 * 2 * hd * pairs, 2 * q_like + 2 * kv_like + lse
    n = _n(m, "full_attention")
    return {"flops": n * flops, "bytes": n * nbytes}


def moe_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                  itemsize: int = 2, rows: float | None = None) -> dict:
    """FLOPs and HBM bytes of the step's held-expert calls (every layer
    held here; ``per: step``) at ``rows`` rows (token, choice) a layer, as
    the program counted them; None: a balanced router's, a token's
    ``held_share``. Three matmuls a row forward and six backward (the
    backward's second run of the two input matmuls is its own choice and
    is not counted; nor is a tile's padding). Bytes: every held expert's
    weights read once (and their float32 gradients written once,
    backward), a row's input gathered and its output scattered. The
    shared expert is not in it (scope ds.moe_shared)."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    if rows is None:
        rows = batch * seq * held_share(m)
    weights = m["num_experts"] * 3 * d * f
    flops = rows * 2 * 3 * d * f
    nbytes = weights * itemsize + 2 * rows * d * itemsize
    if backward:
        flops, nbytes = 2 * flops, nbytes + weights * 4 + rows * d * itemsize
    n = m["num_hidden_layers"]
    return {"flops": n * flops, "bytes": n * nbytes}

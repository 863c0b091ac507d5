"""Architecture ``kimi_linear``: what the harness knows about Kimi-Linear
(moonshotai ``Kimi-Linear-48B-A3B-Instruct`` ``config.json``): a pre-norm
stack whose token mixers are KDA linear attention (three of four layers)
and latent attention without positions (MLA, NoPE), and whose channel
mixers are a dense SwiGLU in the leading layers and sigmoid-routed experts
with one shared expert after them, of which THIS CHIP HOLDS A SHARE.
Nothing is imported from ``deepspeed_tpu``. Three parts, as
``architectures/mistral.py``: ``WIDTHS``, the plain float32 ``reference``,
and the operations and bytes the algorithm requires.

The layers (RMSNorm eps ``rms_norm_eps``, untied head, no bias unless said)::

    x <- x + Mix_l(rmsnorm(x));   x <- x + Ch_l(rmsnorm(x))

KDA (H heads of dk = dv = ``linear_attn_config.head_dim``)::

    q = l2norm(silu(conv4(x Wq))) / sqrt(dk);  k = l2norm(silu(conv4(x Wk)))
    v = silu(conv4(x Wv));    beta_t = sigmoid(x_t Wb)           in R^H
    g_t = -exp(A_log[h]) * softplus((x_t Wf1) Wf2 + dt_bias)     in R^{H x dk}
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                               (S float32, S_0 = 0)
    y = (rmsnorm_head(o) * sigmoid((x Wg1) Wg2 + b_g)) Wo

run here TOKEN BY TOKEN under one ``lax.scan`` (the program runs the
chunked form); ``conv4`` is a causal depthwise convolution of width
``short_conv_kernel_size`` along the sequence (tap i of w multiplies
x_{t-3+i}); l2norm is ``x * rsqrt(sum x^2 + 1e-6)`` over a head's channels.
MLA (``mla_use_nope``: no rotation)::

    q = x Wq as H x (nope + rope);  [c, k_pe] = x Wkva;
    [k_nope, v] = rmsnorm(c) Wkvb as H x (nope + v);  k_h = [k_nope_h, k_pe]
    y = softmax_causal(q k^T / sqrt(nope + rope)) v Wo      plain, by q blocks

Routed layer, over the HELD share (``moe.experts`` hold the first E_h
of the router's E experts)::

    scores = sigmoid(h Wr) (float32);  chosen = top-k of scores + b
    w_i = scores_i / (sum of the chosen scores + 1e-20) * routed_scaling_factor
    y = sum_{i chosen and held} w_i E_i(h) + E_shared(h)

Every held expert is evaluated on every token and weighted by its gate
(zero where it was not chosen or is not held): plain, and exact. What the
absent experts would have added is left out, as in the program. The loss
is the engine's: the mean next-token cross-entropy, no auxiliary term.

Weights come in the program's layout (``models/kimi_linear.py``):
``layers.lead`` and ``layers.tail`` hold unrolled layers, ``layers.period``
the layers of one period each stacked over the whole periods; a layer
holds ``kda`` or ``mla`` and ``mlp`` or ``moe``.

**The mask.** A flip of the top-k set matters here only where it moves a
HELD expert in or out (two absent experts trading places change the
renormalising sum by their score gap). So ``reference`` returns ``counted``
[B, tail]: a position is left out iff, in some routed layer, a held
expert's selection score lies within ``check.routing_margin`` (as a share
of that layer's selection-score rms over all positions and experts) of the
boundary it would have to cross: the (k+1)-th score if it is chosen, the
k-th if it is not. Decided from these float32 numbers alone.

Departures from the published description: none in the mathematics. The
config gives no width for the low-rank maps Wf1, Wg1 (128, the head width)
nor the initialisations; the configuration file lists them under
``assumed``, with the rate of the router bias's load-driven update (the
trainer's; it acts after a step, so nothing compared here sees it).

Counts: one multiply-add is 2 FLOPs; training is 3 x forward; remat and
the chunked form's extra products are NOT counted. A token's routed
experts count as ``num_experts_per_token`` times the share held here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from architectures.mistral import (LOSS_BLOCK, least_seconds,  # noqa: F401
                                   logits_of, loss_of, rms_norm)

WIDTHS = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "num_hidden_layers": "num_layers",
    "max_position_embeddings": "max_seq_len",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "linear_attn_config": "linear_attn_config",
    "first_k_dense_replace": "first_k_dense_replace",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "mla_use_nope": "mla_use_nope",
    "moe_intermediate_size": "moe_intermediate_size",
    "moe_renormalize": "moe_norm_topk",
    "moe_router_activation_func": "moe_router_activation",
    "num_experts": "moe_held_experts",          # the experts HELD here
    "num_routed_experts": "num_experts",        # the router's width
    "num_experts_per_token": "moe_top_k",
    "num_shared_experts": "moe_num_shared_experts",
    "routed_scaling_factor": "routed_scaling_factor",
    "kda_gate_rank": "kda_gate_rank",
}
OPTIONAL = ()       # the file states every key
CHECK_KEYS = ("routing_margin", "excluded_share_max")

Q_BLOCK = 512       # query rows per attention block (memory bound only)


# ---- the plain float32 reference -------------------------------------------
def _silu(x):
    return x * jax.nn.sigmoid(x)


def _conv(x, w):
    """Causal depthwise convolution along the sequence: x [B, S, C],
    w [n, C]; y_t = sum_i w[i] x_{t-(n-1)+i}, zeros before the start."""
    n, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(xp[:, i:i + s] * w[i] for i in range(n))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_recurrence(q, k, v, g, beta):
    """Token by token. q, k, g [B, S, H, dk]; v [B, S, H, dv]; beta
    [B, S, H]; the state [B, H, dk, dv] float32 from zero."""
    b, _, h, dk = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None]
        u = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t * b_t[..., None], u)
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    xs = tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(
        step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.swapaxes(o, 0, 1)


def kda_mixer(p, h, *, heads, dk, eps):
    b, s, _ = h.shape
    split = lambda x: x.reshape(b, s, heads, dk)  # noqa: E731
    q = _l2norm(split(_silu(_conv(h @ p["wq"], p["conv_q"])))) * dk ** -0.5
    k = _l2norm(split(_silu(_conv(h @ p["wk"], p["conv_k"]))))
    v = split(_silu(_conv(h @ p["wv"], p["conv_v"])))
    beta = jax.nn.sigmoid(h @ p["w_b"])
    g = -jnp.exp(p["A_log"])[:, None] * split(
        jax.nn.softplus((h @ p["w_f1"]) @ p["w_f2"] + p["dt_bias"]))
    o = rms_norm(kda_recurrence(q, k, v, g, beta), p["o_norm"], eps)
    gate = jax.nn.sigmoid((h @ p["w_g1"]) @ p["w_g2"] + p["b_g"])
    return (o.reshape(b, s, heads * dk) * gate) @ p["wo"]


def causal_attention(q, k, v):
    """q, k [B, S, H, dqk]; v [B, S, H, dv]; plain softmax at scale
    dqk^-1/2, by blocks of query rows."""
    s, d = q.shape[1], q.shape[-1]
    out = []
    for q0 in range(0, s, Q_BLOCK):
        q1 = min(q0 + Q_BLOCK, s)
        ok = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, q0:q1], k[:, :q1])
        scores = jnp.where(ok[None, None], scores / jnp.sqrt(jnp.float32(d)),
                           -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(scores, axis=-1), v[:, :q1]))
    return jnp.concatenate(out, axis=1)


def mla_mixer(p, h, *, heads, nope, rope, dv, lora, eps):
    b, s, _ = h.shape
    q = (h @ p["wq"]).reshape(b, s, heads, nope + rope)
    kva = h @ p["w_kva"]
    kv = (rms_norm(kva[..., :lora], p["kv_norm"], eps) @ p["w_kvb"]).reshape(
        b, s, heads, nope + dv)
    k_pe = jnp.broadcast_to(kva[:, :, None, lora:], (b, s, heads, rope))
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    a = causal_attention(q, k, kv[..., nope:])
    return a.reshape(b, s, heads * dv) @ p["wo"]


def _swiglu(p, h):
    return (_silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


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
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    n_experts = scores.shape[-1]
    gates = jnp.einsum("nk,nke->ne", w * scaling,
                       jax.nn.one_hot(idx, n_experts, dtype=jnp.float32))
    e = p["experts"]
    n_held = e["w_up"].shape[0]
    out = jnp.zeros_like(h)
    for j in range(n_held):
        one = {name: e[name][j] for name in ("w_gate", "w_up", "w_down")}
        out = out + gates[:, first + j, None] * _swiglu(one, h)
    if "shared" in p:
        out = out + _swiglu(p["shared"], h)
    held = select[:, first:first + n_held]
    kth, nxt = ordered[:, top_k - 1, None], ordered[:, top_k, None]
    dist = jnp.where(held >= kth, held - nxt, kth - held)
    return out, jnp.min(dist, axis=-1), jnp.sqrt(jnp.mean(select * select))


@functools.partial(jax.jit, static_argnames=("static",))
def layer(x, p, *, static):
    """One layer on x [B, S, D] float32 -> (x, relative routing distance
    [B, S]; +inf for a layer without a router). ``p``: the layer's weights
    in the program's layout, upcast here; ``static``: the numbers of ``m``
    a layer needs, as a sorted tuple of pairs."""
    m = dict(static)
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
    eps = m["rms_norm_eps"]
    b, s, d = x.shape
    h = rms_norm(x, p["ln1_scale"], eps)
    if "kda" in p:
        x = x + kda_mixer(p["kda"], h, heads=m["kda_heads"],
                          dk=m["kda_head_dim"], eps=eps)
    else:
        x = x + mla_mixer(p["mla"], h, heads=m["num_attention_heads"],
                          nope=m["qk_nope_head_dim"],
                          rope=m["qk_rope_head_dim"], dv=m["v_head_dim"],
                          lora=m["kv_lora_rank"], eps=eps)
    h = rms_norm(x, p["ln2_scale"], eps)
    if "mlp" in p:
        return x + _swiglu(p["mlp"], h), jnp.full((b, s), jnp.inf)
    out, dist, rms = routed(
        p["moe"], h.reshape(b * s, d), top_k=m["num_experts_per_token"],
        first=0, renormalise=m["moe_renormalize"],
        scaling=m["routed_scaling_factor"])
    return x + out.reshape(b, s, d), (dist / rms).reshape(b, s)


def layers_in_order(layers: dict):
    """The layers' weights (never upcast here) in the order they run."""
    by_number = lambda d: [d[k] for k in sorted(d, key=int)]  # noqa: E731
    yield from by_number(layers.get("lead", {}))
    slots = by_number(layers.get("period", {}))
    if slots:
        repeats = jax.tree_util.tree_leaves(slots[0])[0].shape[0]
        for r in range(repeats):
            for slot in slots:
                yield jax.tree_util.tree_map(lambda w: w[r], slot)
    yield from by_number(layers.get("tail", {}))


def _static(m: dict) -> tuple:
    la = m["linear_attn_config"]
    keep = ("rms_norm_eps", "num_attention_heads", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
            "num_experts_per_token", "moe_renormalize",
            "routed_scaling_factor")
    out = {k: m[k] for k in keep}
    out.update(kda_heads=la["num_heads"], kda_head_dim=la["head_dim"])
    return tuple(sorted(out.items()))


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
    la = m["linear_attn_config"]
    return [("kda" if n in la["kda_layers"] else "mla",
             "dense" if n <= m["first_k_dense_replace"] else "moe")
            for n in range(1, m["num_hidden_layers"] + 1)]


def held_share(m: dict) -> float:
    """Routed experts a token computes with HERE: its
    ``num_experts_per_token`` times the share of the experts held."""
    return (m["num_experts_per_token"] * m["num_experts"]
            / m["num_routed_experts"])


def forward_flops_per_token(m: dict, seq: int) -> dict:
    """Forward FLOPs per token at sequence length ``seq``, by part, summed
    over the layers of each kind."""
    d = m["hidden_size"]
    la = m["linear_attn_config"]
    h, dk, r = la["num_heads"], la["head_dim"], m["kda_gate_rank"]
    inner = h * dk
    kda_proj = 2 * (3 * d * inner + d * r + r * inner + d * h
                    + d * r + r * inner + inner * d) \
        + 2 * 3 * la["short_conv_kernel_size"] * inner
    # the recurrence: k^T S, the rank-one write and S^T q, 2 dk dv each
    kda_state = 6 * h * dk * dk
    nh = m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    mla_proj = 2 * (d * nh * qk
                    + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
                    + m["kv_lora_rank"] * nh * (m["qk_nope_head_dim"]
                                                + m["v_head_dim"])
                    + nh * m["v_head_dim"] * d)
    mla_attn = 2 * (qk + m["v_head_dim"]) * nh * (seq + 1) / 2
    dense = 2 * 3 * d * m["intermediate_size"]
    expert = 2 * 3 * d * m["moe_intermediate_size"]
    routed_layer = (2 * d * m["num_routed_experts"]
                    + expert * m["num_shared_experts"]
                    + expert * held_share(m))
    kinds = layer_kinds(m)
    n_kda = sum(mix == "kda" for mix, _ in kinds)
    n_mla = len(kinds) - n_kda
    n_moe = sum(ch == "moe" for _, ch in kinds)
    parts = {"kda_projections": n_kda * kda_proj,
             "kda_state": n_kda * kda_state,
             "mla_projections": n_mla * mla_proj,
             "mla_attention": n_mla * mla_attn,
             "dense_ffn": (len(kinds) - n_moe) * dense,
             "routed_layers": n_moe * routed_layer,
             "head": 2 * d * m["vocab_size"]}
    parts["total"] = sum(parts.values())
    return parts


def train_flops_per_token(m: dict, seq: int) -> float:
    """Required forward + backward FLOPs per trained token."""
    return 3.0 * forward_flops_per_token(m, seq)["total"]


def _n(m: dict, position: int, kind: str) -> int:
    return sum(k[position] == kind for k in layer_kinds(m))


def kda_call_cost(m: dict, batch: int, seq: int, *, backward: bool) -> dict:
    """FLOPs and HBM bytes of the step's KDA calls (ALL the KDA layers
    held here; ``per: step``) over ``batch`` sequences. Forward: the
    recurrence's three products a head and token; q, k, v (bf16), g
    (float32) and beta read once, o written once. Backward: twice the
    products; those five and do read, their five gradients written. The
    chunked form's score matrices and its state history are its own
    choice and are not counted."""
    la = m["linear_attn_config"]
    h, dk = la["num_heads"], la["head_dim"]
    tokens = batch * seq * h
    wide = tokens * dk
    flops = 6 * dk * dk * tokens
    reads = 3 * wide * 2 + wide * 4 + tokens * 4      # q k v, g, beta
    if backward:
        flops, nbytes = 2 * flops, 2 * reads + wide * 2
    else:
        nbytes = reads + wide * 2
    n = _n(m, 0, "kda")
    return {"flops": n * flops, "bytes": n * nbytes}


def mla_flash_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                        itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the step's flash-attention calls (the MLA
    layers held here; ``per: step``), full causal, at the PUBLISHED key
    width (nope + rope) and value width, whatever the kernel pads to.
    Forward: S = QK^T at the key width and O = PV at the value width.
    Backward (one pass): S again, dQ and dK at the key width; dV and dP
    at the value width. Each operand read once, each result written once,
    the float32 log-sum-exp row a head."""
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
    n = _n(m, 0, "mla")
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
    weights = m["num_experts"] * 3 * d * f
    flops = rows * 2 * 3 * d * f
    nbytes = weights * itemsize + 2 * rows * d * itemsize
    if backward:
        flops, nbytes = 2 * flops, nbytes + weights * 4 + rows * d * itemsize
    n = _n(m, 1, "moe")
    return {"flops": n * flops, "bytes": n * nbytes}

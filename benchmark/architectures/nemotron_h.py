"""Architecture ``nemotron_h``: what the harness knows about the Nemotron-H
block as nvidia ``NVIDIA-Nemotron-3-Super-120B-A12B-BF16`` publishes it
(``config.json``, ``model_type`` ``nemotron_h``): a pre-norm stack whose
every layer is ONE sublayer by ``hybrid_override_pattern``: ``M`` a Mamba-2
mixer, ``E`` a LatentMoE feed-forward part, ``*`` grouped-query attention
without positions; an untied head. THIS CHIP HOLDS A SHARE of each mixer's
heads and of each routed layer's experts. Nothing is imported from
``deepspeed_tpu``. Three parts, as ``architectures/mistral.py``: ``WIDTHS``,
the plain float32 ``reference``, and the operations and bytes the algorithm
requires.

A layer (``h = rmsnorm(x, w_l)``, eps ``layer_norm_epsilon``; every layer
is ``x <- x + Mix_l(h)``; ``logits = rmsnorm(x_L, w_f) W_head``)::

    M  [z | xBC | dt] = h W_in            widths H P | H P + 2 G N | H
       [x | B | C] = silu(conv4(xBC) + b)        causal, depthwise
       dt = softplus(dt + dt_bias);  A = -exp(A_log)             a head
       S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D_h x_t
           head h reading the B, C of group h // (H / G)   (S float32, S_0 = 0)
       out = (grouprms(y * silu(z)) * w) W_out    the mean of squares over
           each GROUP's H P / G channels (nemotron_h's MambaRMSNormGated)
    *  q = h Wq (heads of head_dim), k, v = h Wk, h Wv; causal softmax at
       head_dim^-1/2; NO rotation (rope_theta is not read); Wo
    E  s = sigmoid(h Wr) float32; the top k of s + b over all the router's
       experts (n_group = topk_group = 1); w_j = scaling s_j / (sum_chosen s
       + 1e-20);  u = h W_dn (moe_latent_size wide);
       r = sum_j w_j relu(u W1_j)^2 W2_j over the chosen experts HELD here;
       out = r W_up + relu(h V1)^2 V2     (the shared expert reads h)

The Mamba layer runs TOKEN BY TOKEN under one ``lax.scan``
(``architectures/granite_hybrid.py`` ``ssm_recurrence``; the program runs
the chunked form); every held expert is evaluated on every token and
weighted by its gate (zero where it was not chosen), by blocks of rows so
that 8192 tokens fit beside the engine's state. What the absent heads and
experts would have added is left out, as in the program: the partial sums
go on to the next layer. The mask is ``architectures/kimi_linear.py``'s: a
position is left out iff, in some routed layer, a HELD expert's selection
score lies within ``check.routing_margin`` (as a share of that layer's
selection-score rms) of the boundary it would have to cross. The loss is
the engine's: the mean next-token cross-entropy over the vocabulary slice,
no auxiliary term.

Weights come in the program's layout (``models/nemotron_h.py``): a layer
holds ``ln1_scale`` and ``mamba``, ``attn`` or ``moe``; ``layers_in_order``
walks ``lead``, ``period``, ``tail``.

Departures from the published description: the multi-token prediction
module is left out (``num_nextn_predict_layers`` 0: the config does not say
how its inputs are joined). The norm a group and the absence of a rotation
are ``nemotron_h``'s modeling code as remembered, not keys of the config:
the configuration file lists them under ``assumed``.

Counts: one multiply-add is 2 FLOPs; training is 3 x forward; remat and
the chunked form's extra products are NOT counted. A token's routed
experts count as ``num_experts_per_tok`` times the share held here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from architectures import granite_hybrid
from architectures.granite_hybrid import (_conv, _silu, attention_mixer,
                                          ssm_recurrence)
from architectures.kimi_linear import layers_in_order
from architectures.lfm2_moe import ROW_BLOCK
from architectures.mistral import (least_seconds, logits_of,  # noqa: F401
                                   loss_of, rms_norm)

_SAME = ("hidden_size", "intermediate_size", "head_dim", "vocab_size",
         "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
         "ssm_state_size", "n_groups", "conv_kernel", "chunk_size", "expand",
         "use_conv_bias", "mamba_proj_bias", "use_bias", "rope_theta",
         "moe_intermediate_size", "moe_latent_size",
         "moe_shared_expert_intermediate_size", "routed_scaling_factor",
         "n_group", "topk_group")
WIDTHS = {
    **{key: key for key in _SAME},
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "num_hidden_layers": "num_layers",
    "max_position_embeddings": "max_seq_len",
    "layer_norm_epsilon": "norm_eps",
    "norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "use_bias",
    "mlp_bias": "effective_mlp_bias",
    "mlp_hidden_act": "activation",
    "n_routed_experts": "moe_held_experts",     # the experts HELD here
    "num_experts": "moe_held_experts",          # ... under the name
    #                                             reducers/moe.py reads
    "num_routed_experts": "num_experts",        # the router's width
    "n_shared_experts": "moe_num_shared_experts",
    "num_experts_per_tok": "moe_top_k",
    "norm_topk_prob": "moe_norm_topk",
}
OPTIONAL = ("num_experts",)     # the file states every published key
CHECK_KEYS = ("routing_margin", "excluded_share_max")

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


# ---- the plain float32 reference -------------------------------------------
def _relu2(p, h):
    """A non-gated FFN: relu(h W1)^2 W2."""
    return jnp.square(jax.nn.relu(h @ p["w_up"])) @ p["w_down"]


def mamba_mixer(p, h, *, heads, head_dim, groups, state, eps):
    """Mamba-2 with heads in ``groups``: a group's heads read its B and C,
    and the gated norm takes its mean of squares over the group's
    channels."""
    b, s, _ = h.shape
    inner, gn = heads * head_dim, groups * state
    proj = h @ p["w_in"]
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * gn],
                  proj[..., 2 * inner + 2 * gn:])
    xbc = _silu(_conv(xbc, p["conv_w"], p.get("conv_b", 0.0)))
    x = xbc[..., :inner].reshape(b, s, heads, head_dim)
    B = xbc[..., inner:inner + gn].reshape(b, s, groups, state)
    C = xbc[..., inner + gn:].reshape(b, s, groups, state)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssm_recurrence(x, dt, -jnp.exp(p["A_log"]), B, C)
    y = (y + x * p["D"][:, None]).reshape(b, s, inner) * _silu(z)
    y = rms_norm(y.reshape(b, s, groups, inner // groups),
                 p["norm"].reshape(groups, inner // groups), eps)
    return y.reshape(b, s, inner) @ p["w_out"]


def routed(p, h, *, top_k, first, renormalise, scaling):
    """The held share of a LatentMoE layer on h [N, D] -> (out, the least
    distance [N] of a held expert's selection score from the boundary it
    would have to cross, the rms of the selection scores)."""
    scores = jax.nn.sigmoid(h @ p["router"])
    select = scores + p["router_bias"]
    ordered, idx = jax.lax.top_k(select, top_k + 1)
    idx = idx[:, :top_k]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalise:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    e = p["experts"]
    n_held = e["w_up"].shape[0]
    # a held expert's gate: its weight where it was chosen, else 0
    gates = jnp.sum(
        (w * scaling)[..., None]
        * (idx[..., None] == first + jnp.arange(n_held)), axis=1)

    def block(hb, gb):
        u = hb @ p["latent"]["w_dn"]
        r = jnp.zeros_like(u)
        for j in range(n_held):
            r = r + gb[:, j, None] * _relu2(
                {name: e[name][j] for name in ("w_up", "w_down")}, u)
        return r @ p["latent"]["w_up"] + _relu2(p["shared"], hb)

    out = jnp.concatenate([block(h[r:r + ROW_BLOCK], gates[r:r + ROW_BLOCK])
                           for r in range(0, h.shape[0], ROW_BLOCK)])
    held = select[:, first:first + n_held]
    kth, nxt = ordered[:, top_k - 1, None], ordered[:, top_k, None]
    dist = jnp.where(held >= kth, held - nxt, kth - held)
    return out, jnp.min(dist, axis=-1), jnp.sqrt(jnp.mean(select * select))


@functools.partial(jax.jit, static_argnames=("static",))
def layer(x, p, *, static):
    """One layer on x [B, S, D] float32 -> (x, relative routing distance
    [B, S]; +inf for a layer without a router). ``p``: the layer's weights
    in the program's layout, upcast here; ``static``: the numbers of ``m``
    a layer needs, hashable."""
    m = dict(static)
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
    eps = m["layer_norm_epsilon"]
    b, s, d = x.shape
    h = rms_norm(x, p["ln1_scale"], eps)
    far = jnp.full((b, s), jnp.inf)
    if "mamba" in p:
        return x + mamba_mixer(
            p["mamba"], h, heads=m["mamba_num_heads"],
            head_dim=m["mamba_head_dim"], groups=m["n_groups"],
            state=m["ssm_state_size"], eps=eps), far
    if "attn" in p:
        return x + attention_mixer(
            p["attn"], h, heads=m["num_attention_heads"],
            kv_heads=m["num_key_value_heads"],
            scale=m["head_dim"] ** -0.5), far
    out, dist, rms = routed(
        p["moe"], h.reshape(b * s, d), top_k=m["num_experts_per_tok"],
        first=0, renormalise=m["norm_topk_prob"],
        scaling=m["routed_scaling_factor"])
    return x + out.reshape(b, s, d), (dist / rms).reshape(b, s)


_LAYER_KEYS = ("layer_norm_epsilon", "mamba_num_heads", "mamba_head_dim",
               "n_groups", "ssm_state_size", "num_attention_heads",
               "num_key_value_heads", "head_dim", "num_experts_per_tok",
               "norm_topk_prob", "routed_scaling_factor")


def _forward(params, tokens, m: dict):
    """(final-normed hidden [B, S, D] float32, the least relative routing
    distance over the routed layers [B, S])."""
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    x = x.astype(jnp.float32)
    static = tuple((k, m[k]) for k in _LAYER_KEYS)
    least = jnp.full(x.shape[:2], jnp.inf)
    for p in layers_in_order(params["layers"]):
        x, dist = layer(x, p, static=static)
        least = jnp.minimum(least, dist)
    hidden = rms_norm(x, params["final_norm"]["scale"].astype(jnp.float32),
                      float(m["layer_norm_epsilon"]))
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
def _n(m: dict, kind: str) -> int:
    """Layers of ``kind`` (``mamba`` | ``moe`` | ``attn``) held here."""
    return sum(KINDS[ch] == kind for ch in m["hybrid_override_pattern"])


def held_share(m: dict) -> float:
    """Routed experts a token computes with HERE: its
    ``num_experts_per_tok`` times the share of the experts held."""
    return (m["num_experts_per_tok"] * m["n_routed_experts"]
            / m["num_routed_experts"])


def forward_flops_per_token(m: dict, seq: int) -> dict:
    """Forward FLOPs per token at sequence length ``seq``, by part, summed
    over the layers of each kind, at the heads and experts held here."""
    d = m["hidden_size"]
    h, p, n = m["mamba_num_heads"], m["mamba_head_dim"], m["ssm_state_size"]
    inner = h * p
    conv = inner + 2 * m["n_groups"] * n
    mamba_proj = (2 * (d * (inner + conv + h) + inner * d)
                  + 2 * m["conv_kernel"] * conv)
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    attn_proj = 2 * (2 * d * nh * hd + 2 * d * nkv * hd)
    lat, f = m["moe_latent_size"], m["moe_intermediate_size"]
    n_m, n_e, n_a = _n(m, "mamba"), _n(m, "moe"), _n(m, "attn")
    parts = {"mamba_projections": n_m * mamba_proj,
             # the recurrence: the rank-one write dt x B^T and the read S C
             "ssd_state": n_m * 4 * h * p * n,
             "attention_projections": n_a * attn_proj,
             "attention": n_a * 4 * hd * nh * (seq + 1) / 2,
             "router_and_latent": n_e * 2 * (d * m["num_routed_experts"]
                                             + 2 * d * lat),
             "shared_experts": n_e * m["n_shared_experts"] * 2 * 2 * d
             * m["moe_shared_expert_intermediate_size"],
             "held_experts": n_e * 2 * 2 * lat * f * held_share(m),
             "head": 2 * d * m["vocab_size"]}
    parts["total"] = sum(parts.values())
    return parts


def train_flops_per_token(m: dict, seq: int) -> float:
    """Required forward + backward FLOPs per trained token."""
    return 3.0 * forward_flops_per_token(m, seq)["total"]


def ssd_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                  itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the step's state-space scans (ALL the Mamba
    layers held here; ``per: step``): ``architectures/granite_hybrid.py``'s
    count (the recurrence's two products a head and token; x, B, C, dt in
    and y out, and the gradients; the chunked form's own products not
    counted) at this family's keys, B and C a GROUP."""
    return granite_hybrid.ssd_call_cost(
        {"mamba_n_heads": m["mamba_num_heads"],
         "mamba_d_head": m["mamba_head_dim"],
         "mamba_d_state": m["ssm_state_size"],
         "mamba_n_groups": m["n_groups"],
         "layer_types": ["mamba"] * _n(m, "mamba")},
        batch, seq, backward=backward, itemsize=itemsize)


def flash_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                    itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the step's flash-attention calls (the
    attention layers held here; ``per: step``), full causal, at the
    published ``head_dim`` with the key-value heads held shared by the
    query heads held. Forward: S = QK^T and O = PV. Backward (one pass): S
    again, dV, dP, dQ, dK (5 matmuls). Each operand read once, each result
    written once (q, o, do, dq at the query heads; k, v, dk, dv at the kv
    heads; the float32 log-sum-exp row a head)."""
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    pairs = batch * nh * seq * (seq + 1) // 2
    q_like = batch * seq * nh * hd * itemsize
    kv_like = batch * seq * nkv * hd * itemsize
    lse = batch * seq * nh * 4
    if backward:
        flops = 5 * 2 * hd * pairs
        nbytes = 4 * q_like + 4 * kv_like + lse
    else:
        flops = 2 * 2 * hd * pairs
        nbytes = 2 * q_like + 2 * kv_like + lse
    layers = _n(m, "attn")
    return {"flops": layers * flops, "bytes": layers * nbytes}


def moe_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                  itemsize: int = 2, rows: float | None = None) -> dict:
    """FLOPs and HBM bytes of the step's held-expert calls (the routed
    layers held here; ``per: step``) at ``rows`` rows (token, choice) a
    routed layer, as the program counted them; None: a balanced router's,
    a token's ``held_share``. SIX matmul units a row at latent x width: two
    forward and four backward (the backward's second run of ``u W1`` is
    its own choice and is not counted). Bytes: every held expert's weights
    read once (and their float32 gradients written once, backward), a
    row's latent input gathered and its output scattered."""
    lat, f = m["moe_latent_size"], m["moe_intermediate_size"]
    if rows is None:
        rows = batch * seq * held_share(m)
    weights = m["n_routed_experts"] * 2 * lat * f
    flops = rows * 2 * 2 * lat * f
    nbytes = weights * itemsize + 2 * rows * lat * itemsize
    if backward:
        flops, nbytes = 2 * flops, nbytes + weights * 4 + rows * lat * itemsize
    layers = _n(m, "moe")
    return {"flops": layers * flops, "bytes": layers * nbytes}

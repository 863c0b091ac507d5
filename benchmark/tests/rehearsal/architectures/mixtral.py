"""Architecture ``mixtral`` (rehearsal: added by ``tests/test_rehearsal.py``
to a temporary copy of ``benchmark/``, with new files only): the Mistral
decoder whose FFN is a routed mixture of experts, as ``MixtralForCausalLM``
describes it. Attention, norms, RoPE and the loss head are
``architectures/mistral.py``'s; nothing is imported from ``deepspeed_tpu``.

    p = softmax(h Wr)                       h = RMSNorm(x), float32
    top-k experts of p, their probabilities renormalised to sum 1
    x = x + sum_e gate_e * (silu(h Wgate_e) * (h Wup_e)) Wdown_e

Every token reaches its k experts (dropless). The loss is the engine's:
the mean next-token cross-entropy plus ``router_aux_loss_coef`` times the
load-balance term summed over the layers, in each layer
E * sum_e mean_tokens(p_e) * mean_tokens(expert e is the token's first).
Weights in the program's layout: ``layers.router`` [L, D, E] and
``layers.experts.{w_gate, w_up, w_down}`` [L, E, ...] in place of the dense
FFN's. Each expert is evaluated on every token and weighted by its gate
(zero where it was not chosen): plain, and exact.

A top-k router is a discontinuous function: where a position's k-th and
(k+1)-th router logits lie close, a CORRECT program in bfloat16 picks
another expert set than this float32 reference, and its hidden state is
then far off (``benchmark/README.md``, "Routed architectures"). So
``reference`` returns a third value, ``counted`` [B, tail]: a position
counts if and only if in EVERY layer that gap, as a share of the rms of
the layer's router logits (over all positions and experts), is at least
``check.routing_margin``. The mask is a function of these float32 logits
and that one number, never of the program's output. ``routes`` gives the
gaps and the expert sets themselves, for the script that reads on the
chip where a stand-in's routing flips (``tests/routing_flips.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from architectures import mistral
from architectures.mistral import (flash_call_cost, least_seconds,  # noqa: F401
                                   visible_keys_total)

WIDTHS = {**mistral.WIDTHS,
          "num_local_experts": "num_experts",
          "num_experts_per_tok": "moe_top_k",
          "norm_topk_prob": "moe_norm_topk",
          "router_aux_loss_coef": "router_aux_loss_coef"}
OPTIONAL = mistral.OPTIONAL
# keys the configuration's ``check`` must have (``lib/files.py`` demands
# them before any device work): ``reference`` returns a mask
CHECK_KEYS = ("routing_margin", "excluded_share_max")


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "window", "theta", "eps", "top_k",
    "renormalise"))
def layer(x, stacked, *, index, heads, kv_heads, head_dim, window, theta,
          eps, top_k, renormalise):
    """One decoder layer on x [B, S, D] float32 -> (x, load-balance term,
    relative gap [B, S] between the k-th and (k+1)-th router logit, the
    chosen experts [B, S, k] in ascending order, the rms of the router
    logits that the gap is a share of)."""
    p = jax.tree_util.tree_map(lambda w: w[index].astype(jnp.float32),
                               stacked)
    b, s, d = x.shape
    h = mistral.rms_norm(x, p["ln1_scale"], eps)
    q = (h @ p["wq"]).reshape(b, s, heads, head_dim)
    k = (h @ p["wk"]).reshape(b, s, kv_heads, head_dim)
    v = (h @ p["wv"]).reshape(b, s, kv_heads, head_dim)
    pos = jnp.arange(s)
    q, k = mistral.rope(q, pos, theta), mistral.rope(k, pos, theta)
    a = mistral.attention(q, k, v, window).reshape(b, s, heads * head_dim)
    x = x + a @ p["wo"]
    h = mistral.rms_norm(x, p["ln2_scale"], eps).reshape(b * s, d)
    logits = h @ p["router"]                                    # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    n_experts = probs.shape[-1]
    top_p, top_i = jax.lax.top_k(probs, top_k)
    ordered = jax.lax.top_k(logits, top_k + 1)[0]
    scale = jnp.sqrt(jnp.mean(jnp.square(logits)))
    gap = (ordered[:, top_k - 1] - ordered[:, top_k]) / scale
    if renormalise:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(top_i, n_experts, dtype=jnp.float32)  # [N, k, E]
    gates = jnp.einsum("nk,nke->ne", top_p, chosen)
    e = p["experts"]
    out = jnp.zeros_like(h)
    for j in range(n_experts):
        y = (jax.nn.silu(h @ e["w_gate"][j]) * (h @ e["w_up"][j])) \
            @ e["w_down"][j]
        out = out + gates[:, j:j + 1] * y
    aux = n_experts * jnp.sum(jnp.mean(probs, axis=0)
                              * jnp.mean(chosen[:, 0], axis=0))
    return (x + out.reshape(b, s, d), aux, gap.reshape(b, s),
            jnp.sort(top_i, axis=-1).reshape(b, s, top_k), scale)


def _forward(params, tokens, m: dict):
    """(final-normed hidden [B, S, D], summed load-balance term, and of
    each layer: relative gaps [B, S], expert sets [B, S, k], logit rms)."""
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    x = x.astype(jnp.float32)
    aux, gaps, chosen, scales = 0.0, [], [], []
    for i in range(params["layers"]["wq"].shape[0]):
        x, layer_aux, gap, experts, scale = layer(
            x, params["layers"], index=i, heads=m["num_attention_heads"],
            kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
            window=m.get("sliding_window"), theta=float(m["rope_theta"]),
            eps=float(m["rms_norm_eps"]), top_k=m["num_experts_per_tok"],
            renormalise=bool(m["norm_topk_prob"]))
        aux = aux + layer_aux
        gaps.append(gap)
        chosen.append(experts)
        scales.append(scale)
    hidden = mistral.rms_norm(
        x, params["final_norm"]["scale"].astype(jnp.float32),
        float(m["rms_norm_eps"]))
    return hidden, aux, gaps, chosen, scales


def routes(params, tokens, m: dict, tail: int):
    """(least relative gap over the layers [B, tail], expert sets
    [L, B, tail, k], logit rms [L]) of the last ``tail`` positions."""
    _, _, gaps, chosen, scales = _forward(params, tokens, m)
    return (jnp.min(jnp.stack(gaps), axis=0)[:, -tail:],
            jnp.stack(chosen)[:, :, -tail:], jnp.stack(scales))


def reference(params, tokens, targets, m: dict, tail: int):
    """(loss as the engine defines it, a float; logits of the last ``tail``
    positions; which of them count, boolean [B, tail]) from ``params`` in
    the program's layout. ``m`` carries ``routing_margin`` (``CHECK_KEYS``)."""
    hidden, aux, gaps, _, _ = _forward(params, tokens, m)
    loss = float(mistral.loss_of(hidden, params["lm_head"], targets)
                 + m["router_aux_loss_coef"] * aux)
    counted = jnp.min(jnp.stack(gaps), axis=0) >= m["routing_margin"]
    return (loss, mistral.logits_of(hidden[:, -tail:], params["lm_head"]),
            counted[:, -tail:])


# ---- required operations ---------------------------------------------------
def forward_flops_per_token(m: dict, seq: int) -> dict:
    """Forward FLOPs per token, by part: a token multiplies the attention
    projections, the router and the SwiGLU of its k experts."""
    d, hd, f = m["hidden_size"], m["head_dim"], m["intermediate_size"]
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    attn_proj = 2 * (d * nh * hd + 2 * d * nkv * hd + nh * hd * d)
    router = 2 * d * m["num_local_experts"]
    experts = 2 * m["num_experts_per_tok"] * 3 * d * f
    attn = (4 * hd * nh * visible_keys_total(seq, m.get("sliding_window"))
            / seq)
    head = 2 * d * m["vocab_size"]
    return {"attention_projections": attn_proj, "router": router,
            "experts": experts, "attention_layer": attn, "head": head,
            "total": m["num_hidden_layers"] * (attn_proj + router + experts
                                               + attn) + head}


def train_flops_per_token(m: dict, seq: int) -> float:
    """Required forward + backward FLOPs per trained token."""
    return 3.0 * forward_flops_per_token(m, seq)["total"]

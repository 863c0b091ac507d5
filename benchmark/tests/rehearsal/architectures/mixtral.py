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


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "window", "theta", "eps", "top_k",
    "renormalise"))
def layer(x, stacked, *, index, heads, kv_heads, head_dim, window, theta,
          eps, top_k, renormalise):
    """One decoder layer on x [B, S, D] float32 -> (x, load-balance term)."""
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
    probs = jax.nn.softmax(h @ p["router"], axis=-1)            # [N, E]
    n_experts = probs.shape[-1]
    top_p, top_i = jax.lax.top_k(probs, top_k)
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
    return x + out.reshape(b, s, d), aux


def reference(params, tokens, targets, m: dict, tail: int):
    """(loss as the engine defines it, a float; logits of the last ``tail``
    positions) from ``params`` in the program's layout."""
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    x = x.astype(jnp.float32)
    aux = 0.0
    for i in range(params["layers"]["wq"].shape[0]):
        x, layer_aux = layer(
            x, params["layers"], index=i, heads=m["num_attention_heads"],
            kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
            window=m.get("sliding_window"), theta=float(m["rope_theta"]),
            eps=float(m["rms_norm_eps"]), top_k=m["num_experts_per_tok"],
            renormalise=bool(m["norm_topk_prob"]))
        aux = aux + layer_aux
    hidden = mistral.rms_norm(
        x, params["final_norm"]["scale"].astype(jnp.float32),
        float(m["rms_norm_eps"]))
    loss = float(mistral.loss_of(hidden, params["lm_head"], targets)
                 + m["router_aux_loss_coef"] * aux)
    return loss, mistral.logits_of(hidden[:, -tail:], params["lm_head"])


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

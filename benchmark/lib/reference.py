"""Plain float32 reference of the Mistral decoder: the yardstick `correct`
rests on. Independent of the code under test: nothing is imported from
``deepspeed_tpu``; no kernel, no KV cache, no serving batch, no remat.

It follows the published architecture (Mistral-7B-v0.1 ``config.json`` and
the ``MistralForCausalLM`` description): token embedding, then per layer

    h = RMSNorm(x);  q, k, v = h Wq, h Wk, h Wv  (no bias)
    q, k = RoPE(q), RoPE(k)          rotate-half convention, theta 1e4
    a = softmax(q k^T / sqrt(head_dim) + mask) v
        mask: causal AND sliding window (query i sees keys in (i-W, i]);
        grouped-query attention: each KV head serves num_heads/num_kv_heads
        query heads
    x = x + a Wo
    x = x + (silu(RMSNorm(x) Wgate) * (RMSNorm(x) Wup)) Wdown

then a final RMSNorm and an untied output head. Weights are given in the
program's own layout (so the same numbers are compared): a dict with
``embed.tokens`` [V, D], ``layers.*`` stacked on a leading layer axis with
matrices stored [in, out], ``final_norm.scale`` and ``lm_head`` [D, V].
One layer at a time is sliced from the stack and upcast to float32, so a
bf16 stack that fills the chip never needs a whole f32 copy.

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a
TPU a float32 matmul otherwise runs in bf16 passes.

Departures from the published description: none in the mathematics.
Attention is evaluated in blocks of query rows (same result, bounded
memory); the loss is the mean next-token cross-entropy over all
positions, evaluated in chunks of positions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 1024      # query rows per attention block (memory bound only)
LOSS_BLOCK = 2048   # positions per cross-entropy block


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """x [B, S, H, D], positions [S]; rotate-half: the two halves of the
    head dimension form the rotated pairs."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window):
    """q [B, S, H, D]; k, v [B, S, Hkv, D]; causal + sliding window."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    out = []
    for q0 in range(0, s, Q_BLOCK):
        q1 = min(q0 + Q_BLOCK, s)
        k0 = 0 if window is None else max(0, q0 - window + 1)
        qi = jnp.arange(q0, q1)[:, None]
        ki = jnp.arange(k0, q1)[None, :]
        ok = ki <= qi
        if window is not None:
            ok = ok & (qi - ki < window)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, q0:q1], k[:, k0:q1])
        scores = scores / jnp.sqrt(jnp.float32(d))
        scores = jnp.where(ok[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, k0:q1]))
    return jnp.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim",
                                             "window", "theta", "eps"))
def layer(x, stacked, *, index, heads, kv_heads, head_dim, window, theta,
          eps):
    """One decoder layer on x [B, S, D] float32; ``stacked`` is the
    program's stacked layer dict, sliced at ``index`` (a traced scalar, so
    one executable serves every layer) and upcast here."""
    p = {k_: w[index].astype(jnp.float32) for k_, w in stacked.items()}
    b, s, _ = x.shape
    h = rms_norm(x, p["ln1_scale"], eps)
    q = (h @ p["wq"]).reshape(b, s, heads, head_dim)
    k = (h @ p["wk"]).reshape(b, s, kv_heads, head_dim)
    v = (h @ p["wv"]).reshape(b, s, kv_heads, head_dim)
    pos = jnp.arange(s)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    a = attention(q, k, v, window).reshape(b, s, heads * head_dim)
    x = x + a @ p["wo"]
    h = rms_norm(x, p["ln2_scale"], eps)
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def final_hidden(params, tokens, m: dict):
    """Final-normed hidden states [B, S, D] float32 for tokens [B, S].
    ``m`` is the model object of a configuration file (HF key names)."""
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    x = x.astype(jnp.float32)
    n_layers = params["layers"]["wq"].shape[0]
    for i in range(n_layers):
        x = layer(x, params["layers"], index=i,
                  heads=m["num_attention_heads"],
                  kv_heads=m["num_key_value_heads"],
                  head_dim=m["head_dim"], window=m.get("sliding_window"),
                  theta=float(m["rope_theta"]), eps=float(m["rms_norm_eps"]))
    return rms_norm(x, params["final_norm"]["scale"].astype(jnp.float32),
                    float(m["rms_norm_eps"]))


@jax.jit
def logits_of(hidden, lm_head):
    return hidden @ lm_head.astype(jnp.float32)


@jax.jit
def _nll_sum(hidden, lm_head, targets):
    lg = hidden @ lm_head.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    tl = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tl)


def loss_of(hidden, lm_head, targets):
    """Mean next-token cross-entropy over every position of [B, S]."""
    s = hidden.shape[1]
    total = 0.0
    for s0 in range(0, s, LOSS_BLOCK):
        total = total + _nll_sum(hidden[:, s0:s0 + LOSS_BLOCK], lm_head,
                                 targets[:, s0:s0 + LOSS_BLOCK])
    return total / (hidden.shape[0] * s)


def errors(got, ref) -> tuple[float, float]:
    """(max, rms) error of ``got`` against ``ref``, each relative to the
    reference's own scale: max|got-ref| / max|ref| and
    rms(got-ref) / rms(ref). Non-finite output is infinitely wrong."""
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    if not bool(jnp.all(jnp.isfinite(got))):
        return float("inf"), float("inf")
    return (float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref))),
            float(jnp.sqrt(jnp.mean((got - ref) ** 2))
                  / jnp.sqrt(jnp.mean(ref ** 2))))

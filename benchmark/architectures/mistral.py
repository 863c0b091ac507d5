"""Architecture ``mistral``: what the harness knows about the Mistral-style
dense decoder (RMSNorm, RoPE, GQA, optional sliding window, SwiGLU, untied
head), in one module found by the ``architecture`` key of a configuration
file. Nothing is imported from ``deepspeed_tpu``. Three parts:

1. ``WIDTHS``: published key -> attribute of the program's ``ModelConfig``,
   which ``lib/modelspec.py`` holds the model as built to.
2. The plain float32 reference ``correct`` rests on (``reference``): no
   kernel, no KV cache, no serving batch, no remat.
3. The operations and bytes the algorithm requires, from shapes alone
   (``train_flops_per_token``, ``flash_call_cost``, ``least_seconds``).

The reference follows the published architecture (Mistral-7B-v0.1
``config.json`` and the ``MistralForCausalLM`` description): token
embedding, then per layer

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
memory); the loss is the engine's: the mean next-token cross-entropy over
all positions (a dense decoder has no auxiliary term), evaluated in chunks
of positions.

The counts are the yardstick's own: nothing reads the program's
``cost_analysis`` or its FLOP estimators. One multiply-add is 2 FLOPs.
Training requires forward plus backward = 3 x forward matmul FLOPs;
recomputation (remat, the flash backward's score recompute) is NOT counted
in ``train_flops_per_token``. Attention counts only key positions the
causal + sliding-window mask lets a query see. ``m`` is everywhere the
``WIDTHS`` keys of the model as built.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# published key -> attribute of the program's ModelConfig. A key of
# OPTIONAL may be missing from a published config.json (v0.1 states no
# head_dim, a model without a window no sliding_window); every other key
# is demanded of the configuration file.
WIDTHS = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "sliding_window": "sliding_window",
    "num_hidden_layers": "num_layers",
    "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
OPTIONAL = ("head_dim", "sliding_window")

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


def reference(params, tokens, targets, m: dict, tail: int):
    """(loss as the engine defines it, a float; logits of the last ``tail``
    positions) from ``params`` in the program's layout."""
    hidden = final_hidden(params, tokens, m)
    # a number, so the loss's slabs are gone before the tail's are asked for
    loss = float(loss_of(hidden, params["lm_head"], targets))
    return loss, logits_of(hidden[:, -tail:], params["lm_head"])


# ---- required operations and bytes -----------------------------------------
def visible_keys_total(seq: int, window: int | None) -> int:
    """Sum over query positions i in [0, seq) of the keys visible to i:
    min(i + 1, window) under a causal mask with a sliding window."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    # positions 0..window-1 see i+1 keys, the rest see `window`
    return window * (window + 1) // 2 + (seq - window) * window


def layer_matmul_params(m: dict) -> int:
    """Parameters of one decoder layer that a token multiplies (GQA
    projections + SwiGLU FFN); norms are not matmuls."""
    d, hd = m["hidden_size"], m["head_dim"]
    nh, nkv, f = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["intermediate_size"])
    attn = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
    ffn = 3 * d * f
    return attn + ffn


def forward_flops_per_token(m: dict, seq: int) -> dict:
    """Forward FLOPs per token at sequence length ``seq``, by part."""
    layers = m["num_hidden_layers"]
    per_layer = 2 * layer_matmul_params(m)
    head = 2 * m["hidden_size"] * m["vocab_size"]
    # QK^T and PV: 2 matmuls x 2 FLOPs x head_dim per (query, key) pair
    attn_layer = (4 * m["head_dim"] * m["num_attention_heads"]
                  * visible_keys_total(seq, m.get("sliding_window")) / seq)
    return {"layer_matmul": per_layer, "head": head,
            "attention_layer": attn_layer,
            "total": layers * (per_layer + attn_layer) + head}


def train_flops_per_token(m: dict, seq: int) -> float:
    """Required forward + backward FLOPs per trained token."""
    return 3.0 * forward_flops_per_token(m, seq)["total"]


def flash_call_cost(m: dict, batch: int, seq: int, *,
                    backward: bool, itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of one flash-attention call over ``batch``
    sequences of one layer.

    forward: S = QK^T and O = PV (2 matmuls). backward (one pass):
    recompute S, then dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q
    (5 matmuls) - the recompute is part of the flash algorithm, so it
    counts for the KERNEL's roofline (not for model FLOP utilization).
    Bytes: each operand read once, each result written once (q, o, do,
    dq at num_attention_heads; k, v, dk, dv at num_key_value_heads; the
    f32 log-sum-exp row per head)."""
    hd, nh, nkv = (m["head_dim"], m["num_attention_heads"],
                   m["num_key_value_heads"])
    pairs = batch * nh * visible_keys_total(seq, m.get("sliding_window"))
    matmuls = 5 if backward else 2
    flops = matmuls * 2 * hd * pairs
    q_like = batch * seq * nh * hd * itemsize
    kv_like = batch * seq * nkv * hd * itemsize
    lse = batch * seq * nh * 4
    if backward:
        nbytes = (3 * q_like + 2 * kv_like + lse    # q, o, do, k, v, lse
                  + q_like + 2 * kv_like)           # dq, dk, dv
    else:
        nbytes = q_like + 2 * kv_like + q_like + lse
    return {"flops": flops, "bytes": nbytes}


def least_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """Roofline: the least time the chip could take and which bound it."""
    t_c = cost["flops"] / peaks["bf16_flops_per_s"]
    t_m = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")

"""Architecture ``ouro``: what the harness knows about the Ouro looped
decoder (ByteDance ``Ouro-2.6B`` ``config.json``, ``model_type`` ``ouro``;
the LoopLM paper, arXiv 2510.25741): ONE stack of sandwich-norm layers run
``total_ut_steps`` times a forward pass on the same weights, the final norm
and the one head after every pass, an exit gate beside the head, and the
expected loss over the exits. Nothing is imported from ``deepspeed_tpu``.
Three parts, as ``architectures/mistral.py``: ``WIDTHS``, the plain float32
``reference``, and the operations and bytes the algorithm requires.

With ``T = total_ut_steps``, ``L = num_hidden_layers``, RMSNorm at
``rms_norm_eps``, no bias anywhere but the gate's, an untied head::

    h(0) = E[tokens]
    for t = 1..T:                        the SAME L layers' weights every t
      u = h(t-1)
      for l = 1..L:
        a = u + rmsnorm(Attn_l(rmsnorm(u, g1)), g1o)
        u = a + rmsnorm(SwiGLU_l(rmsnorm(a, g2)), g2o)
      h(t)      = rmsnorm(u, g_f)        the one final norm, after EVERY pass;
      logits(t) = h(t) W_head              the normed state enters pass t + 1
      lambda_t  = sigmoid(h(t) . w_g + b_g)
    Attn: q = x Wq, k = x Wk, v = x Wv as heads of head_dim; rotate-half
          rotary at rope_theta on q and k, the same positions every pass;
          causal softmax(q k^T / sqrt(head_dim)) v; then Wo.
    SwiGLU(x) = (silu(x Wgate) * (x Wup)) Wdown
    p_t = lambda_t prod_{j<t}(1 - lambda_j)  for t < T;  p_T = prod_{j<T}(1 - lambda_j)
    loss = mean over positions of [ sum_t p_t nll_t - beta H(p) ]
    nll_t = -log softmax(logits(t))[next token];  H(p) = -sum_t p_t log p_t

``reference`` returns that loss (as the engine defines it) and pass ``T``'s
logits of the last ``tail`` positions: what the program's ``apply``
returns. Python loops over the passes and the layers; no scan, no kernel,
no remat.

What ``config.json`` does not state, and is ASSUMED here as in the program
(the configuration file lists each under ``assumed``): the sandwich norm
and where its four norms sit; that the NORMED state enters the next pass;
no embedding multiplier; no attention bias and no q/k norm; the gate's
shape (d -> 1 with a bias, on the normed state); the stage-I objective
above with ``beta`` = ``exit_entropy_beta``; that the last pass takes what
probability is left (its own gate is not read). ``early_exit_threshold``,
``max_window_layers``, ``use_sliding_window`` are kept in the file and not
read: the window is off, and exit by the cumulative gate is inference's.

Departures from that description: none in the mathematics. Attention is
evaluated in blocks of query rows and the loss in blocks of positions (same
result, bounded memory); ``log p`` is made from ``log_sigmoid`` of the
gate's logit and of its negative, so a saturated gate loses nothing.

Weights come in the program's layout (``models/ouro.py``): ``embed.tokens``
[V, D]; ``layers.*`` stacked on a leading layer axis, matrices [in, out],
the norms ``ln1_scale`` (g1), ``ln1_out_scale`` (g1o), ``ln2_scale`` (g2),
``ln2_out_scale`` (g2o); ``final_norm.scale``; ``lm_head`` [D, V];
``exit_gate.w`` [D] and ``exit_gate.b`` []. One layer at a time is sliced
from the stack and upcast to float32.

Counts: one multiply-add is 2 FLOPs; training is 3 x forward; remat (every
layer application runs its forward twice a step) and the flash backward's
score recompute are NOT counted in ``train_flops_per_token``. A token pays
``T x L`` layer applications, ``T`` head products and ``T`` gates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# published key -> attribute of the program's ModelConfig; every key is
# demanded of the configuration file. ``exit_entropy_beta`` is the one key
# that is not the source's: the loss the reference returns needs it, so the
# file states it at top level (and under ``assumed``) and the model as
# built is held to it like a width
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
    "total_ut_steps": "total_ut_steps",
    "exit_entropy_beta": "exit_entropy_beta",
}
OPTIONAL = ()

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


def attention(q, k, v):
    """q [B, S, H, D]; k, v [B, S, Hkv, D]; full causal."""
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    out = []
    for q0 in range(0, s, Q_BLOCK):
        q1 = min(q0 + Q_BLOCK, s)
        ok = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, q0:q1], k[:, :q1])
        scores = scores / jnp.sqrt(jnp.float32(d))
        scores = jnp.where(ok[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :q1]))
    return jnp.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim",
                                             "theta", "eps"))
def layer(x, stacked, *, index, heads, kv_heads, head_dim, theta, eps):
    """One sandwich-norm layer on x [B, S, D] float32; ``stacked`` is the
    program's stacked layer dict, sliced at ``index`` (a traced scalar, so
    one executable serves every layer of every pass) and upcast here."""
    p = {k_: w[index].astype(jnp.float32) for k_, w in stacked.items()}
    b, s, _ = x.shape
    h = rms_norm(x, p["ln1_scale"], eps)
    q = (h @ p["wq"]).reshape(b, s, heads, head_dim)
    k = (h @ p["wk"]).reshape(b, s, kv_heads, head_dim)
    v = (h @ p["wv"]).reshape(b, s, kv_heads, head_dim)
    pos = jnp.arange(s)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    a = attention(q, k, v).reshape(b, s, heads * head_dim) @ p["wo"]
    x = x + rms_norm(a, p["ln1_out_scale"], eps)
    h = rms_norm(x, p["ln2_scale"], eps)
    m = (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
    return x + rms_norm(m, p["ln2_out_scale"], eps)


def exit_states(params, tokens, m: dict) -> list:
    """[h(1), ..., h(T)], each final-normed [B, S, D] float32. ``m`` is the
    model object of a configuration file (published key names)."""
    eps = float(m["rms_norm_eps"])
    final = params["final_norm"]["scale"].astype(jnp.float32)
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    x = x.astype(jnp.float32)
    exits = []
    for _ in range(m["total_ut_steps"]):
        for i in range(m["num_hidden_layers"]):
            x = layer(x, params["layers"], index=i,
                      heads=m["num_attention_heads"],
                      kv_heads=m["num_key_value_heads"],
                      head_dim=m["head_dim"], theta=float(m["rope_theta"]),
                      eps=eps)
        x = rms_norm(x, final, eps)
        exits.append(x)
    return exits


def exit_log_probs(params, exits: list):
    """log p [T, B, S] of the exit distribution from the gates of the
    first T - 1 exits; the last exit takes what is left."""
    w = params["exit_gate"]["w"].astype(jnp.float32)
    b = params["exit_gate"]["b"].astype(jnp.float32)
    log_p, stayed = [], jnp.zeros(exits[0].shape[:2], jnp.float32)
    for h in exits[:-1]:
        z = h @ w + b
        log_p.append(jax.nn.log_sigmoid(z) + stayed)
        stayed = stayed + jax.nn.log_sigmoid(-z)
    return jnp.stack(log_p + [stayed])


@jax.jit
def logits_of(hidden, lm_head):
    return hidden @ lm_head.astype(jnp.float32)


@jax.jit
def _nll(hidden, lm_head, targets):
    lg = hidden @ lm_head.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    return lse - jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]


def nll_of(hidden, lm_head, targets):
    """Next-token cross-entropy [B, S] of one exit, a block of positions
    at a time."""
    s = hidden.shape[1]
    return jnp.concatenate(
        [_nll(hidden[:, s0:s0 + LOSS_BLOCK], lm_head,
              targets[:, s0:s0 + LOSS_BLOCK])
         for s0 in range(0, s, LOSS_BLOCK)], axis=1)


def expected_loss(params, tokens, targets, m: dict, exits=None):
    """The loss as the engine defines it: the mean over positions of the
    exits' losses under the exit distribution, less beta x its entropy."""
    if exits is None:
        exits = exit_states(params, tokens, m)
    log_p = exit_log_probs(params, exits)
    p = jnp.exp(log_p)
    nll = jnp.stack([nll_of(h, params["lm_head"], targets) for h in exits])
    entropy = -jnp.sum(p * log_p, axis=0)
    return jnp.mean(jnp.sum(p * nll, axis=0)
                    - m["exit_entropy_beta"] * entropy)


def reference(params, tokens, targets, m: dict, tail: int):
    """(loss as the engine defines it, a float; pass T's logits of the last
    ``tail`` positions) from ``params`` in the program's layout."""
    exits = exit_states(params, tokens, m)
    # a number, so the loss's slabs are gone before the tail's are asked for
    loss = float(expected_loss(params, tokens, targets, m, exits))
    return loss, logits_of(exits[-1][:, -tail:], params["lm_head"])


# ---- required operations and bytes -----------------------------------------
def visible_keys_total(seq: int) -> int:
    """Sum over query positions of the keys a causal mask lets them see."""
    return seq * (seq + 1) // 2


def layer_matmul_params(m: dict) -> int:
    """Parameters of one layer that a token multiplies (the four attention
    projections and the SwiGLU); norms are not matmuls."""
    d, hd = m["hidden_size"], m["head_dim"]
    nh, nkv, f = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["intermediate_size"])
    return 2 * d * nh * hd + 2 * d * nkv * hd + 3 * d * f


def forward_flops_per_token(m: dict, seq: int) -> dict:
    """Forward FLOPs per token at sequence length ``seq``, by part, summed
    over the ``T`` passes."""
    t, n = m["total_ut_steps"], m["num_hidden_layers"]
    d = m["hidden_size"]
    parts = {
        "layer_matmuls": t * n * 2 * layer_matmul_params(m),
        # QK^T and PV: 2 matmuls x 2 FLOPs x head_dim a visible pair, head
        "attention": t * n * 4 * m["head_dim"] * m["num_attention_heads"]
        * visible_keys_total(seq) / seq,
        "heads": t * 2 * d * m["vocab_size"],
        "gates": t * 2 * d}
    parts["total"] = sum(parts.values())
    return parts


def train_flops_per_token(m: dict, seq: int) -> float:
    """Required forward + backward FLOPs per trained token."""
    return 3.0 * forward_flops_per_token(m, seq)["total"]


def mha_flash_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                        itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the step's flash-attention calls of one
    direction (``per: step``): ``T x L`` calls over ``batch`` sequences,
    full causal. Forward: S = QK^T and O = PV. Backward (one pass): S
    again, dV, dP, dQ, dK (5 matmuls; the recompute is the algorithm's, so
    it counts for the KERNEL's roofline). Remat's second run of the
    forward kernel is the program's choice and is not counted. Each
    operand read once, each result written once (q, o, do, dq at the query
    heads; k, v, dk, dv at the key heads; the float32 log-sum-exp row a
    head)."""
    hd, nh, nkv = (m["head_dim"], m["num_attention_heads"],
                   m["num_key_value_heads"])
    pairs = batch * nh * visible_keys_total(seq)
    q_like = batch * seq * nh * hd * itemsize
    kv_like = batch * seq * nkv * hd * itemsize
    lse = batch * seq * nh * 4
    if backward:
        flops, nbytes = 5 * 2 * hd * pairs, 4 * q_like + 4 * kv_like + lse
    else:
        flops, nbytes = 2 * 2 * hd * pairs, 2 * q_like + 2 * kv_like + lse
    calls = m["total_ut_steps"] * m["num_hidden_layers"]
    return {"flops": calls * flops, "bytes": calls * nbytes}


def least_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """Roofline: the least time the chip could take and which bound it."""
    t_c = cost["flops"] / peaks["bf16_flops_per_s"]
    t_m = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")

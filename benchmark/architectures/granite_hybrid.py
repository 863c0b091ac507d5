"""Architecture ``granite_hybrid``: what the harness knows about the Granite
4.0-H family (ibm-granite ``granite-4.0-h-micro`` ``config.json``,
``model_type`` ``granitemoehybrid`` with ``num_local_experts`` 0, so dense):
a pre-norm stack of Mamba-2 state-space layers with a grouped-query
attention layer among every few (``layer_types``), a SwiGLU in every
layer, four muP multipliers and a tied head. Nothing is imported from
``deepspeed_tpu``. Three parts, as ``architectures/mistral.py``: ``WIDTHS``,
the plain float32 ``reference``, and the operations and bytes the
algorithm requires.

The layers (RMSNorm eps ``rms_norm_eps``; no bias but the convolution's)::

    x0 = embed[tokens] * embedding_multiplier
    x <- x + residual_multiplier * Mix_l(rmsnorm(x))
    x <- x + residual_multiplier * (silu(h Wg) * (h Wu)) Wd,   h = rmsnorm(x)
    logits = rmsnorm(x_L) embed^T / logits_scaling

``mamba`` (H = ``mamba_n_heads`` heads of P = ``mamba_d_head``, state N =
``mamba_d_state``, G = ``mamba_n_groups`` groups of heads sharing B and C)::

    [z | xBC | dt] = h W_in                 widths H P | H P + 2 G N | H
    xBC = silu(conv4(xBC) + b_conv);   [x | B | C] = xBC   widths H P | G N | G N
    dt_t = softplus(dt_t + dt_bias)  in R^H;     A = -exp(A_log)  in R^H
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        per head, S in R^{P x N}
    y_t = S_t C_t + D x_t                             (S float32, S_0 = 0)
    out = (rmsnorm(y * silu(z)) * w) W_out            the norm over all H P

run here TOKEN BY TOKEN under one ``lax.scan`` over the positions (the
program runs the chunked form, ``ops/ssd.py``); ``conv4`` is a causal
depthwise convolution of width ``mamba_d_conv`` along the sequence (tap i
of w multiplies x_{t-3+i}). ``attention`` (``position_embedding_type``
"nope": nothing is rotated)::

    q = h Wq as num_attention_heads x hd;  k, v = h Wk, h Wv as
    num_key_value_heads x hd  (each serves heads / kv_heads query heads)
    y = softmax_causal(q k^T * attention_multiplier) v Wo   plain, by q blocks

The loss is the engine's: the mean next-token cross-entropy over all
positions, no auxiliary term. Nothing in the forward pass is a discrete
decision, so ``reference`` returns two values and every position counts.

Weights come in the program's layout (``models/granite_hybrid.py``, the
stack of ``models/stack.py``): ``layers.lead`` and ``layers.tail`` hold
unrolled layers, ``layers.period`` the layers of one period each stacked
over the whole periods; a layer holds ``mamba`` or ``attn``, and ``mlp``.

Departures from the published description: none in the mathematics. HF's
``time_step_limit`` clamp of dt is (0, inf): none. The config gives no
initialisation; the configuration file lists it under ``assumed``.

Counts: one multiply-add is 2 FLOPs; training is 3 x forward; remat and
the chunked form's extra products (the [Q, Q] matrices) are NOT counted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from architectures.mistral import (least_seconds, logits_of,  # noqa: F401
                                   loss_of, rms_norm)

_SAME = ("hidden_size", "intermediate_size", "vocab_size", "layer_types",
         "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
         "mamba_d_conv", "mamba_chunk_size", "mamba_expand",
         "mamba_conv_bias", "mamba_proj_bias", "embedding_multiplier",
         "attention_multiplier", "residual_multiplier", "logits_scaling")
WIDTHS = {
    **{key: key for key in _SAME},
    "shared_intermediate_size": "intermediate_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "num_hidden_layers": "num_layers",
    "max_position_embeddings": "max_seq_len",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "use_bias",
    "num_local_experts": "num_experts",
    "position_embedding_type": "position_embedding",
}
OPTIONAL = ()       # the file states every key

Q_BLOCK = 512       # query rows per attention block (memory bound only)


# ---- the plain float32 reference -------------------------------------------
def _silu(x):
    return x * jax.nn.sigmoid(x)


def _conv(x, w, bias):
    """Causal depthwise convolution along the sequence: x [B, S, C],
    w [n, C]; y_t = sum_i w[i] x_{t-(n-1)+i} + bias, zeros before the
    start."""
    n, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(xp[:, i:i + s] * w[i] for i in range(n)) + bias


def ssm_recurrence(x, dt, A, B, C):
    """Token by token. x [B, S, H, P]; dt [B, S, H]; A [H]; B, C
    [B, S, G, N], a group serving H / G heads; the state [B, H, P, N]
    float32 from zero. Returns y [B, S, H, P] (without the D x term)."""
    b, _, h, p = x.shape
    rep = h // B.shape[2]

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = (jnp.repeat(v, rep, axis=1) for v in (b_t, c_t))
        state = (state * jnp.exp(dt_t * A)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    xs = tuple(jnp.swapaxes(v, 0, 1) for v in (x, dt, B, C))
    _, y = jax.lax.scan(
        step, jnp.zeros((b, h, p, B.shape[-1]), jnp.float32), xs)
    return jnp.swapaxes(y, 0, 1)


def mamba_mixer(p, h, *, heads, head_dim, groups, state, eps):
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
    y = (y + x * p["D"][:, None]).reshape(b, s, inner)
    return rms_norm(y * _silu(z), p["norm"], eps) @ p["w_out"]


def causal_attention(q, k, v, scale):
    """q [B, S, H, D]; k, v [B, S, Hkv, D]; plain softmax at ``scale``, by
    blocks of query rows."""
    s, rep = q.shape[1], q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, rep, axis=2) for x in (k, v))
    out = []
    for q0 in range(0, s, Q_BLOCK):
        q1 = min(q0 + Q_BLOCK, s)
        ok = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, q0:q1], k[:, :q1])
        scores = jnp.where(ok[None, None], scores * scale, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(scores, axis=-1), v[:, :q1]))
    return jnp.concatenate(out, axis=1)


def attention_mixer(p, h, *, heads, kv_heads, scale):
    b, s, _ = h.shape
    hd = p["wq"].shape[-1] // heads
    q = (h @ p["wq"]).reshape(b, s, heads, hd)
    k = (h @ p["wk"]).reshape(b, s, kv_heads, hd)
    v = (h @ p["wv"]).reshape(b, s, kv_heads, hd)
    return causal_attention(q, k, v, scale).reshape(b, s, heads * hd) \
        @ p["wo"]


@functools.partial(jax.jit, static_argnames=("static",))
def layer(x, p, *, static):
    """One layer on x [B, S, D] float32. ``p``: the layer's weights in the
    program's layout, upcast here; ``static``: the numbers of ``m`` a
    layer needs, as a sorted tuple of pairs."""
    m = dict(static)
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
    eps, res = m["rms_norm_eps"], m["residual_multiplier"]
    h = rms_norm(x, p["ln1_scale"], eps)
    if "mamba" in p:
        mixed = mamba_mixer(
            p["mamba"], h, heads=m["mamba_n_heads"],
            head_dim=m["mamba_d_head"], groups=m["mamba_n_groups"],
            state=m["mamba_d_state"], eps=eps)
    else:
        mixed = attention_mixer(
            p["attn"], h, heads=m["num_attention_heads"],
            kv_heads=m["num_key_value_heads"],
            scale=m["attention_multiplier"])
    x = x + res * mixed
    h = rms_norm(x, p["ln2_scale"], eps)
    mlp = p["mlp"]
    return x + res * (
        (_silu(h @ mlp["w_gate"]) * (h @ mlp["w_up"])) @ mlp["w_down"])


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


_LAYER_KEYS = ("rms_norm_eps", "residual_multiplier", "mamba_n_heads",
               "mamba_d_head", "mamba_n_groups", "mamba_d_state",
               "num_attention_heads", "num_key_value_heads",
               "attention_multiplier")


def final_hidden(params, tokens, m: dict):
    """Final-normed hidden states [B, S, D] float32 for tokens [B, S]."""
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    x = x.astype(jnp.float32) * m["embedding_multiplier"]
    static = tuple(sorted((k, m[k]) for k in _LAYER_KEYS))
    for p in layers_in_order(params["layers"]):
        x = layer(x, p, static=static)
    return rms_norm(x, params["final_norm"]["scale"].astype(jnp.float32),
                    float(m["rms_norm_eps"]))


def reference(params, tokens, targets, m: dict, tail: int):
    """(loss as the engine defines it, a float; logits of the last ``tail``
    positions) from ``params`` in the program's layout. The head is the
    embedding table, transposed."""
    hidden = final_hidden(params, tokens, m) / m["logits_scaling"]
    head = params["embed"]["tokens"].T
    loss = float(loss_of(hidden, head, targets))
    return loss, logits_of(hidden[:, -tail:], head)


# ---- required operations and bytes -----------------------------------------
def _count(m: dict, kind: str) -> int:
    return sum(t == kind for t in m["layer_types"])


def forward_flops_per_token(m: dict, seq: int) -> dict:
    """Forward FLOPs per token at sequence length ``seq``, by part, summed
    over the layers of each kind."""
    d, f = m["hidden_size"], m["shared_intermediate_size"]
    h, p, n = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    inner, gn = h * p, m["mamba_n_groups"] * n
    conv = inner + 2 * gn
    mamba_proj = (2 * (d * (inner + conv + h) + inner * d)
                  + 2 * m["mamba_d_conv"] * conv)
    # the recurrence: the rank-one write dt x B^T and the read S C
    ssd_state = 4 * h * p * n
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // nh
    attn_proj = 2 * (2 * d * nh * hd + 2 * d * nkv * hd)
    attention = 4 * hd * nh * (seq + 1) / 2
    n_mamba, n_attn = _count(m, "mamba"), _count(m, "attention")
    parts = {"mamba_projections": n_mamba * mamba_proj,
             "ssd_state": n_mamba * ssd_state,
             "attention_projections": n_attn * attn_proj,
             "attention": n_attn * attention,
             "ffn": (n_mamba + n_attn) * 2 * 3 * d * f,
             "head": 2 * d * m["vocab_size"]}
    parts["total"] = sum(parts.values())
    return parts


def train_flops_per_token(m: dict, seq: int) -> float:
    """Required forward + backward FLOPs per trained token."""
    return 3.0 * forward_flops_per_token(m, seq)["total"]


def ssd_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                  itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the step's state-space scans (ALL the Mamba
    layers held here; ``per: step``) over ``batch`` sequences. Forward: the
    recurrence's two products a head and token (the write and the read of
    a [P, N] state); x, B, C read once (``itemsize``) and dt (float32), y
    written once. Backward: twice the products; those four and dy read,
    their four gradients written. The chunked form's [Q, Q] decay and
    score matrices and its chunk states are its own choice and are not
    counted. At the published widths the bytes bound it (0.17 ms a layer
    forward at 8192 tokens on a v5e against 0.09 for the products): the
    scan's roofline is the memory's, as ``least_seconds`` names it."""
    h, p, n = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    tokens = batch * seq
    bc = 2 * m["mamba_n_groups"] * n * itemsize       # B and C, a token
    flops = 4 * h * p * n * tokens
    if backward:
        flops = 2 * flops
        nbytes = tokens * (h * (3 * p * itemsize + 8) + 2 * bc)
    else:
        nbytes = tokens * (h * (2 * p * itemsize + 4) + bc)
    n_layers = _count(m, "mamba")
    return {"flops": n_layers * flops, "bytes": n_layers * nbytes}


def gqa_flash_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                        itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the step's flash-attention calls (the
    attention layers held here; ``per: step``), full causal, at the head
    width hidden / heads (64) with ``num_key_value_heads`` shared 4 : 1.
    Forward: S = QK^T and O = PV. Backward (one pass): S again, dV, dP, dQ,
    dK (5 matmuls). Each operand read once, each result written once (q,
    o, do, dq at the query heads; k, v, dk, dv at the kv heads; the
    float32 log-sum-exp row a head)."""
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // nh
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
    n_layers = _count(m, "attention")
    return {"flops": n_layers * flops, "bytes": n_layers * nbytes}

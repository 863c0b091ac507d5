"""Architecture ``deepseek_v3``: what the harness knows about the DeepSeek-V3
block as kakaocorp ``kanana-2-30b-a3b-instruct-2601`` publishes it
(``config.json``, ``model_type`` ``deepseek_v3``): a plain pre-norm stack
whose every layer is rotated latent attention (MLA) with a DIRECT query
(``q_lora_rank`` null), then a dense SwiGLU in the first
``first_k_dense_replace`` layers and bias-corrected sigmoid-routed experts
beside ``n_shared_experts`` shared ones after them, of which THIS CHIP
HOLDS A SHARE. Nothing is imported from ``deepspeed_tpu``. Three parts, as
``architectures/mistral.py``: ``WIDTHS``, the plain float32 ``reference``,
and the operations and bytes the algorithm requires.

A layer (``h = rmsnorm(x)``, ``x <- x + f(h)`` twice), H heads::

    q = h Wq  as H x (nope + rope)            (no query latent, no query norm)
    [c, k_pe] = h Wkva  (kv_lora + rope);  [k_nope, v] = rmsnorm(c) Wkvb
    q_pe, k_pe: each pair (x_2i, x_2i+1) rotated by position x theta_i,
        theta_i = rope_theta^(-2i / rope) (``rope_interleave``: the pairs
        are neighbours, not halves); k_pe is ONE head, shared by all H
    y = softmax_causal(q k^T (nope + rope)^-1/2) v Wo      (rope_scaling null)

The rotation is written here on the neighbours where they lie; the program
lays them out as halves first (a permutation of q's and k's rotated
channels alike, which their product does not see). The attention runs by
blocks of ``Q_BLOCK`` query rows against every key under the mask, one
compiled body for all blocks, so that a row of 32768 fits beside the
engine's state.

Routed layer: ``architectures/kimi_linear.py`` ``routed`` as it is
(sigmoid scores, the top k of scores + bias, the chosen scores over their
sum + 1e-20 times ``routed_scaling_factor``, the held experts, plus the
shared experts as ONE SwiGLU of their summed width), and its mask: a
position is left out iff, in some routed layer, a held expert's selection
score lies within ``check.routing_margin`` (as a share of that layer's
selection-score rms) of the boundary it would have to cross. The loss is
the engine's: the mean next-token cross-entropy over the vocabulary slice,
no auxiliary term.

Weights come in the program's layout (``models/deepseek_v3.py``): a layer
holds ``mla`` and ``mlp`` or ``moe``; ``layers_in_order`` walks ``lead``,
``period``, ``tail``.

Counts: one multiply-add is 2 FLOPs; training is 3 x forward; remat is
NOT counted. A token's routed experts count as ``num_experts_per_tok``
times the share held here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from architectures.kimi_linear import _swiglu, layers_in_order, routed
from architectures.lfm2_moe import ROW_BLOCK, _by_rows  # noqa: F401
from architectures.mistral import (LOSS_BLOCK, least_seconds,  # noqa: F401
                                   logits_of, loss_of, rms_norm)

WIDTHS = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "rope_interleave": "rope_interleave",
    "rope_scaling": "rope_scaling",
    "attention_bias": "use_bias",
    "tie_word_embeddings": "tie_embeddings",
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

Q_BLOCK = 256       # query rows per attention block (memory bound only)


# ---- the plain float32 reference -------------------------------------------
def rotate_pairs(x, theta: float):
    """x [B, S, H, R] with each neighbouring pair (x_2i, x_2i+1) rotated by
    ``position x theta^(-2i / R)``; the pairs stay where they lie."""
    r = x.shape[-1]
    freq = theta ** (-2.0 * jnp.arange(r // 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def causal_attention(q, k, v):
    """q, k [B, S, H, dqk]; v [B, S, H, dv]; plain softmax at scale
    dqk^-1/2 under the causal mask, ``Q_BLOCK`` query rows at a time
    against every key (one body for all blocks)."""
    b, s, h, d = q.shape
    block = min(Q_BLOCK, s)
    if s % block:
        raise ValueError(f"a sequence of {s} in blocks of {block}")
    keys = jnp.arange(s)

    def rows(args):
        qb, first = args
        ok = keys[None, :] <= first + jnp.arange(block)[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(
            jnp.float32(d))
        scores = jnp.where(ok[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    blocks = q.reshape(b, s // block, block, h, d).swapaxes(0, 1)
    out = jax.lax.map(rows, (blocks, jnp.arange(0, s, block)))
    return out.swapaxes(0, 1).reshape(b, s, h, v.shape[-1])


def mla_mixer(p, h, *, heads, nope, rope, dv, lora, eps, theta):
    b, s, _ = h.shape
    q = (h @ p["wq"]).reshape(b, s, heads, nope + rope)
    kva = h @ p["w_kva"]
    kv = (rms_norm(kva[..., :lora], p["kv_norm"], eps) @ p["w_kvb"]).reshape(
        b, s, heads, nope + dv)
    k_pe = rotate_pairs(kva[:, :, None, lora:], theta)
    q = jnp.concatenate(
        [q[..., :nope], rotate_pairs(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, heads, rope))],
        axis=-1)
    a = causal_attention(q, k, kv[..., nope:])
    return a.reshape(b, s, heads * dv) @ p["wo"]


@functools.partial(jax.jit, static_argnames=("static",))
def layer(x, p, *, static):
    """One layer on x [B, S, D] float32 -> (x, relative routing distance
    [B, S]; +inf for a layer without a router). ``p``: the layer's weights
    in the program's layout, upcast here; ``static``: the numbers of ``m``
    a layer needs, hashable."""
    m = dict(static)
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
    eps = m["rms_norm_eps"]
    b, s, d = x.shape
    x = x + mla_mixer(
        p["mla"], rms_norm(x, p["ln1_scale"], eps),
        heads=m["num_attention_heads"], nope=m["qk_nope_head_dim"],
        rope=m["qk_rope_head_dim"], dv=m["v_head_dim"],
        lora=m["kv_lora_rank"], eps=eps, theta=float(m["rope_theta"]))
    h = rms_norm(x, p["ln2_scale"], eps).reshape(b * s, d)
    if "mlp" in p:
        out = _by_rows(functools.partial(_swiglu, p["mlp"]), h)
        return x + out.reshape(b, s, d), jnp.full((b, s), jnp.inf)
    out, dist, rms = routed(
        p["moe"], h, top_k=m["num_experts_per_tok"], first=0,
        renormalise=m["norm_topk_prob"], scaling=m["routed_scaling_factor"])
    return x + out.reshape(b, s, d), (dist / rms).reshape(b, s)


_LAYER_KEYS = ("rms_norm_eps", "num_attention_heads", "qk_nope_head_dim",
               "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rope_theta",
               "num_experts_per_tok", "norm_topk_prob",
               "routed_scaling_factor")


def _forward(params, tokens, m: dict):
    """(final-normed hidden [B, S, D] float32, the least relative routing
    distance over the routed layers [B, S])."""
    if m["rope_scaling"] or not m["rope_interleave"]:
        raise ValueError("the reference rotates neighbouring pairs at the "
                         "plain frequencies: rope_interleave, no scaling")
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    x = x.astype(jnp.float32)
    static = tuple((k, m[k]) for k in _LAYER_KEYS)
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
    return [("mla", "dense" if i < m["first_k_dense_replace"] else "moe")
            for i in range(m["num_hidden_layers"])]


def _n(m: dict, position: int, kind: str) -> int:
    return sum(k[position] == kind for k in layer_kinds(m))


def held_share(m: dict) -> float:
    """Routed experts a token computes with HERE: its
    ``num_experts_per_tok`` times the share of the experts held."""
    return (m["num_experts_per_tok"] * m["n_routed_experts"]
            / m["num_routed_experts"])


def live_pairs(seq: int) -> int:
    """(query, key) pairs the causal mask leaves live, one head, one
    sequence: the same count whatever sweeps them, one row or spans."""
    return seq * (seq + 1) // 2


def forward_flops_per_token(m: dict, seq: int) -> dict:
    """Forward FLOPs per token at sequence length ``seq``, by part, summed
    over the layers."""
    d = m["hidden_size"]
    nh = m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    layers = m["num_hidden_layers"]
    n_moe = _n(m, 1, "moe")
    mla_proj = 2 * (d * nh * qk
                    + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
                    + m["kv_lora_rank"] * nh * (m["qk_nope_head_dim"]
                                                + m["v_head_dim"])
                    + nh * m["v_head_dim"] * d)
    mla_attn = 2 * (qk + m["v_head_dim"]) * nh * live_pairs(seq) / seq
    expert = 2 * 3 * d * m["moe_intermediate_size"]
    parts = {"mla_projections": layers * mla_proj,
             "mla_attention": layers * mla_attn,
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


def mla_flash_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                        itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the step's flash-attention calls (every
    layer; ``per: step``), full causal at LIVE pairs, at the PUBLISHED key
    width (nope + rope) and value width, whatever the kernel pads to and
    however the row is swept (whole, or in spans whose partial outputs are
    merged: the merge is the program's cost, not the roofline's). Forward:
    S = QK^T at the key width and O = PV at the value width. Backward (one
    pass): S again, dQ and dK at the key width; dV and dP at the value
    width. Each operand read once, each result written once, the float32
    log-sum-exp row a head."""
    nh = m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    dv = m["v_head_dim"]
    pairs = batch * nh * live_pairs(seq)
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
    a token's ``held_share``. NINE matmul units a row: three forward and
    six backward (the backward's second run of the two input matmuls is
    its own choice and is not counted). Bytes: every held expert's weights
    read once (and their float32 gradients written once, backward), a
    row's input gathered and its output scattered."""
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

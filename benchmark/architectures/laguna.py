"""Architecture ``laguna``: what the harness knows about Laguna (poolside
``Laguna-S-2.1`` ``config.json``, ``model_type`` ``laguna``): a pre-norm
stack whose attention layers are of two kinds AT TWO WIDTHS (one full layer
of 48 query heads to three window layers of 72, over 8 key heads of 128),
each gated a head and followed by a dense SwiGLU (layer 0) or by
softmax-routed experts beside a shared one, of which THIS CHIP HOLDS A
SHARE. Nothing is imported from ``deepspeed_tpu``. Three parts, as
``architectures/mistral.py``: ``WIDTHS``, the plain float32 ``reference``,
and the operations and bytes the algorithm requires.

For layer ``l`` of kind ``t = layer_types[l]`` with ``H_t =
num_attention_heads_per_layer[l]`` query heads (RMSNorm eps
``rms_norm_eps``, no bias, untied head)::

    h = rmsnorm(x, g1);  q = h Wq as H_t heads of head_dim;
    k = h Wk, v = h Wv as Hkv heads of head_dim
    q, k = rotate_t(q), rotate_t(k)     rotate-half over the FIRST
                                        head_dim x partial_rotary_factor
                                        channels (pairs (i, i + rot / 2)),
                                        the table of rope_parameters[t]
                                        reckoned on that width; the other
                                        channels pass through
    a = softmax(q k^T / sqrt(head_dim) + mask_t) v     H_t / Hkv query
                                                       heads a key head
    g = sigmoid(h Wg)                   Wg [hidden, H_t]: one number a head
    x = x + concat_i(g_i a_i) Wo
    h2 = rmsnorm(x, g2)
    dense:   x = x + (silu(h2 Wg') * (h2 Wu')) Wd'
    sparse:  r = h2 Wr  (E logits);  p = softmax(r);  T = the k largest
             w_e = s p_e / sum_{j in T} p_j    (norm_topk_prob; s =
                                                moe_routed_scaling_factor)
             x = x + sum_{e in T, e held} w_e SwiGLU_e(h2) + SwiGLU_sh(h2)

``mask_t``, ``rotate_t``'s tables (plain and YaRN, cos and sin times
``attention_factor``) and the mask of positions are
``architectures/mellum.py``'s; YaRN's ramp ends are reckoned on the ROTATED
width (at the published sizes 64: low 9, high 18). After the last layer
``rmsnorm`` and the head over the vocabulary slice; the loss is the
engine's, the mean next-token cross-entropy, with no auxiliary term.

Weights come in the program's layout (``models/laguna.py``): a layer holds
its attention weights under ``swa`` or ``full`` (``wq``, ``wk``, ``wv``,
``wg``, ``wo``) and ``mlp`` or ``moe`` (``router``, ``experts``,
``shared``); ``layers_in_order`` walks ``lead``, ``period``, ``tail``.

Departures from the published config: none in what it states. What it does
not state (the router's scoring function, an ungated shared expert, where
the gate is read and multiplied, which half is rotated) is listed under
``assumed`` in the configuration file.

Counts: one multiply-add is 2 FLOPs; training is 3 x forward; remat and a
masked tile's dead half are NOT counted (attention counts live pairs
only). A token's routed experts count as ``num_experts_per_tok`` times the
share held here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from architectures import mellum
from architectures.kimi_linear import _swiglu, layers_in_order
from architectures.lfm2_moe import _by_rows
from architectures.mellum import (KINDS, _hashable, attention,  # noqa: F401
                                  live_pairs, rotate)
from architectures.mistral import (LOSS_BLOCK, least_seconds,  # noqa: F401
                                   logits_of, loss_of, rms_norm)

WIDTHS = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "head_dim": "head_dim",
    "num_attention_heads": "num_heads",     # a full layer's, as published
    "num_attention_heads_per_layer": "num_attention_heads_per_layer",
    "num_key_value_heads": "num_kv_heads",
    "moe_intermediate_size": "moe_intermediate_size",
    "shared_expert_intermediate_size": "shared_expert_intermediate_size",
    "num_experts": "moe_held_experts",          # the experts HELD here
    "num_routed_experts": "num_experts",        # the router's width
    "num_experts_per_tok": "moe_top_k",
    "norm_topk_prob": "moe_norm_topk",
    "moe_routed_scaling_factor": "routed_scaling_factor",
    "sliding_window": "sliding_window",
    "layer_types": "layer_types",
    "mlp_layer_types": "mlp_layer_types",
    "mlp_only_layers": "mlp_only_layers",
    "gating": "gating",
    "gating_types": "gating_types",
    "rope_parameters": "rope_parameters",
    "rms_norm_eps": "norm_eps",
    "attention_bias": "use_bias",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "num_hidden_layers": "num_layers",
    "max_position_embeddings": "max_seq_len",
}
OPTIONAL = ()       # the file states every key
CHECK_KEYS = ("routing_margin", "excluded_share_max")


# ---- the plain float32 reference -------------------------------------------
def kind_heads(m: dict) -> dict:
    """{layer_types entry: its query heads}."""
    return dict(zip(m["layer_types"], m["num_attention_heads_per_layer"]))


def rotate_leading(x, head_dim: int, section: dict):
    """x [B, S, H, D] with its first ``head_dim x partial_rotary_factor``
    channels rotated by the section's table, reckoned on that width; the
    others as they are."""
    rot = int(head_dim * section.get("partial_rotary_factor", 1))
    freq, factor = mellum.inv_freq(rot, section)
    return jnp.concatenate(
        [rotate(x[..., :rot], freq, factor), x[..., rot:]], axis=-1)


def gated_attention(p, h, *, heads, kv_heads, head_dim, section, window):
    """One attention sublayer on the normed h [B, S, D] -> [B, S, D]: the
    projections at ``heads`` query heads, the rotation, the masked softmax,
    the sigmoid gate a head, the output projection."""
    b, s, _ = h.shape
    q = (h @ p["wq"]).reshape(b, s, heads, head_dim)
    k = (h @ p["wk"]).reshape(b, s, kv_heads, head_dim)
    v = (h @ p["wv"]).reshape(b, s, kv_heads, head_dim)
    q = rotate_leading(q, head_dim, section)
    k = rotate_leading(k, head_dim, section)
    a = attention(q, k, v, window)                  # [B, S, heads, D]
    g = jax.nn.sigmoid(h @ p["wg"])                 # [B, S, heads]
    return (a * g[..., None]).reshape(b, s, -1) @ p["wo"]


def routed(p, h, *, top_k, first, renormalise, scaling):
    """The held share of a routed layer on h [N, D] -> (out, the least
    distance [N] of a held expert's router logit from the boundary it
    would have to cross, the rms of the logits): ``architectures/
    mellum.py``'s softmax router and held experts, the chosen weights times
    ``scaling``, plus the shared expert where the layer has one (every
    chip computes it alike: a share counts it whole)."""
    out, dist, rms = mellum.routed(p, h, top_k=top_k, first=first,
                                   renormalise=renormalise)
    out = scaling * out
    if "shared" in p:
        out = out + _by_rows(functools.partial(_swiglu, p["shared"]), h)
    return out, dist, rms


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
    kind = "swa" if "swa" in p else "full"
    t = KINDS[kind]
    x = x + gated_attention(
        p[kind], rms_norm(x, p["ln1_scale"], eps),
        heads=kind_heads(m)[t],
        kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        section=dict(dict(m["rope_parameters"])[t]),
        window=m["sliding_window"] if kind == "swa" else None)
    h = rms_norm(x, p["ln2_scale"], eps).reshape(b * s, d)
    if "mlp" in p:
        out = _by_rows(functools.partial(_swiglu, p["mlp"]), h)
        return x + out.reshape(b, s, d), jnp.full((b, s), jnp.inf)
    out, dist, rms = routed(p["moe"], h, top_k=m["num_experts_per_tok"],
                            first=0, renormalise=m["norm_topk_prob"],
                            scaling=m["moe_routed_scaling_factor"])
    return x + out.reshape(b, s, d), (dist / rms).reshape(b, s)


_LAYER_KEYS = ("rms_norm_eps", "head_dim", "num_attention_heads_per_layer",
               "num_key_value_heads", "layer_types", "sliding_window",
               "rope_parameters", "num_experts_per_tok", "norm_topk_prob",
               "moe_routed_scaling_factor")


def _forward(params, tokens, m: dict):
    """(final-normed hidden [B, S, D] float32, the least relative routing
    distance over the routed layers [B, S])."""
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    x = x.astype(jnp.float32)
    static = tuple((k, _hashable(m[k])) for k in _LAYER_KEYS)
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
def _n(m: dict, kind: str) -> int:
    """Layers whose attention is of ``kind`` (``swa`` | ``full``)."""
    return sum(t == KINDS[kind] for t in m["layer_types"])


def _n_sparse(m: dict) -> int:
    return sum(t == "sparse" for t in m["mlp_layer_types"])


def held_share(m: dict) -> float:
    """Routed experts a token computes with HERE: its
    ``num_experts_per_tok`` times the share of the experts held."""
    return (m["num_experts_per_tok"] * m["num_experts"]
            / m["num_routed_experts"])


def forward_flops_per_token(m: dict, seq: int) -> dict:
    """Forward FLOPs per token at sequence length ``seq``, by part, summed
    over the layers; the head count is the layer's kind's."""
    d, hd, nkv = m["hidden_size"], m["head_dim"], m["num_key_value_heads"]
    heads = kind_heads(m)
    expert = 2 * 3 * d * m["moe_intermediate_size"]
    shared = 2 * 3 * d * m["shared_expert_intermediate_size"]
    parts = {
        # q and o at the kind's heads, k and v at the key heads, the gate
        "projections": sum(2 * (2 * d * heads[t] * hd + 2 * d * nkv * hd
                                + d * heads[t]) for t in m["layer_types"]),
        # QK^T and PV: 2 matmuls x 2 FLOPs x head_dim a live pair and head
        "swa_attention": _n(m, "swa") * 4 * hd * heads.get(KINDS["swa"], 0)
        * live_pairs(m, seq, "swa") / seq,
        "full_attention": _n(m, "full") * 4 * hd
        * heads.get(KINDS["full"], 0) * live_pairs(m, seq, "full") / seq,
        "dense_ffn": (len(m["layer_types"]) - _n_sparse(m))
        * 2 * 3 * d * m["intermediate_size"],
        "routed_layers": _n_sparse(m) * (
            2 * d * m["num_routed_experts"] + shared
            + expert * held_share(m)),
        "head": 2 * d * m["vocab_size"]}
    parts["total"] = sum(parts.values())
    return parts


def train_flops_per_token(m: dict, seq: int) -> float:
    """Required forward + backward FLOPs per trained token."""
    return 3.0 * forward_flops_per_token(m, seq)["total"]


def _flash_call_cost(m, batch, seq, kind, backward, itemsize):
    """``architectures/mellum.py``'s count of the step's flash calls of the
    layers of one kind (live pairs only, each operand read once and each
    result written once) at THAT KIND'S query heads."""
    at_kind = {**m, "num_attention_heads": kind_heads(m).get(KINDS[kind], 0)}
    return mellum._flash_call_cost(at_kind, batch, seq, kind, backward,
                                   itemsize)


def swa_flash_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                        itemsize: int = 2) -> dict:
    """The window layers' kernels at their 72 heads: 4,063,488 live pairs a
    head of 33,558,528 at 8192 and a window of 512."""
    return _flash_call_cost(m, batch, seq, "swa", backward, itemsize)


def full_flash_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                         itemsize: int = 2) -> dict:
    """The full layers' kernels at their 48 heads: every pair under the
    diagonal."""
    return _flash_call_cost(m, batch, seq, "full", backward, itemsize)


def moe_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                  itemsize: int = 2, rows: float | None = None) -> dict:
    """FLOPs and HBM bytes of the step's held-expert calls (the routed
    layers held here; ``per: step``) at ``rows`` rows (token, choice) a
    routed layer, as the program counted them; None: a balanced router's,
    a token's ``held_share``. Three matmul units a row forward and EIGHT
    backward: the units that run (the backward rule keeps nothing of the
    forward but its inputs and makes ``gate`` and ``up`` again before its
    six products; remat's rerun of the forward is not counted, nor is a
    tile's padding). THE OTHER FIVE ROUTED MODULES COUNT SIX BACKWARD (the
    work the mathematics needs: PR 63 settled them on nine units a row and
    could not move this one, because tier-1's ``tests/test_laguna.py``
    ``test_required_operations_by_hand`` pins these eight and a
    ``benchmark`` PR edits nothing under ``tests/``): this cell's
    ``moe_experts_roofline.routed`` reads about 6% over what nine would
    give (the backward call turns memory-bound at six; ``PERF.md`` section
    7). Bytes: every held expert's weights read once (and their float32
    gradients written once, backward), a row's input gathered and its
    output scattered."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    if rows is None:
        rows = batch * seq * held_share(m)
    weights = m["num_experts"] * 3 * d * f
    unit = rows * 2 * d * f
    nbytes = weights * itemsize + 2 * rows * d * itemsize
    if backward:
        flops, nbytes = 8 * unit, nbytes + weights * 4 + rows * d * itemsize
    else:
        flops = 3 * unit
    return {"flops": _n_sparse(m) * flops, "bytes": _n_sparse(m) * nbytes}

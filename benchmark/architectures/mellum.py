"""Architecture ``mellum``: what the harness knows about Mellum 2
(JetBrains ``Mellum2-12B-A2.5B-Instruct`` ``config.json``, ``model_type``
``mellum``): a pre-norm stack whose attention layers are of two kinds,
three with a window to one without, each followed by softmax-routed
experts of which THIS CHIP HOLDS A SHARE. Nothing is imported from
``deepspeed_tpu``. Three parts, as ``architectures/mistral.py``:
``WIDTHS``, the plain float32 ``reference``, and the operations and bytes
the algorithm requires.

For layer ``l`` of kind ``t = layer_types[l]`` (RMSNorm eps
``rms_norm_eps``, no bias, untied head)::

    h = rmsnorm(x, g1);  q = h Wq as H heads of head_dim;
    k = h Wk, v = h Wv as Hkv heads of head_dim        (head_dim is given:
                                        it is NOT hidden_size / H)
    q, k = rotate_t(q), rotate_t(k)     rotate-half, the whole head, the
                                        table of rope_parameters[t]
    a = softmax(q k^T / sqrt(head_dim) + mask_t) v     H / Hkv query heads
                                                       a key head
    x = x + a Wo
    h2 = rmsnorm(x, g2);  r = h2 Wr  (E logits);  p = softmax(r)
    T = the k largest of p;  w_e = p_e / sum_{j in T} p_j   (norm_topk_prob)
    x = x + sum_{e in T, e held} w_e (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e

``mask_t`` is causal and, for ``sliding_attention``, also drops keys with
``row - col >= sliding_window`` (the last ``sliding_window`` positions, the
row's own among them). ``rotate_t``: angle ``position x inv_freq_i`` on the
pair (i, i + head_dim / 2), with ``inv_freq_i = theta^(-2i / head_dim)``
for ``rope_type`` ``default``; for ``yarn`` (factor ``s``, original context
``L``, ``beta_fast``, ``beta_slow``)::

    c(n) = head_dim ln(L / (2 pi n)) / (2 ln theta)
    low = floor(c(beta_fast));  high = ceil(c(beta_slow))
    ramp_i = clip((i - low) / (high - low), 0, 1)
    inv_freq_i = theta^(-2i/head_dim) ((1 - ramp_i) + ramp_i / s)

and cos and sin multiplied by ``attention_factor`` (at the published sizes
low 18, high 35, factor 1.2772588722239782 = 0.1 ln 16 + 1). After the
last layer ``rmsnorm`` and the head over the vocabulary slice; the loss is
the engine's, the mean next-token cross-entropy, with no auxiliary term.
There is no shared expert, no selection bias and no scaling of the
weights. Every held expert is evaluated on every token and weighted by its
gate (zero where it was not chosen or is not held): plain, and exact. What
the absent experts would have added is left out, as in the program.

Weights come in the program's layout (``models/mellum.py``):
``layers.lead`` and ``layers.tail`` hold unrolled layers, ``layers.period``
the layers of one period each stacked over the whole periods
(``architectures/kimi_linear.py`` ``layers_in_order`` walks them); a layer
holds its attention weights under ``swa`` or ``full`` and ``moe``.

**The mask.** As ``architectures/kimi_linear.py`` for a held share: a flip
of the top-k set matters only where it moves a HELD expert in or out (two
absent experts trading places change the renormalising sum by their
probability gap). ``reference`` returns ``counted`` [B, tail]: a position
is left out iff, in some layer, a held expert's router LOGIT lies within
``check.routing_margin`` (as a share of that layer's logits' rms over all
positions and experts) of the boundary it would have to cross: the
(k+1)-th logit if it is chosen, the k-th if it is not. The softmax is
monotone, so the logits decide the set; decided from these float32 numbers
alone.

Departures from the published description: none in the mathematics. Not in
the published config and so not here: a q/k norm, a multi-token prediction
head, an auxiliary loss; ``intermediate_size`` is unused (every layer is
``sparse``). The configuration file lists them under ``assumed``.

Counts: one multiply-add is 2 FLOPs; training is 3 x forward; remat and a
masked tile's dead half are NOT counted (attention counts live pairs
only). A token's routed experts count as ``num_experts_per_tok`` times the
share held here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from architectures.kimi_linear import _swiglu, layers_in_order  # noqa: F401
from architectures.mistral import (LOSS_BLOCK, least_seconds,  # noqa: F401
                                   logits_of, loss_of, rms_norm,
                                   visible_keys_total)

WIDTHS = {
    "hidden_size": "hidden_size",
    "head_dim": "head_dim",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_experts": "moe_held_experts",          # the experts HELD here
    "num_routed_experts": "num_experts",        # the router's width
    "num_experts_per_tok": "moe_top_k",
    "norm_topk_prob": "moe_norm_topk",
    "sliding_window": "sliding_window",
    "layer_types": "layer_types",
    "rope_parameters": "rope_parameters",
    "rms_norm_eps": "norm_eps",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "num_hidden_layers": "num_layers",
    "max_position_embeddings": "max_seq_len",
}
OPTIONAL = ()       # the file states every key
CHECK_KEYS = ("routing_margin", "excluded_share_max")

Q_BLOCK = 512       # query rows per attention block (memory bound only)
KINDS = {"swa": "sliding_attention", "full": "full_attention"}


# ---- the plain float32 reference -------------------------------------------
def inv_freq(head_dim: int, section: dict):
    """(inv_freq [head_dim // 2] float32, the factor on cos and sin) of one
    section of ``rope_parameters``, the closed form above."""
    theta = float(section["rope_theta"])
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / head_dim)
    if section["rope_type"] == "default":
        return plain, 1.0
    if section["rope_type"] != "yarn":
        raise ValueError(f"rope_type {section['rope_type']!r}")
    low, high = yarn_ramp_ends(head_dim, section)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    factor = section.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(section["factor"]) + 1.0
    return plain * ((1 - ramp) + ramp / section["factor"]), float(factor)


def yarn_ramp_ends(head_dim: int, section: dict) -> tuple[int, int]:
    """``(low, high)``: the pairs between which YaRN blends."""
    def c(n):
        return (head_dim * math.log(
            section["original_max_position_embeddings"] / (2 * math.pi * n))
            / (2 * math.log(section["rope_theta"])))
    return (max(math.floor(c(section["beta_fast"])), 0),
            min(math.ceil(c(section["beta_slow"])), head_dim - 1))


def rotate(x, freq, factor):
    """x [B, S, H, D] rotated by position; rotate-half pairs."""
    d = x.shape[-1]
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window):
    """q [B, S, H, D]; k, v [B, S, Hkv, D]; plain softmax at D^-1/2, causal
    and, with ``window``, over the last ``window`` positions; by blocks of
    query rows, a key head serving its H / Hkv query heads."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    q = q.reshape(b, s, hkv, h // hkv, d)
    out = []
    for q0 in range(0, s, Q_BLOCK):
        q1 = min(q0 + Q_BLOCK, s)
        k0 = 0 if window is None else max(0, q0 - window + 1)
        qi = jnp.arange(q0, q1)[:, None]
        ki = jnp.arange(k0, q1)[None, :]
        ok = ki <= qi
        if window is not None:
            ok = ok & (qi - ki < window)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", q[:, q0:q1], k[:, k0:q1])
        scores = jnp.where(ok, scores / jnp.sqrt(jnp.float32(d)), -jnp.inf)
        out.append(jnp.einsum("bgrqk,bkgd->bqgrd",
                              jax.nn.softmax(scores, axis=-1), v[:, k0:q1]))
    return jnp.concatenate(out, axis=1).reshape(b, s, h, d)


def routed(p, h, *, top_k, first, renormalise):
    """The held share of a routed layer on h [N, D] -> (out, the least
    distance [N] of a held expert's router logit from the boundary it
    would have to cross, the rms of the logits)."""
    logits = h @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    ordered, idx = jax.lax.top_k(logits, top_k + 1)
    idx = idx[:, :top_k]
    w = jnp.take_along_axis(probs, idx, axis=-1)
    if renormalise:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    e = p["experts"]
    n_held = e["w_up"].shape[0]
    if n_held < logits.shape[-1]:
        # a share takes the weights as given in the backward, as the
        # program's does: of their gradient it has only its own terms
        w = jax.lax.stop_gradient(w)
    gates = jnp.einsum("nk,nke->ne", w, jax.nn.one_hot(
        idx, logits.shape[-1], dtype=jnp.float32))
    out = jnp.zeros_like(h)
    for j in range(n_held):
        one = {name: e[name][j] for name in ("w_gate", "w_up", "w_down")}
        out = out + gates[:, first + j, None] * _swiglu(one, h)
    held = logits[:, first:first + n_held]
    kth, nxt = ordered[:, top_k - 1, None], ordered[:, top_k, None]
    dist = jnp.where(held >= kth, held - nxt, kth - held)
    return out, jnp.min(dist, axis=-1), jnp.sqrt(jnp.mean(logits * logits))


def _hashable(x):
    if isinstance(x, dict):
        return tuple(sorted((k, _hashable(v)) for k, v in x.items()))
    return tuple(x) if isinstance(x, list) else x


@functools.partial(jax.jit, static_argnames=("static",))
def layer(x, p, *, static):
    """One layer on x [B, S, D] float32 -> (x, relative routing distance
    [B, S]). ``p``: the layer's weights in the program's layout, upcast
    here; ``static``: the numbers of ``m`` a layer needs, hashable."""
    m = dict(static)
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
    eps, hd = m["rms_norm_eps"], m["head_dim"]
    b, s, d = x.shape
    kind = "swa" if "swa" in p else "full"
    a = p[kind]
    h = rms_norm(x, p["ln1_scale"], eps)
    q = (h @ a["wq"]).reshape(b, s, m["num_attention_heads"], hd)
    k = (h @ a["wk"]).reshape(b, s, m["num_key_value_heads"], hd)
    v = (h @ a["wv"]).reshape(b, s, m["num_key_value_heads"], hd)
    freq, factor = inv_freq(hd, dict(dict(m["rope_parameters"])[KINDS[kind]]))
    q, k = rotate(q, freq, factor), rotate(k, freq, factor)
    window = m["sliding_window"] if kind == "swa" else None
    x = x + attention(q, k, v, window).reshape(b, s, -1) @ a["wo"]
    h = rms_norm(x, p["ln2_scale"], eps)
    out, dist, rms = routed(p["moe"], h.reshape(b * s, d),
                            top_k=m["num_experts_per_tok"], first=0,
                            renormalise=m["norm_topk_prob"])
    return x + out.reshape(b, s, d), (dist / rms).reshape(b, s)


_LAYER_KEYS = ("rms_norm_eps", "head_dim", "num_attention_heads",
               "num_key_value_heads", "sliding_window", "rope_parameters",
               "num_experts_per_tok", "norm_topk_prob")


def _forward(params, tokens, m: dict):
    """(final-normed hidden [B, S, D] float32, the least relative routing
    distance over the layers [B, S])."""
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
    return sum(t == KINDS[kind] for t in m["layer_types"])


def live_pairs(m: dict, seq: int, kind: str) -> int:
    """(query, key) pairs the mask of one layer of ``kind`` leaves live,
    one head, one sequence."""
    return visible_keys_total(
        seq, m["sliding_window"] if kind == "swa" else None)


def held_share(m: dict) -> float:
    """Routed experts a token computes with HERE: its
    ``num_experts_per_tok`` times the share of the experts held."""
    return (m["num_experts_per_tok"] * m["num_experts"]
            / m["num_routed_experts"])


def forward_flops_per_token(m: dict, seq: int) -> dict:
    """Forward FLOPs per token at sequence length ``seq``, by part, summed
    over the layers."""
    d, hd = m["hidden_size"], m["head_dim"]
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    n = len(m["layer_types"])
    # QK^T and PV: 2 matmuls x 2 FLOPs x head_dim a live pair and head
    pair = 4 * hd * nh
    parts = {
        "projections": n * 2 * (2 * d * nh * hd + 2 * d * nkv * hd),
        "swa_attention": _n(m, "swa") * pair * live_pairs(m, seq, "swa")
        / seq,
        "full_attention": _n(m, "full") * pair * live_pairs(m, seq, "full")
        / seq,
        "router": n * 2 * d * m["num_routed_experts"],
        "held_experts": n * 2 * 3 * d * m["moe_intermediate_size"]
        * held_share(m),
        "head": 2 * d * m["vocab_size"]}
    parts["total"] = sum(parts.values())
    return parts


def train_flops_per_token(m: dict, seq: int) -> float:
    """Required forward + backward FLOPs per trained token."""
    return 3.0 * forward_flops_per_token(m, seq)["total"]


def _flash_call_cost(m, batch, seq, kind, backward, itemsize):
    """The step's flash-attention calls of the layers of one kind
    (``per: step``) at LIVE pairs: a masked tile's dead half is the
    kernel's cost, not the roofline's. Forward: S = QK^T and O = PV.
    Backward (one pass): S again, dV, dP, dQ, dK (5 matmuls). Each operand
    read once, each result written once (q, o, do, dq at the query heads;
    k, v, dk, dv at the key heads; the float32 log-sum-exp row a head)."""
    hd, nh, nkv = (m["head_dim"], m["num_attention_heads"],
                   m["num_key_value_heads"])
    pairs = batch * nh * live_pairs(m, seq, kind)
    q_like = batch * seq * nh * hd * itemsize
    kv_like = batch * seq * nkv * hd * itemsize
    lse = batch * seq * nh * 4
    if backward:
        flops, nbytes = 5 * 2 * hd * pairs, 4 * q_like + 4 * kv_like + lse
    else:
        flops, nbytes = 2 * 2 * hd * pairs, 2 * q_like + 2 * kv_like + lse
    n = _n(m, kind)
    return {"flops": n * flops, "bytes": n * nbytes}


def swa_flash_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                        itemsize: int = 2) -> dict:
    """The window layers' kernels: 16,253,440 live pairs a head of
    134,225,920 at 16384 and a window of 1024."""
    return _flash_call_cost(m, batch, seq, "swa", backward, itemsize)


def full_flash_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                         itemsize: int = 2) -> dict:
    """The full layers' kernels: every pair under the diagonal."""
    return _flash_call_cost(m, batch, seq, "full", backward, itemsize)


def moe_call_cost(m: dict, batch: int, seq: int, *, backward: bool,
                  itemsize: int = 2, rows: float | None = None) -> dict:
    """FLOPs and HBM bytes of the step's held-expert calls (every layer
    held here; ``per: step``) at ``rows`` rows (token, choice) a layer, as
    the program counted them; None: a balanced router's, a token's
    ``held_share``. Three matmuls a row forward and six backward (the
    backward's second run of the two input matmuls is its own choice and
    is not counted; nor is a block's padding). Bytes: every held expert's
    weights read once (and their float32 gradients written once,
    backward), a row's input gathered and its output scattered."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    if rows is None:
        rows = batch * seq * held_share(m)
    weights = m["num_experts"] * 3 * d * f
    flops = rows * 2 * 3 * d * f
    nbytes = weights * itemsize + 2 * rows * d * itemsize
    if backward:
        flops, nbytes = 2 * flops, nbytes + weights * 4 + rows * d * itemsize
    n = len(m["layer_types"])
    return {"flops": n * flops, "bytes": n * nbytes}

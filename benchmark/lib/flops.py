"""Operations and bytes the algorithm requires, from shapes alone.

These are the yardstick's own counts: nothing here reads the program's
``cost_analysis`` or its FLOP estimators. A model configuration is the
``model`` object of a file under ``benchmark/configs`` (HF key names).

Conventions: one multiply-add is 2 FLOPs. Training requires forward plus
backward = 3 x forward matmul FLOPs; recomputation (remat, the flash
backward's score recompute) is NOT counted in ``train_flops_per_token``.
Attention counts only key positions the causal + sliding-window mask
lets a query see.
"""

from __future__ import annotations


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

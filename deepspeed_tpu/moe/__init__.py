from .layer import MoE  # noqa: F401
from .sharded_moe import (  # noqa: F401
    balance_bias,
    dequantize_experts,
    held_experts_ffn,
    moe_ffn,
    moe_ffn_held,
    moe_ffn_grouped,
    quantize_experts,
    sigmoid_top_k,
    top_k_gating,
)

"""Mixtral family: MoE decoder.

A DecoderLM whose FFN is a top-k routed mixture of experts. Expert weights
are stacked ``[L, E, ...]``: the ``ep`` mesh axis shards E (expert
parallelism), fsdp/tp still shard the inner dims — the composition the
reference builds with expert-parallel groups
(deepspeed/moe/layer.py:89, utils/groups.py:117).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..moe.sharded_moe import moe_ffn
from .base import ModelConfig, register_model
from .transformer import DecoderLM, _dense_init


def mixtral_config(size: str = "8x7b", **overrides) -> ModelConfig:
    presets = {
        "tiny": dict(hidden_size=64, num_layers=2, num_heads=4,
                     num_kv_heads=2, intermediate_size=128, vocab_size=512,
                     max_seq_len=128, num_experts=4, moe_top_k=2),
        # MoE reference config (ISSUE 16): big enough that routing,
        # ep sharding and the dispatch wire dominate like a real MoE
        # block (8 experts top-2 -> 4x total/active param ratio in the
        # FFN), small enough for the bench rig and slow tests
        "ref": dict(hidden_size=256, num_layers=4, num_heads=4,
                    num_kv_heads=4, intermediate_size=512,
                    vocab_size=4096, max_seq_len=512, num_experts=8,
                    moe_top_k=2, capacity_factor=1.25),
        "8x7b": dict(hidden_size=4096, num_layers=32, num_heads=32,
                     num_kv_heads=8, intermediate_size=14336,
                     vocab_size=32000, max_seq_len=4096, num_experts=8,
                     moe_top_k=2, rope_theta=1e6),
    }
    base = dict(norm_type="rmsnorm", activation="swiglu",
                position_embedding="rope", use_bias=False,
                tie_embeddings=False)
    base.update(presets[size])
    base.update(overrides)
    return ModelConfig(**base)


@register_model("mixtral")
class Mixtral(DecoderLM):
    def __init__(self, config: ModelConfig | None = None,
                 size: str | None = None, **overrides):
        if config is not None and (size is not None or overrides):
            raise ValueError(
                "pass either an explicit config or size/overrides, not both")
        config = config or mixtral_config(size or "8x7b", **overrides)
        if config.num_experts <= 0:
            raise ValueError("Mixtral requires num_experts > 0")
        super().__init__(config)

    def init(self, rng: jax.Array):
        params = super().init(rng)
        c = self.config
        dt = c.param_dtype
        d, f, L, E = (c.hidden_size, c.intermediate_size, c.num_layers,
                      c.num_experts)
        std = 0.02
        resid_std = std / (2 * L) ** 0.5
        keys = jax.random.split(jax.random.fold_in(rng, 17), 4)
        layers = params["layers"]
        # replace dense FFN with routed experts + gate
        for name in ("w_up", "w_down", "w_gate", "w_up_b", "w_down_b",
                     "w_gate_b"):
            layers.pop(name, None)
        layers["router"] = _dense_init(keys[0], (L, d, E), std, dt)
        layers["experts"] = {
            "w_up": _dense_init(keys[1], (L, E, d, f), std, dt),
            "w_gate": _dense_init(keys[2], (L, E, d, f), std, dt),
            "w_down": _dense_init(keys[3], (L, E, f, d), resid_std, dt),
        }
        return params

    # set True by init_inference: decode batches route through the
    # sort-by-expert grouped GEMM (exact top-k, no capacity padding or
    # drops) instead of the training path's [N, E, C] capacity einsum
    # (reference: inference v2 moe_gemm/moe_gather/moe_scatter vs
    # training sharded_moe dispatch)
    moe_serving_dispatch = False

    # set by the training engine (runtime/engine.py, ISSUE 16): the
    # ep-sharded explicit dispatch/combine exchange, routing overrides
    # from the moe config block (None = this config's values), and the
    # router-telemetry opt-in. Class attrs so plain model use (tests,
    # serving) keeps the implicit einsum collectives.
    moe_dispatcher = None
    moe_capacity_factor = None
    moe_min_capacity = None
    moe_router_telemetry = False

    def _mlp(self, p, h):
        c = self.config
        from ..moe.sharded_moe import dequantize_experts
        experts = dequantize_experts(p["experts"], h.dtype)
        norm = c.moe_norm_topk
        if self.moe_serving_dispatch:
            from ..moe.sharded_moe import moe_ffn_grouped
            return moe_ffn_grouped(h, p["router"], experts,
                                   k=c.moe_top_k,
                                   activation=c.activation,
                                   normalize_topk=norm)
        hook = None
        if self.moe_router_telemetry:
            from ..moe.dispatch import publish_router_metrics
            hook = publish_router_metrics
        cf = self.moe_capacity_factor
        mc = self.moe_min_capacity
        return moe_ffn(
            h, p["router"], experts, k=c.moe_top_k,
            capacity_factor=c.capacity_factor if cf is None else cf,
            min_capacity=c.min_capacity if mc is None else mc,
            activation=c.activation, normalize_topk=norm,
            dispatcher=self.moe_dispatcher, metrics_hook=hook)

    def partition_rules(self):
        rules = [r for r in super().partition_rules()
                 if "w_up" not in r[0] and "w_down" not in r[0]
                 and "w_gate" not in r[0]]
        return rules + [
            (r"layers/router", P()),
            (r"layers/experts/(w_up|w_gate)$", P(None, "ep", None, "tp")),
            (r"layers/experts/w_down$", P(None, "ep", "tp", None)),
        ]

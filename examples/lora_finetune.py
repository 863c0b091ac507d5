"""LoRA finetune: frozen int8 base + trainable adapters + RLHF-style
generation through the hybrid engine.

Run:  python examples/lora_finetune.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import deepspeed_tpu as ds  # noqa: E402
from deepspeed_tpu.linear import (LoRAConfig, LoRAModel,  # noqa: E402
                                  QuantizationConfig)
from deepspeed_tpu.models import GPT2  # noqa: E402
from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402


def main():
    enable_compile_cache()
    model = LoRAModel(
        GPT2(size="tiny"),
        LoRAConfig(lora_r=8, lora_alpha=16, target_mods=[]),
        QuantizationConfig(q_bits=8),
        target_regex=r"layers/w[qkvo]$|layers/w_(up|down)$")
    print(f"adapters on {len(model.lora_state.adapters)} weights; "
          "base is frozen int8")

    engine, _, _, _ = ds.initialize(
        model=model,
        config={
            "train_batch_size": 16,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "mesh": {"fsdp": -1},
            "zero_optimization": {"stage": 2},
            "hybrid_engine": {"enabled": True, "max_out_tokens": 64},
            "steps_per_print": 5,
        })

    key = jax.random.PRNGKey(0)
    for _ in range(10):
        key, sub = jax.random.split(key)
        tokens = jax.random.randint(sub, (16, 65), 0, 512)
        engine.train_batch((tokens[:, :-1], tokens[:, 1:]))

    prompts = jnp.zeros((2, 8), jnp.int32)
    out = engine.generate(prompts, max_new_tokens=16, do_sample=True)
    print("generated:", out.shape, "mean latency",
          f"{engine.generate_latency():.3f}s")


if __name__ == "__main__":
    main()

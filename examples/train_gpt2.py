"""Train GPT-2 with ZeRO-3 + bf16 on whatever devices are visible.

Run:  python examples/train_gpt2.py  [--steps 50] [--size tiny|125m]
(On a CPU dev box: JAX_PLATFORMS=cpu
 XLA_FLAGS=--xla_force_host_platform_device_count=8)
The model and sequence length come from --size alone: the same program
runs on every backend.
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import deepspeed_tpu as ds  # noqa: E402
from deepspeed_tpu.models import GPT2  # noqa: E402
from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--size", default="tiny", choices=["tiny", "125m"])
    args = ap.parse_args()

    enable_compile_cache()
    print("devices:", jax.devices())
    model = GPT2(size=args.size)
    seq = model.config.max_seq_len      # tiny: 128, 125m: 1024
    batch = 16

    config = {
        "train_batch_size": batch,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "scheduler": {"type": "WarmupLR",
                      "params": {"warmup_num_steps": 10}},
        "gradient_clipping": 1.0,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3},
        "mesh": {"fsdp": -1},
        "steps_per_print": 5,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)

    key = jax.random.PRNGKey(0)
    for step in range(args.steps):
        key, sub = jax.random.split(key)
        tokens = jax.random.randint(sub, (batch, seq + 1), 0,
                                    model.config.vocab_size)
        engine.train_batch((tokens[:, :-1], tokens[:, 1:]))
    engine.save_checkpoint("/tmp/ds_tpu_gpt2_ckpt")
    print("done; checkpoint at /tmp/ds_tpu_gpt2_ckpt")


if __name__ == "__main__":
    main()

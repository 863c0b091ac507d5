"""Train a Llama-7B-parity model on ONE TPU chip (ZeRO-Infinity tier).

All 6.7B parameters' fp32 master + Adam moments live in the TPU host's
pinned memory (~48 GiB with bfloat16 moments); the compiled train step
streams one layer at a time through HBM (runtime/infinity.py). The
config below is exactly the reference's `offload_param`/`offload_optimizer`
JSON — the streamed engine is selected automatically on a single chip.

Throughput is PCIe-bound by design (the whole optimizer state crosses
the host link every step); this is the capability tier, not measured
on the chip by the benchmark (`ROADMAP.md` queue 1 item 9) — see
`save_16bit_model` for the bridge onto a sharded multi-chip run once a
pod is available.

Two knobs worth knowing:
- ``--ga N`` gradient accumulation: the master+moments stream is paid
  once per optimizer step, so MFU climbs with ga (measured on v5e:
  0.121 at ga=1 -> 0.308 at ga=16).
- ``--nvme DIR`` moves the fp32 master + Adam moments to DISK, paged
  per layer through the native AIO op into the C++ CPU Adam — model
  size becomes bounded by NVMe capacity instead of host RAM (run this
  ON the TPU host so the swap files are local).

Run: python examples/train_7b_one_chip.py [--layers N] (defaults to the
full 32-layer 7B config; pass --layers 4 for a quick functional check).
"""

import argparse
import sys

sys.path.insert(0, ".")

import jax
import numpy as np

import deepspeed_tpu as ds
from deepspeed_tpu.models import Llama
from deepspeed_tpu.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--ga", type=int, default=1,
                    help="gradient accumulation steps")
    ap.add_argument("--nvme", type=str, default=None,
                    help="swap dir: page master+moments from NVMe")
    args = ap.parse_args()

    model = Llama(hidden_size=4096, num_layers=args.layers, num_heads=32,
                  num_kv_heads=32, intermediate_size=11008,
                  vocab_size=32000, max_seq_len=args.seq,
                  remat_policy="segments", attn_impl="flash",
                  tie_embeddings=False)
    print(f"{model.config.num_params() / 1e9:.2f}B parameters")

    offload_opt = ({"device": "nvme", "nvme_path": args.nvme}
                   if args.nvme else
                   {"device": "cpu", "moment_dtype": "bfloat16"})
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_batch_size": args.batch * args.ga,
        "train_micro_batch_size_per_gpu": args.batch,
        "bf16": {"enabled": True},
        "optimizer": {"type": "FusedAdam",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "gradient_clipping": 1.0,
        "zero_optimization": {
            "stage": 3,
            "offload_param": {"device": "cpu"},
            "offload_optimizer": offload_opt,
        },
        "steps_per_print": 1,
    })
    rpt = engine.host_memory_report()
    print(f"host-resident optimizer tier: {rpt['pinned_host'] / 2**30:.1f}"
          f" GiB ({rpt['host_fraction']:.1%})")

    rng = np.random.default_rng(0)
    for step in range(args.steps):
        tokens = rng.integers(0, 32000,
                              (args.batch * args.ga, args.seq + 1))
        loss = engine.train_batch((tokens[:, :-1], tokens[:, 1:]))
        print(f"step {step}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()

"""Host-side >=1B NVMe-tier trajectory (VERDICT r4 #4).

Runs the streamed ZeRO-Infinity NVMe tier (runtime/infinity.py; reference
stage3.py:1926 optimizer-state swap + pipelined_optimizer_swapper.py) at
1B+ parameters with >90% of optimizer state paged from DISK, entirely on
the LOCAL host (JAX CPU backend): compute, pinned staging, and the AIO
swap files all live on one machine, so the disk traffic is real (the
step times are CPU-backend times, not device times).

Prints ONE JSON line:
  {"params_b": 1.03, "offloaded_fraction": 0.97, "steps": N,
   "losses": [...], "tokens_per_sec": ..., "nvme_read_gib_per_step": ...,
   "nvme_written_gib_per_step": ..., "nvme_state_gib": ..., ...}

Usage: python tools/nvme_1b_trajectory.py [n_steps] [--out artifact.json]
"""

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

# local CPU backend, one device, before jax import
flags = os.environ.get("XLA_FLAGS", "")
flags = " ".join(f for f in flags.split()
                 if "host_platform_device_count" not in f)
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=1").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np  # noqa: E402


def main() -> dict:
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import Llama
    from deepspeed_tpu.runtime.infinity import StreamedZeroEngine

    steps = int(sys.argv[1]) if len(sys.argv) > 1 and \
        not sys.argv[1].startswith("--") else 20
    if os.environ.get("DS_NVME_TRAJ_TINY"):   # CPU-smoke rigs
        model = Llama(size="tiny", max_seq_len=128, tie_embeddings=False)
        micro, seq = 2, 64
    else:
        # ~1.03B params; layer tier (master+moments -> disk) carries 97%
        model = Llama(hidden_size=1792, num_layers=26, num_heads=16,
                      num_kv_heads=16, intermediate_size=4800,
                      vocab_size=8192, max_seq_len=256,
                      tie_embeddings=False)
        micro, seq = 1, 128
    swap = os.environ.get("DS_NVME_TRAJ_DIR", "/tmp/ds_nvme_1b")
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_batch_size": micro,
        "bf16": {"enabled": True},
        "optimizer": {"type": "FusedAdam",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "gradient_clipping": 1.0,
        "zero_optimization": {
            "stage": 3,
            "offload_param": {"device": "cpu", "stream": True},
            "offload_optimizer": {"device": "nvme", "nvme_path": swap}},
        "steps_per_print": 10 ** 9})
    assert isinstance(engine, StreamedZeroEngine) and engine._nvme
    n_params = model.config.num_params()
    if not os.environ.get("DS_NVME_TRAJ_TINY"):
        assert n_params >= 1.0e9, n_params

    # fixed batch -> memorization: the loss must strictly fall, proving
    # the disk-paged Adam actually updates a coherent 1B state
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, model.config.vocab_size, (micro, seq + 1))
    data = (tokens[:, :-1], tokens[:, 1:])

    losses = []
    t_compile = time.perf_counter()
    losses.append(float(engine.train_batch(data)))   # compile + step 1
    compile_s = time.perf_counter() - t_compile
    rpt = engine.host_memory_report()
    t0 = time.perf_counter()
    for _ in range(steps - 1):
        losses.append(float(engine.train_batch(data)))
    dt = (time.perf_counter() - t0) / max(steps - 1, 1)
    io = engine._last_nvme_io
    out = {
        "params_b": round(n_params / 1e9, 3),
        "offloaded_fraction": round(rpt["offloaded_fraction"], 3),
        "nvme_state_gib": round(rpt["nvme"] / 2 ** 30, 2),
        "host_state_gib": round(rpt["pinned_host"] / 2 ** 30, 2),
        "nvme_read_gib_per_step": round(io["read"] / 2 ** 30, 2),
        "nvme_written_gib_per_step": round(io["written"] / 2 ** 30, 2),
        "steps": steps,
        "losses": [round(l, 4) for l in losses],
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        # decisively decreasing: every loss in the last quarter of the
        # run sits below every loss in the first quarter (robust to the
        # small bounces of early Adam steps and near-zero noise)
        "decreasing": bool(max(losses[-max(len(losses) // 4, 1):])
                           < min(losses[:max(len(losses) // 4, 1)])),
        "step_s": round(dt, 2),
        "tokens_per_sec": round(micro * seq / dt, 1),
        "compile_plus_first_step_s": round(compile_s, 1),
        "platform": "local host (cpu backend + local NVMe)",
    }
    engine.close()
    return out


if __name__ == "__main__":
    res = main()
    line = json.dumps(res)
    print(line)
    if "--out" in sys.argv:
        Path(sys.argv[sys.argv.index("--out") + 1]).write_text(line + "\n")

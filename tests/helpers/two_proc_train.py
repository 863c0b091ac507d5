"""Worker for test_launcher_multiprocess: launched (2 processes x 4 CPU
devices) by deepspeed_tpu.launcher.launch, which has already done the
jax.distributed rendezvous before this script runs. Trains 3 ZeRO-2
steps on a fixed batch and writes {rank, world, global_devices, losses}
as JSON to the path in argv[1]."""

import json
import os
import sys

import jax

# before any backend is instantiated: tests must stay off the real chip
# even when this worker is started by hand without JAX_PLATFORMS
jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

import deepspeed_tpu as ds  # noqa: E402
from deepspeed_tpu.models import GPT2  # noqa: E402


def main():
    out_path = sys.argv[1]
    engine, _, _, _ = ds.initialize(model=GPT2(size="tiny"), config={
        "train_batch_size": 16,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "gradient_clipping": 1.0,
        "mesh": {"fsdp": -1},
        "steps_per_print": 10 ** 9,
    })
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(0), (16, 17), 0, 512))
    batch = (tokens[:, :-1], tokens[:, 1:])
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    with open(out_path, "w") as f:
        json.dump({"rank": jax.process_index(),
                   "world": jax.process_count(),
                   "global_devices": jax.device_count(),
                   "losses": losses}, f)


main()

"""Dev runner for bench.serve7b_int8 on the real chip."""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import jax
import bench  # noqa: E402
import deepspeed_tpu as ds  # noqa: E402
from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

print("devices:", jax.devices())
res = bench.serve7b_int8(ds, on_tpu=jax.devices()[0].platform != "cpu")
print(json.dumps(res))

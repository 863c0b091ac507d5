import sys
from pathlib import Path
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import time
import numpy as np
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)
import deepspeed_tpu as ds
from deepspeed_tpu.models import Llama
from deepspeed_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()

ga = int(sys.argv[1]) if len(sys.argv) > 1 else 8
stream_dtype = sys.argv[2] if len(sys.argv) > 2 else "master"
micro = int(sys.argv[3]) if len(sys.argv) > 3 else 8
loss_chunk = int(sys.argv[4]) if len(sys.argv) > 4 else 0
seq = 2048
batch = micro * ga
model = Llama(hidden_size=4096, num_layers=32, num_heads=32,
              num_kv_heads=32, intermediate_size=11008,
              vocab_size=32000, max_seq_len=2048,
              remat_policy="segments", attn_impl="flash",
              loss_chunk=loss_chunk, tie_embeddings=False)
engine, _, _, _ = ds.initialize(model=model, config={
    "train_batch_size": batch,
    "train_micro_batch_size_per_gpu": micro,
    "bf16": {"enabled": True},
    "optimizer": {"type": "FusedAdam",
                  "params": {"lr": 1e-4, "weight_decay": 0.01}},
    "gradient_clipping": 1.0,
    "zero_optimization": {
        "stage": 3,
        "offload_param": {"device": "cpu",
                          "stream_dtype": stream_dtype},
        "offload_optimizer": {"device": "cpu",
                              "moment_dtype": "bfloat16"}},
    "steps_per_print": 10 ** 9})
rpt = engine.host_memory_report()
print("host GiB", round(rpt["pinned_host"]/2**30,1), "frac", round(rpt["host_fraction"],3))
tokens = jax.random.randint(jax.random.PRNGKey(0), (batch, seq + 1), 0, 32000)
data = (tokens[:, :-1], tokens[:, 1:])
loss = float(engine.train_batch(data))
t0 = time.perf_counter()
loss = float(engine.train_batch(data))
dt = time.perf_counter() - t0
tps = batch * seq / dt
mfu = tps * model.config.flops_per_token(seq) / 197e12
print("ga", ga, "stream", stream_dtype, "micro", micro, "step_s", round(dt,2), "tps", round(tps,1), "mfu", round(mfu,4), "loss", round(loss,4))

"""int8-expert MoE decode vs dense at batch 16/64 (routing-overhead
floor sweep) on the real chip. Run from the repo root."""
import sys
from pathlib import Path
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import time
import numpy as np
import jax, jax.numpy as jnp
import deepspeed_tpu as ds
from deepspeed_tpu.models import Llama, Mixtral
from deepspeed_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()

def decode_tps(model, B, P=128, N=64, **kw):
    e = ds.init_inference(model, dtype="bfloat16", max_out_tokens=512, **kw)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, 32000, size=(B, P)))
    np.asarray(e.generate(prompts, max_new_tokens=N))
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        out = e.generate(prompts, max_new_tokens=N)
    np.asarray(out)
    return B * N / ((time.perf_counter() - t0) / reps)

kw = dict(hidden_size=1024, num_layers=12, num_heads=8, num_kv_heads=8,
          intermediate_size=2816, vocab_size=32000, max_seq_len=2048)
for B in (16, 64):
    moe = Mixtral(num_experts=8, moe_top_k=2, **kw)
    dense = Llama(**kw)
    mq = decode_tps(moe, B, quantize_moe_experts=True)
    mb = decode_tps(moe, B)
    d = decode_tps(dense, B)
    print(f"B={B} moe_int8 {round(mq,1)} moe_bf16 {round(mb,1)} "
          f"dense {round(d,1)} ratio_int8 {round(d/mq,2)} "
          f"ratio_bf16 {round(d/mb,2)}")

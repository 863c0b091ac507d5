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

moe = Mixtral(hidden_size=1024, num_layers=12, num_heads=8, num_kv_heads=8,
              intermediate_size=2816, num_experts=8, moe_top_k=2,
              vocab_size=32000, max_seq_len=2048)
dense = Llama(hidden_size=1024, num_layers=12, num_heads=8, num_kv_heads=8,
              intermediate_size=2816, vocab_size=32000, max_seq_len=2048)
B, P, N = 16, 128, 64
rng = np.random.default_rng(0)
prompts = jnp.asarray(rng.integers(0, 32000, size=(B, P)))

def decode_tps(model, grouped=None):
    # the engine binds the dispatch mode at construction (per-engine
    # model copy): pass it through the config, never set it post-hoc
    e = ds.init_inference(model, dtype="bfloat16", max_out_tokens=512,
                          moe_grouped_dispatch=bool(grouped))
    np.asarray(e.generate(prompts, max_new_tokens=N))
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        out = e.generate(prompts, max_new_tokens=N)
    np.asarray(out)
    return B * N / ((time.perf_counter() - t0) / reps)

m_grp = decode_tps(moe, grouped=True)        # opt-in grouped dispatch
m_ein = decode_tps(moe)                      # einsum path (default)
d = decode_tps(dense)
print("moe grouped tps", round(m_grp,1), "moe einsum tps", round(m_ein,1),
      "dense tps", round(d,1))
print("overhead grouped", round(d/m_grp,2), "einsum", round(d/m_ein,2))

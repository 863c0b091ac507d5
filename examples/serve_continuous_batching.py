"""FastGen-style serving: paged KV cache + continuous batching.

Run:  python examples/serve_continuous_batching.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402
from deepspeed_tpu.inference.v2 import build_engine  # noqa: E402
from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402


def main():
    enable_compile_cache()
    engine = build_engine("llama", size="tiny",
                          engine_config={"num_kv_blocks": 128,
                                         "kv_block_size": 64,
                                         "max_chunk_size": 128})
    rng = np.random.default_rng(0)

    # admit three requests with different prompt lengths (ragged batch)
    uids = [101, 102, 103]
    prompts = [rng.integers(0, 500, size=n).tolist() for n in (17, 64, 3)]
    logits = engine.put(uids, prompts)
    print("prefill logits:", logits.shape)

    # continuous batching: greedy-decode all three for 16 ticks
    tokens = {u: [] for u in uids}
    nxt = {u: int(np.argmax(np.asarray(logits[i])))
           for i, u in enumerate(uids)}
    for _ in range(16):
        logits = engine.put(uids, [[nxt[u]] for u in uids])
        for i, u in enumerate(uids):
            nxt[u] = int(np.argmax(np.asarray(logits[i])))
            tokens[u].append(nxt[u])

    for u in uids:
        cached, blocks = engine.query(u)
        print(f"seq {u}: {cached} tokens in {blocks} KV blocks; "
              f"generated {tokens[u][:8]}...")
    engine.flush(uids)
    print("flushed; free blocks:", engine.state_manager.allocator.free_blocks)


if __name__ == "__main__":
    main()

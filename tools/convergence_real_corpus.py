"""Real-corpus convergence vs an independent implementation
(VERDICT r4 #8; reference: tests/model/ convergence suites, SURVEY §4).

Trains a GPT-2-architecture byte-level LM on a REAL public text corpus
(the reference project's markdown docs/blogs, ~1.5 MB of prose, routed
through runtime/data_pipeline's MMapIndexedDataset) twice, at IDENTICAL
hyperparameters and identical batch order:

  1. through deepspeed_tpu.initialize (ZeRO stage 1 engine), and
  2. through an INDEPENDENT from-scratch flax.linen + optax
     implementation written here (no deepspeed_tpu model/engine code),

then writes both loss curves to an artifact. Agreement of the curves is
the parity evidence the synthetic induction-head suite cannot give:
any engine-side numerics bug (loss scaling, grad averaging, optimizer
wiring, data path) shows up as curve divergence against the
independent implementation.

Model is the GPT-2 block architecture (learned positions, pre-LN,
GELU, biases) scaled to the harness's single CPU core; byte-level
vocab avoids any tokenizer download (zero-egress rig).

Usage: python tools/convergence_real_corpus.py [steps] [--tiny]
       [--out artifact.json]
"""

import glob
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

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

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

CORPUS_GLOB = "/root/reference/**/*.md"
SEQ, BATCH, LR = 256, 8, 3e-4


# ---------------------------------------------------------------------
def build_corpus(tmpdir: str) -> np.ndarray:
    """Real text -> MMapIndexedDataset (one doc per file) -> flat byte
    stream (exercises the data-pipeline indexed format end to end)."""
    from deepspeed_tpu.runtime.data_pipeline.data_sampling.indexed_dataset \
        import MMapIndexedDataset, MMapIndexedDatasetBuilder

    files = sorted(glob.glob(CORPUS_GLOB, recursive=True))
    assert files, "no corpus files found"
    prefix = os.path.join(tmpdir, "corpus")
    b = MMapIndexedDatasetBuilder(prefix, dtype=np.int32)
    for f in files:
        data = np.frombuffer(Path(f).read_bytes(), np.uint8)
        if len(data) > 32:
            b.add_item(data.astype(np.int32))
    b.finalize()
    ds = MMapIndexedDataset(prefix)
    stream = np.concatenate([np.asarray(ds[i]) for i in range(len(ds))])
    return stream.astype(np.int32)


def batches(stream: np.ndarray, steps: int, seq: int, batch: int):
    """Deterministic batch schedule shared by both implementations."""
    rng = np.random.default_rng(1234)
    hi = len(stream) - seq - 1
    for _ in range(steps):
        starts = rng.integers(0, hi, batch)
        tok = np.stack([stream[s:s + seq + 1] for s in starts])
        yield tok[:, :-1], tok[:, 1:]


def warmup_steps(steps: int) -> int:
    return min(100, max(steps // 5, 1))


# ---------------------------------------------------------------------
# independent implementation: flax.linen + optax, written from scratch
def independent_run(stream, steps, cfg) -> list:
    import flax.linen as nn
    import optax

    V, D, L, H, S = (cfg["vocab"], cfg["d"], cfg["layers"], cfg["heads"],
                     cfg["seq"])

    # GPT-2's init is part of the hyperparameters: normal(0.02)
    # everywhere, residual projections scaled by 1/sqrt(2L)
    init = nn.initializers.normal(0.02)
    resid_init = nn.initializers.normal(0.02 / np.sqrt(2 * L))

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.LayerNorm(epsilon=1e-5)(x)
            B, T, _ = h.shape
            qkv = nn.Dense(3 * D, kernel_init=init)(h)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B, T, H, D // H)
            k = k.reshape(B, T, H, D // H)
            v = v.reshape(B, T, H, D // H)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D // H)
            mask = np.tril(np.ones((T, T), bool))
            s = jnp.where(mask, s, -1e30)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            x = x + nn.Dense(D, kernel_init=resid_init)(
                a.reshape(B, T, D))
            h2 = nn.LayerNorm(epsilon=1e-5)(x)
            m = nn.Dense(4 * D, kernel_init=init)(h2)
            m = nn.Dense(D, kernel_init=resid_init)(
                nn.gelu(m, approximate=True))
            return x + m

    class LM(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            x = nn.Embed(V, D, embedding_init=init)(tokens)
            x = x + self.param(
                "wpe", nn.initializers.normal(0.02), (S, D))[None]
            for _ in range(L):
                x = Block()(x)
            x = nn.LayerNorm(epsilon=1e-5)(x)
            # tied head (GPT-2)
            wte = self.variables["params"]["Embed_0"]["embedding"]
            return x @ wte.T

    model = LM()
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, S), jnp.int32))

    sched = optax.warmup_cosine_decay_schedule(
        0.0, LR, warmup_steps(steps), steps)
    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     optax.adamw(sched, b1=0.9, b2=0.999,
                                 weight_decay=0.01))
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, tok, tgt):
        def loss_fn(p):
            logits = model.apply(p, tok)
            ls = jax.nn.log_softmax(logits.astype(jnp.float32))
            nll = -jnp.take_along_axis(ls, tgt[..., None], -1)
            return jnp.mean(nll)
        loss, g = jax.value_and_grad(loss_fn)(params)
        upd, opt_state = tx.update(g, opt_state, params)
        return optax.apply_updates(params, upd), opt_state, loss

    losses = []
    for tok, tgt in batches(stream, steps, S, BATCH):
        params, opt_state, loss = step(params, opt_state,
                                       jnp.asarray(tok), jnp.asarray(tgt))
        losses.append(float(loss))
    return losses


# ---------------------------------------------------------------------
def engine_run(stream, steps, cfg) -> list:
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2

    model = GPT2(vocab_size=cfg["vocab"], hidden_size=cfg["d"],
                 num_layers=cfg["layers"], num_heads=cfg["heads"],
                 max_seq_len=cfg["seq"],
                 intermediate_size=4 * cfg["d"])
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_batch_size": BATCH,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": LR, "betas": (0.9, 0.999),
                                 "weight_decay": 0.01}},
        "scheduler": {"type": "WarmupCosineLR",
                      "params": {"warmup_num_steps": warmup_steps(steps),
                                 "total_num_steps": steps,
                                 "warmup_min_ratio": 0.0}},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 1},
        "steps_per_print": 10 ** 9})
    losses = []
    for tok, tgt in batches(stream, steps, cfg["seq"], BATCH):
        losses.append(float(engine.train_batch(
            {"tokens": tok, "targets": tgt})))
    return losses


def main():
    argv = sys.argv[1:]
    args = [a for i, a in enumerate(argv)
            if not a.startswith("--")
            and (i == 0 or argv[i - 1] != "--out")]
    steps = int(args[0]) if args else 2000
    tiny = "--tiny" in sys.argv
    cfg = (dict(vocab=256, d=128, layers=2, heads=4, seq=SEQ) if tiny
           else dict(vocab=256, d=256, layers=4, heads=8, seq=SEQ))
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        stream = build_corpus(td)
    t0 = time.time()
    ours = engine_run(stream, steps, cfg)
    t1 = time.time()
    ref = independent_run(stream, steps, cfg)
    t2 = time.time()
    k = max(steps // 10, 1)
    out = {
        "corpus_bytes": int(len(stream)),
        "corpus": "reference project markdown docs/blogs (public text)",
        "config": cfg, "steps": steps, "batch": BATCH, "lr": LR,
        "warmup": warmup_steps(steps),
        "every": 10,
        "engine_losses": [round(l, 4) for l in ours[::10]],
        "flax_losses": [round(l, 4) for l in ref[::10]],
        "engine_final": round(float(np.mean(ours[-k:])), 4),
        "flax_final": round(float(np.mean(ref[-k:])), 4),
        "final_ratio": round(float(np.mean(ours[-k:]))
                             / float(np.mean(ref[-k:])), 4),
        "engine_seconds": round(t1 - t0, 1),
        "flax_seconds": round(t2 - t1, 1),
    }
    line = json.dumps(out)
    print(line)
    if "--out" in sys.argv:
        Path(sys.argv[sys.argv.index("--out") + 1]).write_text(line + "\n")


if __name__ == "__main__":
    main()

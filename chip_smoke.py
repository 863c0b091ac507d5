#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that deepspeed_tpu still starts on a TPU.

One process, phases in sequence, each engine freed before the next is
built; any failed check or exception in any phase exits non-zero:

1. device gate  — a TPU whose ``device_kind`` has a published peak, or exit;
2. train        — ``ds.initialize`` (bf16, ZeRO-3, AdamW, clipping,
                  ``mesh: {"fsdp": -1}``) + ``engine.train_batch`` x5 at
                  sequence 8192 with the flash kernel and segment remat;
3. serve        — ``build_engine("mistral", "7b")`` with the DEFAULT
                  ``RaggedInferenceEngineConfig`` behind
                  ``AsyncInferenceServer``: six concurrent requests, one
                  prompt past the 4096 window;
4. agreement    — the flash and paged-attention Pallas kernels, compiled
                  by Mosaic, against their jnp references on the device.

The model is the ``mistral`` ``7b`` preset at its published widths
(hidden 4096, 32 q / 8 kv heads, head_dim 128, FFN 14336, vocab 32000,
window 4096, RoPE, untied embeddings; source: the preset in
``deepspeed_tpu/models/mistral.py``). It is cut by DEPTH only, weights
are random from a seed, nothing is loaded and nothing needs a network.

There is no CPU mode and no switch. The last line of stdout is one JSON
object ``{"ok": true, "device": {...}}``; timings printed on the way are
information, not claims (``PERF.md``).

    chiprun --timeout 1500 -- python chip_smoke.py
    chiprun --chips 4 --timeout 1500 -- python chip_smoke.py
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import gc
import importlib.metadata
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

import deepspeed_tpu as ds
from deepspeed_tpu import telemetry
from deepspeed_tpu.accelerator.tpu_accelerator import TPU_Accelerator
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.engine_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.paged import paged_forward
from deepspeed_tpu.models.mistral import Mistral, mistral_config
from deepspeed_tpu.ops.layers import dot_product_attention, window_bias
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.serving import AsyncInferenceServer, ServingConfig
from deepspeed_tpu.telemetry.bridges import compile_event_count
from deepspeed_tpu.utils.compile_cache import enable_compile_cache

# ---- depth: the only cut from the published 32-layer model ---------------
# Train: ZeRO-3 state is 14 B/param (bf16 params + f32 master + two f32 Adam
# moments) plus f32 grads in the step. Embeddings (262M params) and two
# layers (218M each) give 9.1 GiB of state and a 13.7 GiB compiled peak at
# sequence 8192 with the chunked cross-entropy (AOT memory analysis for v5e,
# PR 21) against one chip's 15.75 GiB; three layers do not fit.
TRAIN_LAYERS = 2
# Serve: the engine initialises in f32 and casts to bf16, so the peak is
# 6 B/param at start-up: a quarter of the model (8 layers + embeddings,
# 2.0G params) peaks at 12 GiB and then holds 4 GiB beside the KV pool.
SERVE_LAYERS = 8
# Agreement runs paged_forward on a model of its own (kernel vs gather path)
AGREE_LAYERS = 2

MOSAIC = "tpu_custom_call"   # custom_call_target of a Mosaic-compiled kernel


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a run is sized by. ``FULL`` is what ``main`` runs on the chip;
    tests/test_chip_smoke.py drives the same phases at a tiny size on the
    CPU (kernels interpreted: control flow and the leak check only)."""
    size: str = "7b"
    overrides: dict = dataclasses.field(default_factory=dict)  # tests only
    train_layers: int = TRAIN_LAYERS
    serve_layers: int = SERVE_LAYERS
    agree_layers: int = AGREE_LAYERS
    seq: int = 8192                     # 2x the window: the mask matters
    loss_chunk: int = 1024              # never materialise [S, V] logits
    train_steps: int = 5
    # ~24, ~300, ~1500 and one past the window; 32 new tokens each
    prompts: tuple = (24, 300, 1500, 4300, 310, 30)
    new_tokens: int = 32
    # a leaf this large must be sharded over every device (four chips)
    large_leaf: int = 1 << 20


FULL = Sizes()


class SmokeFailure(AssertionError):
    """A check the smoke makes did not hold."""


def check(cond, message: str) -> None:
    # not `assert`: that is compiled out under -O
    if not cond:
        raise SmokeFailure(message)


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def hbm_in_use() -> list[int] | None:
    """Per-device ``bytes_in_use``; None where the backend reports none
    (the CPU backend of the test rig)."""
    stats = [d.memory_stats() for d in jax.devices()]
    if not all(stats):
        return None
    return [int(s["bytes_in_use"]) for s in stats]


def ledger_entries(name: str) -> list:
    led = telemetry.get_ledger()
    check(led is not None, "the executable ledger is off; call "
          "telemetry.configure(executable_ledger=True) first")
    entries = [e for e in led.entries() if e.name == name]
    check(entries, f"no executable named {name!r} reached the ledger")
    for e in entries:
        check(not e.register_error,
              f"ledger could not analyse {name}: {e.register_error}")
    return entries


def check_mosaic(entries: list, what: str, at_least: int = 1) -> int:
    """Every entry holds >= ``at_least`` Mosaic custom calls. Live on a
    TPU only: in interpret mode (the CPU rig) a kernel is plain HLO."""
    n = min(e.custom_calls.get(MOSAIC, 0) for e in entries)
    if on_tpu():
        check(n >= at_least,
              f"{what}: {n} Mosaic custom call(s) in the compiled HLO, "
              f"expected >= {at_least} — the kernel was interpreted or "
              f"replaced by a fallback")
    return n


# --------------------------------------------------------------------------
def device_gate(cache_dir: str) -> dict:
    """Exit before any work unless JAX reports a TPU whose device_kind
    has a published peak. Returns the device block of the final JSON."""
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX reports platform "
            f"{d.platform!r} ({d.device_kind}). There is no CPU mode.")
    peak = TPU_Accelerator().peak_flops()    # raises on an unknown kind
    print(f"device: platform={d.platform} kind={d.device_kind!r} "
          f"count={len(devices)} default_backend={jax.default_backend()} "
          f"peak_bf16={peak:.3g} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"cache_dir={cache_dir} "
          f"cache_entries_at_start={_cache_entries(cache_dir)}",
          flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def _cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


# --------------------------------------------------------------------------
def train_phase(sz: Sizes) -> dict:
    t0 = time.perf_counter()
    n_dev = len(jax.devices())
    model = Mistral(size=sz.size, num_layers=sz.train_layers,
                    attn_impl="flash", remat_policy="segments",
                    loss_chunk=sz.loss_chunk, **sz.overrides)
    batch_size = n_dev                  # one sequence per device
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_batch_size": batch_size,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3},
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "gradient_clipping": 1.0,
        "mesh": {"fsdp": -1},
        "steps_per_print": 10 ** 9,
    })
    check(engine.topology.sizes["fsdp"] == n_dev,
          f"fsdp: -1 resolved to {engine.topology.sizes['fsdp']}, "
          f"not the {n_dev} devices")
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(0), (batch_size, sz.seq + 1), 0,
        model.config.vocab_size))
    batch = (tokens[:, :-1], tokens[:, 1:])

    losses, step_s = [], []
    for i in range(sz.train_steps):
        t = time.perf_counter()
        loss = engine.train_batch(batch)
        loss.block_until_ready()
        step_s.append(time.perf_counter() - t)
        losses.append(float(loss))
        if i == 0:
            compiles_after_first = compile_event_count()
    setup_s = time.perf_counter() - t0 - sum(step_s[1:])
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall over {sz.train_steps} steps: {losses}")
    check(compile_event_count() == compiles_after_first,
          f"steps after the first compiled: backend_compile count went "
          f"{compiles_after_first} -> {compile_event_count()}")

    step = ledger_entries("compiled_step")
    check(len(step) == 1, f"{len(step)} train-step executables, not 1")
    # forward kernel + the one-pass backward kernel
    n_mosaic = check_mosaic(step, "train step", at_least=2)
    hbm = hbm_in_use()
    if n_dev > 1:
        _check_sharded(engine, step[0], n_dev, sz.large_leaf, hbm)
    out = {"layers": sz.train_layers, "devices": n_dev, "seq": sz.seq,
           "batch": batch_size, "losses": [round(x, 4) for x in losses],
           "setup_s": round(setup_s, 1),
           "steady_step_s": round(float(np.median(step_s[1:])), 3),
           "mosaic_calls": n_mosaic,
           "compiled_peak_gib": round(step[0].peak_hbm_bytes / 2 ** 30, 2),
           "hbm_in_use_gib": (None if hbm is None else
                              [round(b / 2 ** 30, 2) for b in hbm])}
    del engine, loss
    return out


def _check_sharded(engine, step, n_dev: int, large_leaf: int,
                   hbm: list[int] | None) -> None:
    """ZeRO-3 over ``n_dev`` chips: state split, collectives present,
    memory spread."""
    state = {k: engine.state[k] for k in ("params", "master", "opt_state")}
    large = [x for x in jax.tree.leaves(state) if x.size >= large_leaf]
    check(large, "no large state leaf to check")
    for x in large:
        shards = x.addressable_shards
        check(len({s.device for s in shards}) == n_dev
              and len({str(s.index) for s in shards}) == n_dev,
              f"leaf {x.shape} {x.dtype}: not {n_dev} distinct shards")
        check(all(s.data.size * n_dev == x.size for s in shards),
              f"leaf {x.shape}: a shard is not 1/{n_dev} of the array")
    ops = {c["op"] for c in step.collectives}
    # the CPU backend of the test rig leaves a reduce-scatter as
    # all-reduce + slice; the TPU compiler forms it
    need = {"all_gather", "reduce_scatter" if on_tpu() else "all_reduce"}
    check(need <= ops,
          f"compiled step has collectives {sorted(ops)}: ZeRO-3 needs "
          f"{sorted(need)}")
    if hbm is not None:
        check(max(hbm) < 4 * min(hbm),
              f"device memory is not spread: bytes_in_use {hbm}")


# --------------------------------------------------------------------------
def serve_phase(sz: Sizes) -> dict:
    t0 = time.perf_counter()
    engine = build_engine("mistral", size=sz.size,
                          num_layers=sz.serve_layers, **sz.overrides)
    build_s = time.perf_counter() - t0
    vocab = engine.model.config.vocab_size
    chunk_cap = RaggedInferenceEngineConfig().max_chunk_size
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, n).tolist() for n in sz.prompts]
    check(max(sz.prompts) >= chunk_cap,
          "no prompt reaches the largest prefill bucket")

    async def wave(server):
        t = time.perf_counter()
        handles = [await server.submit(p, max_new_tokens=sz.new_tokens)
                   for p in prompts]
        outs = await asyncio.gather(*(h.tokens() for h in handles))
        return outs, time.perf_counter() - t

    async def run():
        async with AsyncInferenceServer(engine, ServingConfig(
                default_max_new_tokens=sz.new_tokens)) as server:
            cold, cold_s = await wave(server)   # compiles every bucket
            after_cold = server.metrics()
            warm, warm_s = await wave(server)   # same buckets, compiled
            return cold, cold_s, warm, warm_s, after_cold, server.metrics()

    cold, cold_s, warm, warm_s, after_cold, metrics = asyncio.run(run())
    for outs in (cold, warm):
        for n, toks in zip(sz.prompts, outs):
            check(len(toks) == sz.new_tokens,
                  f"prompt of {n}: streamed {len(toks)} tokens, asked "
                  f"for {sz.new_tokens}")
            check(all(0 <= t < vocab for t in toks),
                  f"prompt of {n}: token outside the vocabulary")

    # prompts >= max_chunk_size went through the largest prefill bucket
    prefill = ledger_entries("v2/dispatch")
    by_chunk: collections.Counter = collections.Counter()
    for e in prefill:
        # operands end (tokens [B, S], pos0, block_tables, true_len)
        by_chunk[e.signature[-4][0][1]] += e.calls
    check(max(by_chunk) == chunk_cap,
          f"largest prefill bucket seen {max(by_chunk)}, default "
          f"max_chunk_size {chunk_cap}")
    want = 2 * (max(sz.prompts) // chunk_cap)
    check(by_chunk[chunk_cap] >= want,
          f"{by_chunk[chunk_cap]} dispatches at the {chunk_cap}-token "
          f"bucket; two waves of a {max(sz.prompts)}-token prompt need "
          f">= {want}")
    n_prefill = check_mosaic(prefill, "prefill executables")
    n_decode = check_mosaic(ledger_entries("v2/fused_dispatch"),
                            "decode executable")

    # nothing leaked after the drain
    check(metrics["open_requests"] == 0,
          f"{metrics['open_requests']} requests still open")
    mgr = engine.state_manager
    check(not mgr.seqs, f"sequences left behind: {sorted(mgr.seqs)}")
    check(engine.free_blocks == engine.num_kv_blocks
          and mgr.allocator.free_blocks == engine.num_kv_blocks,
          f"leaked KV blocks: {engine.free_blocks} schedulable, "
          f"{mgr.allocator.free_blocks} free of {engine.num_kv_blocks}")
    holders = sorted({str(d) for x in jax.tree.leaves(
        (engine.params, engine.pools)) for d in x.devices()})
    warm_dispatches = (metrics["host_dispatches"]
                       - after_cold["host_dispatches"])
    out = {"layers": sz.serve_layers, "requests": len(prompts),
           "prompts": list(sz.prompts), "new_tokens": sz.new_tokens,
           "params_and_pools_on": holders,
           "setup_s": round(build_s + cold_s, 1),
           "steady_wave_s": round(warm_s, 3),
           "prefill_dispatches_by_chunk": dict(sorted(by_chunk.items())),
           "mosaic_calls": {"prefill": n_prefill, "decode": n_decode},
           # wall per host dispatch of the warm wave, prefill chunks and
           # fused K-step decode dispatches together
           "steady_wave_dispatches": warm_dispatches,
           "steady_tick_s": round(warm_s / max(warm_dispatches, 1), 4)}
    del engine
    return out


# --------------------------------------------------------------------------
# Tolerances for kernel-vs-reference agreement, set from the dtype before
# any chip run. The kernels feed the MXU bf16 operands (p and ds are cast
# to bf16 before their dots) and store bf16 results, so each output carries
# a few bf16 roundings — bf16 has 8 significand bits, eps = 2^-8 = 3.9e-3.
# Both errors are relative to the reference's own scale:
#   max = max|a - ref| / max|ref|,  rms = rms(a - ref) / rms(ref).
# KERNEL: one attention call. Interpret mode on the CPU measured 3-5e-3
# (max) and 2-3e-3 (rms) at these head shapes; the bound is ~5 eps / ~2.5
# eps. MODEL: logits after two whole bf16 layers run as two separately
# compiled programs, where every matmul output is rounded to bf16 again;
# interpret mode at the full widths measured 1.1e-2 for both, the bound is
# three times that. A wrong mask, a dropped page or a missing window gives
# an error of order 1, and an fp8 path (eps >= 6e-2 per element) exceeds
# the kernel bound; ordinary bf16 rounding does not.
TOL_KERNEL = (2e-2, 1e-2)       # (max, rms)
TOL_MODEL = (4e-2, 3e-2)


def _errors(got, ref) -> tuple[float, float]:
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    if not bool(jnp.all(jnp.isfinite(got))):
        return float("inf"), float("inf")
    return (float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref))),
            float(jnp.sqrt(jnp.mean((got - ref) ** 2))
                  / jnp.sqrt(jnp.mean(ref ** 2))))


def agreement_phase(sz: Sizes) -> dict:
    """Every comparison is made and printed before any is judged, so one
    run shows all the errors."""
    t0 = time.perf_counter()
    cfg = mistral_config(sz.size, **sz.overrides)
    errors = {**_flash_agreement(cfg, sz.seq), **_paged_agreement(sz, cfg)}
    out = {name: {"max": float(f"{e[0]:.2e}"), "rms": float(f"{e[1]:.2e}")}
           for name, (e, _) in errors.items()}
    out["seconds"] = round(time.perf_counter() - t0, 1)
    print("agreement errors:", json.dumps(out), flush=True)
    bad = {name: e for name, (e, tol) in errors.items()
           if not (e[0] <= tol[0] and e[1] <= tol[1])}
    check(not bad, f"kernel and reference disagree (max, rms): {bad}; "
          f"tolerances kernel {TOL_KERNEL}, model {TOL_MODEL}")
    return out


def _flash_agreement(cfg, seq: int) -> dict:
    """flash fwd+bwd at the train phase's attention shape [1, seq, 32, 128]
    (8 kv heads) vs ``dot_product_attention`` in f32 at "highest". The
    reference's [S, S] scores do not fit for 32 heads at once, so it runs
    one GQA group (4 q heads, 1 kv head) at a time — attention is
    independent per head, so this is the same computation."""
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rep = hq // hkv
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (1, seq, hq, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, seq, hkv, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, seq, hkv, d), jnp.bfloat16)
    do = jax.random.normal(ks[3], (1, seq, hq, d), jnp.bfloat16)
    result = {}
    for label, window in (("causal", None),
                          ("window", cfg.sliding_window)):
        @jax.jit
        def flash(q, k, v, do):
            o, vjp = jax.vjp(lambda q, k, v: flash_attention(
                q, k, v, causal=True, window=window), q, k, v)
            return (o, *vjp(do))

        @jax.jit
        def reference(q, k, v, do):
            f32 = lambda x: x.astype(jnp.float32)   # noqa: E731
            bias = window_bias(seq, window) if window else None
            o, vjp = jax.vjp(lambda q, k, v: dot_product_attention(
                q, k, v, causal=True, bias=bias), f32(q), f32(k), f32(v))
            return (o, *vjp(f32(do)))

        exe = flash.lower(q, k, v, do).compile()
        if on_tpu():
            check(exe.as_text().count(MOSAIC) >= 2,
                  f"flash {label}: kernels not compiled by Mosaic")
        got = exe(q, k, v, do)
        groups = []
        with jax.default_matmul_precision("highest"):
            for g in range(hkv):
                qs = slice(g * rep, (g + 1) * rep)
                groups.append(reference(q[:, :, qs], k[:, :, g:g + 1],
                                        v[:, :, g:g + 1], do[:, :, qs]))
        ref = [jnp.concatenate(parts, axis=2) for parts in zip(*groups)]
        for n, a, b in zip(("o", "dq", "dk", "dv"), got, ref):
            result[f"flash/{label}/{n}"] = (_errors(a, b), TOL_KERNEL)
        del got, ref, groups
    return result


def _paged_agreement(sz: Sizes, cfg) -> dict:
    """One prefill chunk and one decode step of ``paged_forward`` through
    the Pallas kernel vs the jnp gather path (``use_kernel=False``): same
    bf16 weights, same pools, logits and written KV compared."""
    model = Mistral(size=sz.size, num_layers=sz.agree_layers,
                    **sz.overrides)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                          model.init(jax.random.PRNGKey(2)))
    default = RaggedInferenceEngineConfig()
    bs, chunk = default.kv_block_size, default.max_chunk_size
    per_seq = -(-cfg.max_seq_len // bs)
    rows = 8                                     # decode batch
    shape = (sz.agree_layers, rows * per_seq, bs, cfg.num_kv_heads,
             cfg.head_dim)
    kk, kv_, kt = jax.random.split(jax.random.PRNGKey(3), 3)
    pools = {"k": jax.random.normal(kk, shape, jnp.bfloat16),
             "v": jax.random.normal(kv_, shape, jnp.bfloat16)}
    tables = jnp.arange(rows * per_seq, dtype=jnp.int32).reshape(
        rows, per_seq)
    top = cfg.max_seq_len
    window = cfg.sliding_window

    def step(use_kernel):
        return jax.jit(lambda params, pools, *ops: paged_forward(
            model, params, pools, *ops, use_kernel=use_kernel))

    cases = {
        # a fresh prompt's first chunk, and a chunk that starts past the
        # window with part of the chunk padded
        "prefill": (2, chunk, [0, window + 3 * bs],
                    [chunk, chunk - chunk // 4]),
        # decode rows around the block and window edges, up to the top
        "decode": (rows, 1,
                   [5, bs - 1, bs, window - 1, window, window + bs + 7,
                    top - bs, top - 1][:rows], [1] * rows),
    }
    result = {}
    for label, (b, s, pos0, true_len) in cases.items():
        ops = (jax.random.randint(kt, (b, s), 0, cfg.vocab_size),
               jnp.asarray(pos0, jnp.int32), tables[:b],
               jnp.asarray(true_len, jnp.int32))
        exe = step(True).lower(params, pools, *ops).compile()
        if on_tpu():
            check(MOSAIC in exe.as_text(),
                  f"paged {label}: kernel not compiled by Mosaic")
        logits, new_pools = exe(params, pools, *ops)
        with jax.default_matmul_precision("highest"):
            ref_logits, ref_pools = step(False)(params, pools, *ops)
        result[f"paged/{label}/logits"] = (
            _errors(logits, ref_logits), TOL_MODEL)
        # the chunk's k/v as written to the pool: the same projections in
        # both programs, so they differ by a bf16 ulp or two at most — but
        # the max is over a whole pool scaled by its largest element
        for kv in ("k", "v"):
            result[f"paged/{label}/{kv}"] = (
                _errors(new_pools[kv], ref_pools[kv]), TOL_MODEL)
        del logits, new_pools, ref_logits, ref_pools
    return result


# --------------------------------------------------------------------------
def free_device_memory(label: str) -> None:
    """The previous phase's engine is out of scope: collect it and (where
    the backend reports memory) see that the chip is empty again."""
    gc.collect()
    hbm = hbm_in_use()
    if hbm is None:
        return
    limit = int(jax.devices()[0].memory_stats()["bytes_limit"])
    print(f"after {label}: bytes_in_use per device "
          f"{[round(b / 2 ** 30, 2) for b in hbm]} GiB", flush=True)
    check(max(hbm) < limit // 10,
          f"{label} engine not freed: {max(hbm)} bytes still in use")


def main() -> int:
    t0 = time.perf_counter()
    cache_dir = enable_compile_cache()
    device = device_gate(cache_dir)
    print(f"depth: train {TRAIN_LAYERS}, serve {SERVE_LAYERS}, agreement "
          f"{AGREE_LAYERS} of the 32 published layers; every width as "
          f"published", flush=True)
    # the repo's own record of what each executable contains (Mosaic
    # custom calls, collectives, operand shapes) and of every compile
    telemetry.configure(executable_ledger=True)

    print("train:", json.dumps(train_phase(FULL)), flush=True)
    free_device_memory("train")
    print("serve:", json.dumps(serve_phase(FULL)), flush=True)
    free_device_memory("serve")
    agreement_phase(FULL)
    print(f"total_s: {time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

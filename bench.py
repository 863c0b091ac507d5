"""Benchmark: GPT-2 125M training throughput on one TPU chip.

Prints ONE JSON line on stdout: {"metric", "value", "unit",
"vs_baseline"}. vs_baseline is MFU / 0.45 — the north-star MFU target
from BASELINE.md §9 (the reference's headline training-efficiency claim
class; e.g. Ulysses sustains 54% of peak on A100, BASELINE.md §3).

stderr carries '# '-prefixed tail lines recorded alongside: a
Llama-family training config (BASELINE configs 2-3 class, scaled to one
chip) and a kernel smoke section running every Pallas kernel family on
the real chip (quantize/dequant roundtrips, fused optimizers, norms,
flash attention, block-sparse attention) so interpret-mode-only test
coverage can't hide TPU-specific lowering bugs.

Stage control (BENCH_r05 ended rc=124 with no parseable output): every
stage runs under a SIGALRM budget (``--budget-s``, per-stage), a GLOBAL
deadline (``--total-budget-s``, env ``DS_BENCH_TOTAL_BUDGET_S``,
default 3300 s) skips whatever stages remain once it passes — so the
full matrix can never outlive the harness wall clock — stages can be
selected with ``--stage a,b`` (``--list-stages`` prints them), and the
stdout JSON line is emitted no matter what — after the headline stage,
on any stage timeout, at the global deadline, or from the SIGTERM
handler when the harness's ``timeout`` fires mid-stage — so the driver
always parses a result instead of null.
"""

import argparse
import json
import os
import signal
import sys
import threading
import time

import jax
import jax.numpy as jnp

# bf16 peak FLOPS by device kind (per chip)
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5": 459e12,        # v5p
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,   # trillium
    "cpu": 1e12,             # arbitrary floor for CPU smoke runs
}


def peak_flops(device) -> float:
    kind = getattr(device, "device_kind", "cpu")
    for k, v in PEAK_FLOPS.items():
        if kind.startswith(k):
            return v
    return 1e12



def _cpu_batch(per_dev: int = 2) -> int:
    """CPU-smoke batch: must divide the (possibly virtual) dp world."""
    return per_dev * len(jax.devices())


def _mean_ci95(xs):
    """(mean, t-distribution 95% half-width) over measurement windows.
    A comparison claim is honest only when the CI excludes zero
    (single best-of pairs swing with host dispatch jitter)."""
    import math
    n = len(xs)
    m = sum(xs) / n
    if n < 2:
        return m, float("inf")
    var = sum((x - m) ** 2 for x in xs) / (n - 1)
    t = {2: 12.706, 3: 4.303, 4: 3.182, 5: 2.776, 6: 2.571, 7: 2.447,
         8: 2.365, 9: 2.306, 10: 2.262}.get(n, 2.0)
    return m, t * math.sqrt(var / n)


def _mfu_fields(tps: float, cfg, seq: int) -> dict:
    """Primary MFU is causal-physical accounting; the conventional
    full-attention figure rides along as mfu_noncausal for
    cross-framework comparison (VERDICT r2 weak #1). With --telemetry,
    the device-truth fields from the executable ledger (ISSUE 5) ride
    along: the compiled step's peak HBM and collective payload."""
    peak = peak_flops(jax.devices()[0])
    return {"mfu": round(tps * cfg.flops_per_token(seq) / peak, 4),
            "mfu_noncausal": round(
                tps * cfg.flops_per_token(seq, causal=False) / peak, 4),
            **_ledger_truth_fields(), **_steptrace_fields()}


def _ledger_truth_fields() -> dict:
    """{hbm_peak_bytes, wire bytes} from the telemetry executable ledger
    when it is live (bench --telemetry): the largest registered
    executable's compiler-reported peak HBM and the HLO-accounted
    collective payload. Empty when telemetry/ledger are off."""
    from deepspeed_tpu.utils.telemetry_probe import active_telemetry
    mod = active_telemetry()
    led = mod.get_ledger() if mod is not None else None
    if led is None or not len(led):
        return {}
    out: dict = {}
    peaks = led.peak_hbm_by_name()
    if peaks:
        out["hbm_peak_bytes"] = max(peaks.values())
    # per-axis collective payload + observed wire width (ISSUE 8):
    # train-stage artifacts carry the HLO-accounted bytes so the
    # `--gate comms` diff family can watch them across rounds, and the
    # wire width shows whether qwZ/qgZ int8 payloads carried the
    # traffic (~1.1 B/el) or the wire was fp32 (4.0)
    traffic = led.traffic()
    if traffic:
        by_axis: dict = {}
        for (axis, _op), row in traffic.items():
            by_axis[axis] = by_axis.get(axis, 0) + row["bytes"]
        out["wire_bytes_per_axis"] = by_axis
        from deepspeed_tpu.telemetry.collectives import axis_wire_width
        out["wire_bytes_per_el"] = {
            a: round(w, 3) for a, w in axis_wire_width(traffic).items()}
    return out


def _steptrace_fields() -> dict:
    """{goodput_fraction, badput_seconds, recon_max_rel_err} from the
    steptrace run ledger when it is live (bench --telemetry, ISSUE 20):
    the train stages' artifacts carry the goodput/badput breakdown and
    the telescoping reconciliation error so `--gate train` can watch
    goodput across rounds and the recon contract is checkable from the
    bench record alone. Empty when telemetry/steptrace are off or no
    step completed."""
    from deepspeed_tpu.utils.telemetry_probe import active_telemetry
    mod = active_telemetry()
    st = mod.get_step_recorder() if mod is not None else None
    if st is None or not st.steps_recorded:
        return {}
    s = st.goodput_summary()
    return {"goodput_fraction": round(s["goodput_fraction"], 4),
            "badput_seconds": {k: round(v, 4) for k, v in
                               s["badput_seconds"].items()},
            "recon_max_rel_err": s["recon_max_rel_err"]}


def _train_tput(ds, model, config_extra: dict, batch: int, seq: int,
                steps: int, windows: int = 1):
    """Shared throughput harness: build an engine, warm up, run best-of-
    `windows` timed loops with a device->host sync (float(loss)) per
    window. Returns (tokens/s, last loss). The engine is freed when this
    frame returns (main() gc.collect()s between sections)."""
    config = {
        "train_batch_size": batch,
        "optimizer": {"type": "FusedAdam",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 10 ** 9,
        **config_extra,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (batch, seq + 1), 0,
                                model.config.vocab_size)
    data = (tokens[:, :-1], tokens[:, 1:])
    float(engine.train_batch(data))
    dt = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch(data)
        last = float(loss)  # device->host copy = sync
        dt = min(dt, time.perf_counter() - t0)
    return steps * batch * seq / dt, last


def kernel_smoke() -> dict:
    """Run every Pallas kernel family once on the live backend; returns
    {check: max_abs_err} (floats) — a failure surfaces as an exception
    string instead of an error value."""
    results: dict = {}
    key = jax.random.PRNGKey(0)

    def check(name, fn):
        try:
            results[name] = round(float(fn()), 8)
        except Exception as e:   # noqa: BLE001 — report, don't die
            results[name] = f"FAIL: {type(e).__name__}: {str(e)[:100]}"

    x = jax.random.normal(key, (4096, 1024), jnp.float32)

    def int8_roundtrip():
        from deepspeed_tpu.ops.pallas.quantization import (dequantize_int8,
                                                           quantize_int8)
        q, s, meta = quantize_int8(x)
        return jnp.max(jnp.abs(dequantize_int8(q, s, meta) - x))

    def fp8_roundtrip():
        from deepspeed_tpu.ops.fp_quant import fp_dequantize, fp_quantize
        c, s = fp_quantize(x, q_bits=8, mantissa_bits=3)
        return jnp.max(jnp.abs(
            fp_dequantize(c, s, q_bits=8, mantissa_bits=3, shape=x.shape)
            - x))

    def fp6_roundtrip():
        from deepspeed_tpu.ops.fp_quant import fp_dequantize, fp_quantize
        c, s = fp_quantize(x, q_bits=6, mantissa_bits=2)
        return jnp.max(jnp.abs(
            fp_dequantize(c, s, q_bits=6, mantissa_bits=2, shape=x.shape)
            - x))

    def norms_err():
        from deepspeed_tpu.ops import layers as L
        from deepspeed_tpu.ops.pallas import norms
        scale = jnp.ones((1024,)) * 1.5
        return jnp.max(jnp.abs(norms.rms_norm(x, scale)
                               - L.rms_norm(x, scale)))

    def fused_adam_err():
        import optax
        from deepspeed_tpu.ops.pallas.fused_optimizers import fused_adam
        p = {"w": x[:64]}
        g = {"w": jax.random.normal(jax.random.PRNGKey(1), (64, 1024))}
        tx, ref = fused_adam(1e-3), optax.adam(1e-3)
        up, _ = tx.update(g, tx.init(p), p)
        rup, _ = ref.update(g, ref.init(p), p)
        return jnp.max(jnp.abs(up["w"] - rup["w"]))

    def flash_err():
        from deepspeed_tpu.ops.layers import dot_product_attention
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (2, 512, 8, 64), jnp.float32)
        k = jax.random.normal(ks[1], (2, 512, 8, 64), jnp.float32)
        v = jax.random.normal(ks[2], (2, 512, 8, 64), jnp.float32)
        return jnp.max(jnp.abs(flash_attention(q, k, v, causal=True)
                               - dot_product_attention(q, k, v,
                                                       causal=True)))

    def sparse_err():
        import numpy as np
        from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig
        from deepspeed_tpu.ops.sparse_attention.kernels import \
            block_sparse_attention
        from deepspeed_tpu.ops.sparse_attention.sparse_self_attention \
            import layout_to_bias
        cfg = FixedSparsityConfig(num_heads=4, block=128)
        layout = cfg.make_layout(512)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (2, 4, 512, 64), jnp.float32)
        k = jax.random.normal(ks[1], (2, 4, 512, 64), jnp.float32)
        v = jax.random.normal(ks[2], (2, 4, 512, 64), jnp.float32)
        bias = layout_to_bias(layout, 128)
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(64.0) + bias[None]
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v)
        return jnp.max(jnp.abs(block_sparse_attention(q, k, v, layout)
                               - ref))

    def paged_err():
        # real-hardware parity of the paged-attention kernel vs the
        # exact gathered form (VERDICT r2 weak #7: the alignment-dispatch
        # seam was exercised interpret-mode only)
        import numpy as np
        from deepspeed_tpu.inference.v2.paged import (
            gather_pages, paged_attention, paged_attention_kernel,
            place_in_pages)
        B, SQ, H, D, NB, BS = 2, 16, 4, 64, 32, 16
        ks = jax.random.split(key, 5)
        q = jax.random.normal(ks[0], (B, SQ, H, D))
        k_new = jax.random.normal(ks[1], (B, SQ, H, D))
        v_new = jax.random.normal(ks[2], (B, SQ, H, D))
        k_pool = jax.random.normal(ks[3], (NB, BS, H, D))
        v_pool = jax.random.normal(ks[4], (NB, BS, H, D))
        tables = jnp.asarray(np.random.default_rng(2).permutation(NB)
                             [:B * 8].reshape(B, 8))
        pos0 = jnp.asarray([21, 0])
        true_len = jnp.asarray([SQ, 7])
        from deepspeed_tpu.ops.layers import alibi_slopes
        k_pages = place_in_pages(gather_pages(k_pool, tables), k_new,
                                 pos0, true_len)
        v_pages = place_in_pages(gather_pages(v_pool, tables), v_new,
                                 pos0, true_len)
        live = jnp.arange(SQ)[None, :, None, None] < true_len[:, None,
                                                             None, None]
        # BOTH kernel specializations get hardware parity: the default
        # path and the ALiBi (Bloom) path — worst error is reported
        err = 0.0
        for slopes in (None, alibi_slopes(H)):
            out_k = paged_attention_kernel(
                q, k_new, v_new, k_pool, v_pool, tables, pos0, true_len,
                alibi_slopes=slopes)
            ref = paged_attention(q, k_pages, v_pages, pos0,
                                  alibi_slopes=slopes)
            err = jnp.maximum(err, jnp.max(jnp.abs(
                jnp.where(live, out_k - ref, 0.0))))
        return err

    for name, fn in [("int8_roundtrip", int8_roundtrip),
                     ("fp8_roundtrip", fp8_roundtrip),
                     ("fp6_roundtrip", fp6_roundtrip),
                     ("norms", norms_err),
                     ("fused_adam", fused_adam_err),
                     ("flash_attention", flash_err),
                     ("block_sparse_attention", sparse_err),
                     ("paged_attention", paged_err)]:
        check(name, fn)
    return results


def llama_bench(ds, on_tpu: bool):
    """Llama-family training config (BASELINE configs 2-3 class, scaled
    to one chip): ~340M params, GQA d_head=128, RoPE/RMSNorm/SwiGLU,
    ZeRO-2 + fused Adam at seq 2048."""
    from deepspeed_tpu.models import Llama
    seq = 2048 if on_tpu else 128
    batch = 4 if on_tpu else _cpu_batch()
    model = (Llama(hidden_size=1024, num_layers=24, num_heads=8,
                   num_kv_heads=8, intermediate_size=2816,
                   vocab_size=32000, max_seq_len=seq,
                   remat_policy="segments", attn_impl="flash")
             if on_tpu else Llama(size="tiny", max_seq_len=seq))
    tps, _ = _train_tput(ds, model, {"gradient_clipping": 1.0}, batch,
                         seq, steps=10 if on_tpu else 2,
                         windows=2 if on_tpu else 1)
    return {"metric": "llama_340m_train_tokens_per_sec",
            "value": round(tps, 1), "unit": "tokens/s/chip",
            **_mfu_fields(tps, model.config, seq)}


def longctx_bench(ds, on_tpu: bool):
    """Long-context class (BASELINE config 4 / Ulysses-32k): 32k-token
    sequences on one chip (the sp>1 all-to-all path is exercised on the
    virtual mesh in dryrun_multichip; this measures the long-seq
    attention + remat engine path on real hardware)."""
    from deepspeed_tpu.models import Llama
    seq = 32768 if on_tpu else 256
    model = (Llama(hidden_size=1024, num_layers=12, num_heads=8,
                   num_kv_heads=8, intermediate_size=2816,
                   vocab_size=32000, max_seq_len=seq,
                   remat_policy="segments", attn_impl="flash",
                   loss_chunk=2048)
             if on_tpu else Llama(size="tiny", max_seq_len=seq))
    tps, _ = _train_tput(ds, model, {},
                         batch=1 if on_tpu else _cpu_batch(1),
                         seq=seq, steps=4 if on_tpu else 1)
    # the conventional full-attention figure is ~2x the causal-physical
    # one at 32k; _mfu_fields keeps causal primary
    return {"metric": "llama_32k_seq_train_tokens_per_sec",
            "value": round(tps, 1), "unit": "tokens/s/chip",
            **_mfu_fields(tps, model.config, seq)}


def moe_bench(ds, on_tpu: bool):
    """MoE class (BASELINE config 5 / Mixtral-EP): top-2 routed experts;
    ep>1 dispatch is exercised on the virtual mesh in dryrun_multichip —
    this measures the routed-expert compute path on real hardware."""
    from deepspeed_tpu.models import Mixtral
    seq = 1024 if on_tpu else 64
    batch = 8 if on_tpu else _cpu_batch()
    model = (Mixtral(hidden_size=512, num_layers=8, num_heads=8,
                     num_kv_heads=8, intermediate_size=1408,
                     num_experts=8, moe_top_k=2, vocab_size=32000,
                     max_seq_len=seq, remat_policy="segments",
                     attn_impl="flash")
             if on_tpu else Mixtral(size="tiny", max_seq_len=seq))
    tps, _ = _train_tput(ds, model, {}, batch, seq,
                         steps=8 if on_tpu else 1)
    return {"metric": "mixtral_8e_top2_train_tokens_per_sec",
            "value": round(tps, 1), "unit": "tokens/s/chip"}


def _decode_chain_setup(model, e2, uids, use_kernel: bool):
    """Shared scaffolding for the chain-differenced paged decode-step
    measurement: build the single-token decode operands for `uids` (the
    engine's own bucketing) and a make_chain(length) factory that scans
    the paged step inside ONE jit — a whole chain of decode steps costs
    one dispatch, so differencing two chain lengths cancels the
    harness's per-dispatch RTT."""
    import functools as _ft

    import numpy as np

    from deepspeed_tpu.inference.v2.engine_v2 import _batch_bucket, _bucket
    from deepspeed_tpu.inference.v2.paged import paged_forward

    mgr = e2.state_manager
    seqs = [mgr.seqs[u] for u in uids]
    bb = _batch_bucket(len(seqs))
    tok1 = np.zeros((bb, 1), np.int32)
    pos0_a = np.zeros((bb,), np.int32)
    tlen_a = np.zeros((bb,), np.int32)
    tabs = np.stack([mgr.block_table(s) for s in seqs]
                    + [mgr.block_table(seqs[0])] * (bb - len(seqs)))
    for i, sq_ in enumerate(seqs):
        tok1[i, 0] = 1
        pos0_a[i] = sq_.seen
        tlen_a[i] = 1
    live_blocks = -(-int((pos0_a + tlen_a).max()) // mgr.block_size)
    kb = min(_bucket(max(live_blocks, 1)), tabs.shape[1])
    tabs = tabs[:, :kb]
    fwd = _ft.partial(paged_forward, model, use_kernel=use_kernel)

    def make_chain(length):
        @jax.jit
        def chain(params, pools, tokens, pos0, tables, tlen):
            def body(pools, _):
                lg, pools = fwd(params, pools, tokens, pos0, tables, tlen)
                return pools, lg[0, 0]
            pools, lgs = jax.lax.scan(body, pools, None, length=length)
            return lgs, pools
        return chain

    args = (jnp.asarray(tok1), jnp.asarray(pos0_a), jnp.asarray(tabs),
            jnp.asarray(tlen_a))
    return make_chain, args


def _chain_pair_ms(chain_l, chain_s, params, pools, args,
                   long_n: int, short_n: int, reps: int = 3):
    """best-of-reps for each chain length, then differenced: one
    host dispatch round trip rides on each timing, so a single pair
    is noise-bound — min over reps recovers the device time the
    differencing needs. Returns (ms/step, pools)."""
    dl = ds_ = float("inf")
    for _ in range(reps):
        t2 = time.perf_counter()
        lgs, pools = chain_l(params, pools, *args)
        float(jnp.sum(lgs))
        dl = min(dl, time.perf_counter() - t2)
        t2 = time.perf_counter()
        lgs, pools = chain_s(params, pools, *args)
        float(jnp.sum(lgs))
        ds_ = min(ds_, time.perf_counter() - t2)
    return max(dl - ds_, 1e-9) / (long_n - short_n) * 1e3, pools


def _tick_percentiles(one_tick, n: int):
    """(p50, p99) wall-clock over n host-in-loop scheduler ticks."""
    one_tick()                       # warm the decode bucket
    ticks = []
    for _ in range(n):
        t1 = time.perf_counter()
        one_tick()
        ticks.append((time.perf_counter() - t1) * 1e3)
    ticks.sort()
    return (ticks[len(ticks) // 2],
            ticks[min(len(ticks) - 1, int(len(ticks) * 0.99))])


def _fused_decode_metrics(e, prompts: list, k: int,
                          n_dispatches: int) -> dict:
    """Measure the fused multi-step decode loop (ISSUE 1 tentpole) on a
    v2 engine `e` with no live sequences: prefill `prompts`, then each
    timed host dispatch advances every sequence K tokens inside one
    compiled while_loop (in-graph sampling + KV writes + termination).
    Reported against the per-tick loop's 1 dispatch/token:
    ``fused_dispatches_per_token`` (~1/K) and ``fused_occupancy`` (live
    (row, step) slot fraction) come straight from the engine's serving
    counters, and ``fused_tick_p50_ms`` is the acceptance gate's figure
    — it should sit near K x decode_step_ms_compute, not K x
    host-RTT."""
    uids = list(range(len(prompts)))
    e.put(uids, prompts)
    # decode_fused consumes exactly one pending token per row (the last
    # sampled one); seed the chain with a fixed first token
    for u in uids:
        e.state_manager.extend(u, [1])
    e.reset_serving_metrics()
    # _tick_percentiles' warm (compile) dispatch lands inside the
    # counters but cancels out of the per-token ratios
    p50, p99 = _tick_percentiles(
        lambda: e.decode_fused(uids, k_steps=k), n_dispatches)
    m = e.serving_metrics()
    return {"fused_k": k,
            "fused_tick_p50_ms": round(p50, 2),
            "fused_tick_p99_ms": round(p99, 2),
            "fused_dispatches_per_token": round(
                m["dispatches_per_token"], 4),
            "fused_occupancy": round(m["fused_occupancy"], 3),
            "fused_tokens_per_sec": round(
                len(uids) * k * 1e3 / max(p50, 1e-9), 1)}


def _decode_step_probe(model, e, uids, use_kernel: bool, long_n: int,
                       short_n: int, reps: int) -> float:
    """Chain-differenced device-truth decode-step time (ms) for
    sequences already resident in engine ``e`` — the shared probe
    behind the serving stages' compute denominators. Never donates
    ``e.pools``, so the engine stays usable afterwards."""
    make_chain, args = _decode_chain_setup(model, e, uids,
                                           use_kernel=use_kernel)
    chain_l, chain_s = make_chain(long_n), make_chain(short_n)
    pools = e.pools
    for c in (chain_l, chain_s):                        # compile + warm
        lgs, pools = c(e.params, pools, *args)
        float(jnp.sum(lgs))
    ms, _ = _chain_pair_ms(chain_l, chain_s, e.params, pools, args,
                           long_n, short_n, reps=reps)
    return ms


def _chained_serve_metrics(e, prompts: list, k: int,
                           max_new: int) -> dict:
    """Drive the N-deep chained serving loop (ISSUE 6) over `prompts`
    and report the acceptance figures: per-decode-step wall time with
    the chain's host syncs amortized in (``tick_p50_ms`` over per-chain
    drains; the gate compares it against ``decode_step_ms_compute``)
    and host dispatches per decoded token at equal greedy outputs.
    Engine state is left flushed. Call once warm (compiles), once
    timed."""
    from deepspeed_tpu.inference.v2.serve_loop import FusedServeLoop
    e.reset_serving_metrics()
    loop = FusedServeLoop(e, k_steps=k, strict=True)
    for i, p in enumerate(prompts):
        loop.submit(p, max_new, uid=i)
    t0 = time.perf_counter()
    n_tok = 0
    while loop.has_work():
        for evt in loop.step():
            n_tok += len(evt.tokens)
    wall = time.perf_counter() - t0
    ticks = sorted(dt / s * 1e3 for dt, s in loop.drain_stats if s > 0)
    steps_total = sum(s for _, s in loop.drain_stats)
    m = e.serving_metrics()
    return {"tick_p50_ms": round(ticks[len(ticks) // 2], 2) if ticks
            else None,
            "tick_p99_ms": round(
                ticks[min(len(ticks) - 1, int(len(ticks) * 0.99))], 2)
            if ticks else None,
            "tick_mean_ms": round(wall * 1e3 / max(steps_total, 1), 2),
            "chained_tokens_per_sec": round(n_tok / max(wall, 1e-9), 1),
            "dispatches_per_token_chained": round(
                m["dispatches_per_token"], 4),
            "fused_occupancy_chained": round(m["fused_occupancy"], 3),
            "chain_depth": int(e._config.max_inflight_dispatches),
            "fused_admission": bool(e._config.fused_admission)}


def _bench_serving_slo():
    """ONE constructor for the bench's serving SLO targets (ISSUE 19
    satellite): the ``serving`` stage's ``tokens_per_sec_at_slo`` and
    the ``serve_openloop``/``serve_autotune`` goodput-under-SLO
    figures all gate against the SAME ``ServingConfig``-declared
    targets — no hard-coded SLA drifting from the config. ITL 50 ms is
    the FastGen-style >= 20 tok/s/user SLA."""
    from deepspeed_tpu.serving import ServingConfig
    return ServingConfig(slo_ttft_ms=1000.0, slo_itl_ms=50.0)


def _openloop_drive(e, scfg, prompts, arrivals, max_new):
    """Drive one open-loop Poisson trace against a fresh
    ``AsyncInferenceServer`` on ``e`` and score it under ``scfg``'s
    SLOs. Shared by the serve_openloop load-step phase and the
    serve_autotune measured comparison so both halves of ISSUE 19
    grade traffic identically. Returns client-side latencies, shed
    accounting (zero silent drops is asserted: every submit ends
    completed, shed or failed), goodput under SLO, and the server's
    final metrics."""
    import asyncio

    from deepspeed_tpu.serving import AsyncInferenceServer, RequestFailed

    out = {"ttft": [], "itl": [], "shed_lat": [], "completed": 0,
           "shed": 0, "failed": 0, "good": 0}
    t_wall = {}

    async def client(srv, i):
        await asyncio.sleep(float(arrivals[i]))
        t_sub = time.perf_counter()
        try:
            h = await srv.submit(prompts[i], max_new_tokens=max_new)
            t_first = t_last = None
            n = 0
            async for _tok in h:
                now = time.perf_counter()
                if t_first is None:
                    t_first = now
                t_last = now
                n += 1
        except RequestFailed as err:
            if "shed" in str(err):
                out["shed"] += 1
                out["shed_lat"].append(
                    (time.perf_counter() - t_sub) * 1e3)
            else:
                out["failed"] += 1
            return
        if t_first is None:
            out["failed"] += 1
            return
        out["completed"] += 1
        ttft_ms = (t_first - t_sub) * 1e3
        out["ttft"].append(ttft_ms)
        itl_ms = ((t_last - t_first) / (n - 1) * 1e3) if n > 1 else 0.0
        if n > 1:
            out["itl"].append(itl_ms)
        if ((not scfg.slo_ttft_ms or ttft_ms <= scfg.slo_ttft_ms)
                and (not scfg.slo_itl_ms or itl_ms <= scfg.slo_itl_ms)):
            out["good"] += 1

    async def run():
        async with AsyncInferenceServer(e, scfg) as srv:
            t_wall["t0"] = time.perf_counter()
            await asyncio.gather(*(client(srv, i)
                                   for i in range(len(prompts))))
            t_wall["t1"] = time.perf_counter()
            return srv.metrics()

    m = asyncio.run(run())
    n = len(prompts)
    accounted = out["completed"] + out["shed"] + out["failed"]
    assert accounted == n, (
        f"silent drop: {n - accounted} of {n} requests unaccounted")
    wall = max(t_wall["t1"] - t_wall["t0"], 1e-9)
    out["goodput_rps"] = out["good"] / wall
    out["wall_s"] = wall
    out["metrics"] = m
    return out


def serve_openloop_bench(ds, on_tpu: bool):
    """Open-loop Poisson traffic against the async continuous-batching
    server (ISSUE 6): synthetic clients arrive at a fixed rate, stream
    their tokens, and the stage reports the serving SLO histograms —
    TTFT p50/p99 (submit -> first streamed token, queueing included)
    and per-request mean inter-token latency p50/p99 — plus the
    tick-vs-compute ratio: p50 wall time per decode step through the
    chained serving loop over the chain-differenced device compute
    step (1.0 = the host adds nothing; the acceptance gate is <= 2).

    A second load-step phase (ISSUE 19) replays rate λ -> 3λ -> λ with
    the admission shed and feedback controller armed: goodput under
    the ServingConfig SLOs, shed counts (fast-failed, zero silent
    drops), controller adaptation events, and the controlled
    queue-wait p99 against the uncontrolled phase's (the >= 5x
    BENCH_r06 acceptance bar). Gate with ``--gate serving``."""
    import asyncio

    import numpy as np
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Llama
    from deepspeed_tpu.serving import AsyncInferenceServer, ServingConfig

    if on_tpu:
        model = Llama(hidden_size=1024, num_layers=12, num_heads=8,
                      num_kv_heads=8, intermediate_size=2816,
                      vocab_size=32000, max_seq_len=2048)
        bs_kv, nb, chunk, B = 64, 256, 256, 16
        n_req, rate_rps, p_len, max_new, K, depth = 48, 6.0, 128, 48, 8, 4
    else:
        model = Llama(size="tiny", max_seq_len=256)
        bs_kv, nb, chunk, B = 8, 128, 16, 8
        n_req, rate_rps, p_len, max_new, K, depth = 10, 20.0, 12, 6, 4, 2
    e = InferenceEngineV2(model, RaggedInferenceEngineConfig(
        dtype="bfloat16" if on_tpu else "float32", kv_block_size=bs_kv,
        num_kv_blocks=nb, max_chunk_size=chunk,
        max_ragged_sequence_count=B, fused_decode_steps=K,
        max_inflight_dispatches=depth, fused_admission=True))
    rng = np.random.default_rng(0)
    vocab = model.config.vocab_size
    prompts = [rng.integers(0, vocab, p_len).tolist()
               for _ in range(n_req)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, n_req))

    # device-truth decode step for the ratio denominator
    probe_uids = list(range(10 ** 6, 10 ** 6 + min(4, B)))
    e.put(probe_uids, [prompts[i % n_req] for i in range(len(probe_uids))])
    step_ms = _decode_step_probe(model, e, probe_uids, on_tpu,
                                 *((32, 8, 3) if on_tpu else (4, 2, 1)))
    e.flush(probe_uids)

    # warm the serving-loop executables (prefill buckets + the serve
    # ring loop) outside the measured traffic window — both the full
    # decode-batch bucket and the single-row bucket, so the measured
    # ticks mostly hit the executable cache
    for n_warm in (min(B, n_req), 1):
        _chained_serve_metrics(e, prompts[:n_warm], K,
                               max_new=min(max_new, 2 * K))
    # the gated efficiency counters must cover ONLY the measured
    # traffic window, not the warm-up drives
    e.reset_serving_metrics()
    # per-request tracing (ISSUE 10): with --telemetry the request
    # recorder is live — clear the warm-up traces so the component
    # percentiles and the access log cover only the measured window
    from deepspeed_tpu.utils.telemetry_probe import active_telemetry
    tel = active_telemetry()
    rec = tel.get_request_recorder() if tel is not None else None
    if rec is not None:
        rec.clear()

    results = {"ttft": [], "itl_req": [], "done": 0}

    async def client(srv, i):
        await asyncio.sleep(float(arrivals[i]))
        t_sub = time.perf_counter()
        h = await srv.submit(prompts[i], max_new_tokens=max_new)
        t_first = t_last = None
        n = 0
        async for _tok in h:
            now = time.perf_counter()
            if t_first is None:
                t_first = now
            t_last = now
            n += 1
        if t_first is None:
            return
        results["ttft"].append((t_first - t_sub) * 1e3)
        if n > 1:
            results["itl_req"].append((t_last - t_first) / (n - 1) * 1e3)
        results["done"] += 1

    async def run():
        async with AsyncInferenceServer(
                e, ServingConfig(k_steps=K)) as srv:
            await asyncio.gather(*(client(srv, i)
                                   for i in range(n_req)))
            return srv.session.drain_stats, srv.metrics()

    drains, m = asyncio.run(run())

    def pct(xs, q):
        if not xs:
            return None
        xs = sorted(xs)
        return round(xs[min(len(xs) - 1, int(len(xs) * q))], 2)

    ticks = [dt / s * 1e3 for dt, s in drains if s > 0]
    tick_p50 = pct(ticks, 0.5)
    # tail-latency attribution (ISSUE 10): per-request component
    # percentiles + the dominant p99-TTFT component + a reconciliation
    # figure (worst relative gap between a request's TTFT component sum
    # and its measured TTFT — the acceptance bound is 5%)
    breakdown: dict = {}
    if rec is not None:
        from deepspeed_tpu.telemetry.reqtrace import COMPONENT_KEYS
        pcts = rec.component_percentiles()
        for name in COMPONENT_KEYS:
            row = pcts.get(name)
            breakdown[f"{name}_p50_ms"] = (
                round(row["p50"] * 1e3, 3) if row else None)
            breakdown[f"{name}_p99_ms"] = (
                round(row["p99"] * 1e3, 3) if row else None)
        attr = rec.ttft_attribution()
        breakdown["ttft_dominant_component"] = attr.get(
            "dominant_component")
        recon = [abs((tr.queue_wait_s + tr.prefill_s + tr.migrate_s
                      + tr.first_drain_s) - tr.ttft_s) / tr.ttft_s
                 for tr in rec.completed() if tr.ttft_s]
        breakdown["access_log_requests"] = len(rec.completed())
        breakdown["ttft_recon_max_rel_err"] = (
            round(max(recon), 5) if recon else None)

    # ---- load-step phase (ISSUE 19): rate λ -> 3λ -> λ with the
    # admission shed + online feedback controller armed, against an
    # UNCONTROLLED run of the very same arrival trace (BENCH_r06:
    # unbounded admission put 11.2 s of queue_wait in an 11.5 s TTFT
    # p99) — the controller must hold ITL within budget and keep
    # queue_wait bounded by shedding fast-failed (counted) requests at
    # the 3λ peak.
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.serving import ControllerConfig
    slo = _bench_serving_slo()
    # size the step against MEASURED closed-loop capacity so the 3λ
    # peak genuinely saturates on every platform: λ at ~0.7x capacity
    # is healthy, 3λ overruns it ~2x and builds a real queue
    warm_full = _chained_serve_metrics(e, prompts[:min(B, n_req)], K,
                                       max_new=max_new)
    e.reset_serving_metrics()
    cap_est = max(warm_full["chained_tokens_per_sec"] / max_new, 1.0)
    lam = 0.7 * cap_est
    seg_n = 80
    rates = [lam, 3 * lam, lam]
    rng2 = np.random.default_rng(1)
    arr2, t_at = [], 0.0
    for r in rates:
        for g in rng2.exponential(1.0 / r, seg_n):
            t_at += g
            arr2.append(t_at)
    prompts2 = [rng2.integers(0, vocab, p_len).tolist() for _ in arr2]
    # the phase NEEDS the telemetry plane (request traces feed the
    # controller's queue-wait/burn signals and the queue_wait p99
    # comparison); own it for the phase when the harness did not pass
    # --telemetry (same discipline as the fleet stage)
    owned = not telemetry.is_active()
    if owned:
        telemetry.configure()
    tel2 = active_telemetry()
    rec2 = tel2.get_request_recorder() if tel2 is not None else None
    try:
        base2_cfg = ServingConfig(
            k_steps=K, slo_ttft_ms=slo.slo_ttft_ms,
            slo_itl_ms=slo.slo_itl_ms)
        # admission bound at the engine row count: an admitted request
        # goes straight toward a decode row instead of aging in the
        # mailbox — the queue the BENCH_r06 baseline let grow unbounded
        ctl_cfg = ServingConfig(
            k_steps=K, slo_ttft_ms=slo.slo_ttft_ms,
            slo_itl_ms=slo.slo_itl_ms, shed_queue_depth=B,
            controller=ControllerConfig(
                enabled=True, interval_s=0.5 if on_tpu else 0.1))
        # throwaway drive of the trace itself: the 3λ burst packs
        # chunked-prefill admission buckets no closed-loop warm
        # produces, and one cold compile mid-measurement reads as
        # seconds of queue_wait
        _openloop_drive(e, ctl_cfg, prompts2, arr2, max_new)

        def measured_loadstep(scfg):
            e.reset_serving_metrics()
            if rec2 is not None:
                rec2.clear()
            r = _openloop_drive(e, scfg, prompts2, arr2, max_new)
            qw = None
            if rec2 is not None:
                row = rec2.component_percentiles().get("queue_wait")
                if row and row.get("n"):
                    qw = round(row["p99"] * 1e3, 3)
            return r, qw

        base_run2, base_qw_ms = measured_loadstep(base2_cfg)
        step_out, ctl_qw_ms = measured_loadstep(ctl_cfg)
    finally:
        if owned:
            telemetry.shutdown()
    m2 = step_out["metrics"]
    ctl_actions = m2.get("controller_actions", {})
    base_ttft_p99 = pct(base_run2["ttft"], 0.99)
    ctl_ttft_p99 = pct(step_out["ttft"], 0.99)
    loadstep = {
        "load_step_rates_rps": [round(r, 1) for r in rates],
        "load_step_requests": len(arr2),
        "goodput_under_slo_rps": round(step_out["goodput_rps"], 3),
        # the same trace, unbounded admission, controller off —
        # the BENCH_r06 baseline (qw field named so the serving
        # gate's queue_wait_p99 row matches only the controlled run)
        "uncontrolled_goodput_rps": round(base_run2["goodput_rps"], 3),
        "uncontrolled_ttft_p99_ms": base_ttft_p99,
        "uncontrolled_qw_p99_ms": base_qw_ms,
        "ctl_completed": step_out["completed"],
        "ctl_shed": step_out["shed"],
        "ctl_failed": step_out["failed"],
        "ctl_adaptations": int(sum(ctl_actions.values())),
        "ctl_actions": ctl_actions,
        "ctl_ttft_p99_ms": ctl_ttft_p99,
        "ctl_itl_p99_ms": pct(step_out["itl"], 0.99),
        # shed requests must fail FAST (the whole point vs aging in
        # the mailbox): client-observed submit -> RequestFailed p99
        "shed_fail_fast_p99_ms": pct(step_out["shed_lat"], 0.99),
        "ctl_queue_wait_p99_ms": ctl_qw_ms,
        # >= 5x vs the uncontrolled phase is the acceptance bar; the
        # TTFT ratio is the telemetry-free proxy (BENCH_r06: TTFT p99
        # is queue_wait-dominated uncontrolled)
        "ctl_queue_speedup_x": (
            round(base_qw_ms / max(ctl_qw_ms, 1e-3), 1)
            if base_qw_ms is not None and ctl_qw_ms is not None
            else None),
        "ctl_ttft_speedup_x": (
            round(base_ttft_p99 / max(ctl_ttft_p99, 1e-3), 1)
            if base_ttft_p99 and ctl_ttft_p99 else None),
    }
    return {"metric": "serve_openloop_ttft_p50_ms",
            "value": pct(results["ttft"], 0.5), "unit": "ms",
            "requests": n_req, "completed": results["done"],
            "arrival_rate_rps": rate_rps, "prompt_tokens": p_len,
            "max_new_tokens": max_new,
            "ttft_p99_ms": pct(results["ttft"], 0.99),
            "itl_p50_ms": pct(results["itl_req"], 0.5),
            "itl_p99_ms": pct(results["itl_req"], 0.99),
            "tick_p50_ms": tick_p50,
            "tick_p99_ms": pct(ticks, 0.99),
            "decode_step_ms_compute": round(step_ms, 3),
            "tick_vs_compute_ratio": (
                round(tick_p50 / max(step_ms, 1e-3), 2)
                if tick_p50 else None),
            "dispatches_per_token": round(m["dispatches_per_token"], 4),
            "fused_occupancy": round(m["fused_occupancy"], 3),
            "preemptions": m["preemptions"],
            "chain_depth": depth, "fused_k": K,
            "fused_admission": True, **breakdown, **loadstep}


def serve_autotune_bench(ds, on_tpu: bool):
    """Serving planner stage (ISSUE 19, offline half): calibrate the
    serving cost model on the live engine (fused decode tick + host
    dispatch RTT, solved from an amortized and an unamortized drive),
    AOT-rank the ServingCandidate grid against the open-loop traffic
    model, write artifacts/serving_plan.json, then MEASURE the chosen
    config against the hand-tuned serve_openloop baseline on identical
    Poisson traffic. Acceptance: plan goodput-under-SLO >= baseline
    (``serving_plan_vs_baseline`` >= 1). Render the plan with
    tools/autotune_report.py; gate with ``--gate serving``."""
    import gc

    import numpy as np
    from deepspeed_tpu.autotuning import (AutotuningConfig,
                                          ServingCalibration,
                                          ServingCandidate,
                                          ServingCostModel,
                                          ServingPlanner, TrafficModel,
                                          summarize_serving)
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.serve_loop import FusedServeLoop
    from deepspeed_tpu.models import Llama

    if on_tpu:
        model = Llama(hidden_size=1024, num_layers=12, num_heads=8,
                      num_kv_heads=8, intermediate_size=2816,
                      vocab_size=32000, max_seq_len=2048)
        bs_kv, nb, chunk, B = 64, 256, 256, 16
        n_req, rate_rps, p_len, max_new, K, depth = 192, 6.0, 128, 48, 8, 4
    else:
        model = Llama(size="tiny", max_seq_len=256)
        bs_kv, nb, chunk, B = 8, 128, 16, 8
        n_req, rate_rps, p_len, max_new, K, depth = 128, 20.0, 12, 6, 4, 2
    # the hand-tuned serve_openloop config IS the baseline (and a grid
    # point, so the plan can never rank below it under its own model)
    base_engine = {"dtype": "bfloat16" if on_tpu else "float32",
                   "kv_block_size": bs_kv, "num_kv_blocks": nb,
                   "max_chunk_size": chunk,
                   "max_ragged_sequence_count": B,
                   "fused_decode_steps": K,
                   "max_inflight_dispatches": depth,
                   "fused_admission": True}
    slo = _bench_serving_slo()
    e = InferenceEngineV2(model, RaggedInferenceEngineConfig(
        **base_engine))
    rng = np.random.default_rng(0)
    vocab = model.config.vocab_size
    prompts = [rng.integers(0, vocab, p_len).tolist()
               for _ in range(n_req)]

    def drive_ticks(k_steps, chain_depth, n_tok):
        """Closed-loop drive; returns mean wall seconds per decode
        tick (chain host syncs amortized in — the calibration's
        observable)."""
        loop = FusedServeLoop(e, k_steps=k_steps, temperature=0.0)
        loop.set_chain_depth(chain_depth)
        for i in range(min(4, B)):
            loop.submit(prompts[i % n_req], max_new_tokens=n_tok)
        while loop.has_work():
            loop.step()
        ticks = [dt / s for dt, s in loop.drain_stats if s > 0]
        loop.close()
        return sum(ticks) / max(len(ticks), 1)

    # calibration: t(k=1, d=1) exposes the full host RTT per tick;
    # t(K, depth) amortizes it over the chain span. Two warm drives
    # each (first compiles), best-of-two per point.
    span = K * depth
    t1 = min(drive_ticks(1, 1, 2 * K) for _ in range(2))
    tkd = min(drive_ticks(K, depth, 4 * K) for _ in range(2))
    overhead = max(0.0, (t1 - tkd) * span / max(span - 1, 1))
    tick = max(t1 - overhead, 1e-6)
    cal = ServingCalibration(
        decode_tick_s=round(tick, 6),
        dispatch_overhead_s=round(overhead, 6), source="measured")

    def mk_traffic(rps):
        return TrafficModel(
            arrival_rate_rps=rps, prompt_tokens=p_len,
            output_tokens=max_new, slo_ttft_ms=slo.slo_ttft_ms,
            slo_itl_ms=slo.slo_itl_ms,
            # random-token prompts: prompt-lookup drafts never accept,
            # and the traffic model must say so or the planner buys
            # verify compute that pays nothing on THIS traffic
            draft_acceptance=0.0)

    # saturate: offer 4x the hand-tuned config's calibrated capacity
    # (platform-adaptive). Under this load an unbounded-admission
    # candidate's queue diverges (rho >= 1 -> goodput 0) and the
    # planner must discover admission control — the BENCH_r06 failure
    # mode — rather than win on a tie at idle.
    probe = ServingCostModel(cal, max_rows=B, kv_block_size=bs_kv,
                             base_kv_blocks=nb)
    base_cap = probe.predict(
        ServingCandidate(k_steps=K, chain_depth=depth, ring=True),
        mk_traffic(rate_rps))["capacity_rps"]
    rate_rps = max(rate_rps, round(4.0 * base_cap, 1))
    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, n_req))
    traffic = mk_traffic(rate_rps)
    cfg = AutotuningConfig(
        enabled=True,
        serving_k_steps=[K // 2, K], serving_chain_depths=[1, 2, 4],
        # ring admission only: open-loop arrivals admit at every
        # rowset size, and plain-chain mode compiles one executable
        # bucket per size (a cold-compile storm inside the measured
        # window) — the same reason the hand-tuned baseline runs ring
        serving_ring_modes=[True],
        serving_draft_lens=[0, 3], serving_shed_depths=[0, 2 * B])
    planner = ServingPlanner(
        cfg, cal, traffic, base_engine_config=base_engine,
        base_serving_config={"k_steps": K}, max_rows=B,
        kv_block_size=bs_kv, base_kv_blocks=nb)
    plan = planner.plan()
    os.makedirs("artifacts", exist_ok=True)
    path = plan.save(os.path.join("artifacts", "serving_plan.json"))
    out = summarize_serving(plan)
    out["metric"] = "serving_plan_vs_baseline"
    out["unit"] = "x"
    out["plan_path"] = path
    out["calibration_tick_ms"] = round(tick * 1e3, 4)
    out["calibration_overhead_ms"] = round(overhead * 1e3, 4)

    # measured comparison on identical traffic: hand-tuned baseline
    # first (this engine), then the chosen config (fresh engine built
    # from plan.apply() — the artifact's reproduction contract)
    from deepspeed_tpu.serving import ServingConfig

    def warm(engine, scfg):
        # warm EVERY executable bucket outside the measured traffic
        # window: closed-loop sweeps over admission row counts 1..B,
        # then one throwaway drive of the measured arrival trace
        # itself (saturated admission packs chunked-prefill batches —
        # e.g. 16-chunk ragged buckets — that no closed-loop sweep
        # produces). One cold compile mid-measurement reads as seconds
        # of TTFT and would grade the CONFIG for the compiler's sins.
        k = scfg.k_steps or K
        for n_warm in range(min(B, n_req), 0, -1):
            _chained_serve_metrics(engine, prompts[:n_warm], k,
                                   max_new=min(max_new, 2 * k))
        _openloop_drive(engine, scfg, prompts, arrivals, max_new)
        engine.reset_serving_metrics()

    base_scfg = ServingConfig(k_steps=K, slo_ttft_ms=slo.slo_ttft_ms,
                              slo_itl_ms=slo.slo_itl_ms)
    warm(e, base_scfg)
    base_run = _openloop_drive(e, base_scfg, prompts, arrivals, max_new)
    del e
    gc.collect()
    e2 = InferenceEngineV2(model, plan.engine_config())
    srv_dict = plan.apply().get("serving", {})
    plan_scfg = ServingConfig(**{**srv_dict,
                                 "slo_ttft_ms": slo.slo_ttft_ms,
                                 "slo_itl_ms": slo.slo_itl_ms})
    warm(e2, plan_scfg)
    plan_run = _openloop_drive(e2, plan_scfg, prompts, arrivals,
                               max_new)
    del e2
    gc.collect()

    def pct(xs, q):
        if not xs:
            return None
        xs = sorted(xs)
        return round(xs[min(len(xs) - 1, int(len(xs) * q))], 2)

    out["baseline_goodput_rps"] = round(base_run["goodput_rps"], 3)
    out["plan_goodput_rps"] = round(plan_run["goodput_rps"], 3)
    out["value"] = out["serving_plan_vs_baseline"] = round(
        plan_run["goodput_rps"] / max(base_run["goodput_rps"], 1e-9), 4)
    out["baseline_ttft_p99_ms"] = pct(base_run["ttft"], 0.99)
    out["plan_ttft_p99_ms"] = pct(plan_run["ttft"], 0.99)
    out["baseline_itl_p99_ms"] = pct(base_run["itl"], 0.99)
    out["plan_itl_p99_ms"] = pct(plan_run["itl"], 0.99)
    out["plan_shed"] = plan_run["shed"]
    # stamp the measured truth onto the chosen row and re-save, so
    # tools/autotune_report.py renders predicted vs measured
    chosen = plan.chosen
    if chosen is not None:
        chosen["measured_goodput_rps"] = out["plan_goodput_rps"]
        chosen["measured_ttft_p99_ms"] = out["plan_ttft_p99_ms"]
        chosen["measured_itl_p99_ms"] = out["plan_itl_p99_ms"]
        plan.save(path)
        out["chosen_patch"] = plan.chosen_patch
    del planner, plan
    gc.collect()
    return out


def disagg_bench(ds, on_tpu: bool):
    """Disaggregated serving (ISSUE 13): two acceptance figures.

    (A) Decode-ITL flatness under long-prompt pressure — mixed chat +
    long-prompt traffic, measured twice as the long prompts grow 10x:
    against a single co-located engine (long-prompt chunked prefill
    steals decode ticks at every dispatch boundary, so chat ITL p99
    degrades) and against the prefill/decode split (long prompts
    prefill on the dedicated engine and migrate in as KV block sets —
    decode ticks undisturbed, ITL p99 flat).

    (B) N-replica scaling behind the prefix-affinity router —
    aggregate tokens/s on N=2 replicas at the same per-replica offered
    load vs the single-replica figure (`replica_scaling_x`, acceptance
    >= 0.8), with per-replica placements and prefix hit rates (the
    shared-system-prompt wave lands on the replica holding the chain
    warm)."""
    import asyncio

    import numpy as np
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Llama
    from deepspeed_tpu.serving import (AsyncInferenceServer,
                                       InferenceRouter, PrefillEngine,
                                       RouterConfig, ServingConfig)

    if on_tpu:
        model = Llama(hidden_size=1024, num_layers=12, num_heads=8,
                      num_kv_heads=8, intermediate_size=2816,
                      vocab_size=32000, max_seq_len=4096)
        bs_kv, nb, chunk, B, K = 64, 384, 256, 16, 8
        chat_len, chat_new, n_chat = 64, 64, 12
        long_lens, long_new, n_long, long_gap = (256, 2560), 8, 4, 0.2
        scale_req, scale_new, scale_k, scale_rps = 24, 64, 8, 4.0
    else:
        # big enough that prefill is real COMPUTE (a 320-token prompt's
        # chunked prefill stalls decode for many chain gaps), small
        # enough that the stall windows stay short relative to the run
        # — on this 2-core rig the prefill "mesh" shares silicon with
        # decode, so an oversized model turns the A comparison into a
        # pure CPU-contention measurement (a TPU deployment puts the
        # prefill engine on its own chips)
        model = Llama(size="tiny", hidden_size=128, num_layers=3,
                      num_heads=4, num_kv_heads=4,
                      intermediate_size=344, vocab_size=2048,
                      max_seq_len=512)
        bs_kv, nb, chunk, B, K = 8, 192, 32, 8, 4
        chat_len, chat_new, n_chat = 16, 16, 6
        long_lens, long_new, n_long, long_gap = (32, 320), 4, 3, 0.2
        # deeper fused K for the scaling runs: host work per token is
        # the 2-core rig's scaling ceiling, and K amortizes it
        scale_req, scale_new, scale_k, scale_rps = 12, 32, 16, 2.5
    dtype = "bfloat16" if on_tpu else "float32"

    def mk(params=None):
        return InferenceEngineV2(model, RaggedInferenceEngineConfig(
            dtype=dtype, kv_block_size=bs_kv, num_kv_blocks=nb,
            max_chunk_size=chunk, max_ragged_sequence_count=B,
            fused_decode_steps=K, prefix_cache={"enabled": True}),
            params=params)

    e_single = mk()
    params = e_single.params
    e_pre, e_d0, e_d1 = mk(params), mk(params), mk(params)
    rng = np.random.default_rng(0)
    vocab = model.config.vocab_size

    def prompts(n, length):
        return [rng.integers(0, vocab, length).tolist()
                for _ in range(n)]

    # ---- (A) chat ITL p99 vs long-prompt length, single vs disagg ----
    chat_prompts = prompts(n_chat, chat_len)

    async def mixed_run(router, long_len):
        itls: list[float] = []
        longs = prompts(n_long, long_len)

        async def chat(i):
            h = await router.submit(chat_prompts[i],
                                    max_new_tokens=chat_new)
            prev = None
            async for _t in h:
                now = time.perf_counter()
                if prev is not None:
                    itls.append((now - prev) * 1e3)
                prev = now

        async def long_stream():
            for p in longs:
                await asyncio.sleep(long_gap)
                h = await router.submit(p, max_new_tokens=long_new)
                await h.tokens()

        async with router:
            await asyncio.gather(long_stream(),
                                 *(chat(i) for i in range(n_chat)))
        return itls

    def pct(xs, q):
        if not xs:
            return None
        xs = sorted(xs)
        return round(xs[min(len(xs) - 1, int(len(xs) * q))], 2)

    def single_router():
        return InferenceRouter(
            [AsyncInferenceServer(e_single, ServingConfig(k_steps=K))],
            RouterConfig())

    def disagg_router():
        return InferenceRouter(
            [AsyncInferenceServer(e_d0, ServingConfig(k_steps=K))],
            RouterConfig(disaggregation={
                "enabled": True,
                # chat stays co-located; long prompts migrate
                "prefill_threshold_tokens": chat_len + 1}),
            prefill=PrefillEngine(e_pre, name="prefill0"))

    itl: dict[str, dict[int, float]] = {"single": {}, "disagg": {}}
    migrate_bytes = migrate_blocks = handoffs = 0
    for mode, mk_router in (("single", single_router),
                            ("disagg", disagg_router)):
        # warm pass (compiles prefill buckets + the serve loop) at the
        # SHORT length, outside every measured window
        asyncio.run(mixed_run(mk_router(), long_lens[0]))
        for L in long_lens:
            # best-of-2 windows per point (noisy-rig discipline)
            best = None
            for _ in range(2):
                router = mk_router()
                p99 = pct(asyncio.run(mixed_run(router, L)), 0.99)
                best = p99 if best is None else min(best, p99)
                if mode == "disagg":
                    pm = router.prefill.metrics()
                    migrate_bytes += pm["exported_bytes"]
                    migrate_blocks += pm["exported_blocks"]
                    handoffs += pm["prefills"]
            itl[mode][L] = best
    l0, l1 = long_lens
    single_drift = itl["single"][l1] / max(itl["single"][l0], 1e-6)
    disagg_drift = itl["disagg"][l1] / max(itl["disagg"][l0], 1e-6)
    # migration byte economics: the hand-off moves KV blocks in their
    # storage format — bytes/token rides kv_bytes_per_token exactly
    # (quantized engines migrate quantized; no dequantize leg)
    migrate_bpt = (migrate_bytes / max(migrate_blocks * bs_kv, 1)
                   if migrate_blocks else None)

    # ---- (B) N-replica scaling + per-replica prefix hit rates --------
    shared = rng.integers(0, vocab, 2 * bs_kv).tolist()

    def scale_prompts(n):
        # half shared-system-prompt traffic (the affinity key), half
        # unique chat
        out = []
        for i in range(n):
            if i % 2 == 0:
                out.append(shared
                           + rng.integers(0, vocab, 4).tolist())
            else:
                out.append(rng.integers(0, vocab, chat_len).tolist())
        return out

    async def scale_run(engines, rounds=2):
        """Open-loop Poisson traffic (the serve_openloop discipline)
        at ``scale_rps`` requests/s PER REPLICA: N replicas face N x
        the single-replica offered load, and sustained aggregate
        tokens/s is the scaling figure — best-of-``rounds`` windows
        after one closed-loop warm wave (compiles + prefix-cache
        seed), TTFT p99 reported so 'sustained' is checkable (a
        saturated config shows up as queue growth there first)."""
        servers = [AsyncInferenceServer(
            e, ServingConfig(k_steps=scale_k)) for e in engines]
        router = InferenceRouter(servers, RouterConfig())
        n = scale_req * len(engines)
        rate = scale_rps * len(engines)

        async def warm():
            hs = [await router.submit(p, max_new_tokens=scale_new)
                  for p in scale_prompts(n)]
            for h in hs:
                await h.tokens()

        async def openloop_window():
            reqs = scale_prompts(n)
            arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
            ttfts: list[float] = []

            async def client(i):
                await asyncio.sleep(float(arrivals[i]))
                t_sub = time.perf_counter()
                h = await router.submit(reqs[i],
                                        max_new_tokens=scale_new)
                toks = []
                async for t in h:
                    if not toks:
                        ttfts.append((time.perf_counter() - t_sub)
                                     * 1e3)
                    toks.append(t)
                return len(toks)

            for e in engines:
                e.reset_serving_metrics()
            t0 = time.perf_counter()
            counts = await asyncio.gather(*(client(i)
                                            for i in range(n)))
            wall = time.perf_counter() - t0
            return (sum(counts) / max(wall, 1e-9),
                    pct(sorted(ttfts), 0.99))

        async with router:
            await warm()
            best, ttft = 0.0, None
            for _ in range(rounds):
                tps, t99 = await openloop_window()
                if tps > best:
                    best, ttft = tps, t99
            return best, ttft, router.metrics()

    # single replica on the SAME warmed engine the 2-replica run uses,
    # so the comparison is compile-free on both sides
    t1, ttft1, m1 = asyncio.run(scale_run([e_d0]))
    tn, ttftn, mn = asyncio.run(scale_run([e_d0, e_d1]))
    n_rep = 2
    scaling = tn / max(n_rep * t1, 1e-9)
    per_replica = {
        name: {"decoded_tokens": row["decoded_tokens"],
               "placed": row["placed"],
               "prefix_hit_rate": round(row["prefix_hit_rate"], 3)}
        for name, row in mn["replicas"].items()}

    return {"metric": "disagg_chat_itl_p99_ms_at_10x",
            "value": itl["disagg"][l1], "unit": "ms",
            "chat_itl_p99_ms": {m: {str(L): v for L, v in d.items()}
                                for m, d in itl.items()},
            "long_prompt_lens": list(long_lens),
            "single_itl_p99_drift_x10_ratio": round(single_drift, 3),
            "disagg_itl_p99_drift_x10_ratio": round(disagg_drift, 3),
            "itl_flat_under_10x": bool(disagg_drift <= 1.15),
            "prefill_handoffs": handoffs,
            "migrate_bytes_per_token": (round(migrate_bpt, 3)
                                        if migrate_bpt else None),
            "kv_bytes_per_token": round(e_pre.kv_bytes_per_token(), 3),
            "single_replica_tokens_per_sec": round(t1, 1),
            "aggregate_tokens_per_sec_2rep": round(tn, 1),
            "openloop_rps_per_replica": scale_rps,
            "scale_ttft_p99_ms": {"1rep": ttft1, "2rep": ttftn},
            "replica_scaling_x": round(scaling, 3),
            "replicas": n_rep, "per_replica": per_replica,
            "fused_k": K, "requests_per_replica": scale_req}


def fleet_bench(ds, on_tpu: bool):
    """Fleet health plane (ISSUE 17): kill one replica under open-loop
    Poisson load and measure the detection -> reroute incident
    response. Two replicas behind the health-gated router take Poisson
    traffic; mid-window the victim replica's serving loop is killed
    through the supported fault-injection path (``server.kill()`` — a
    real worker death, not a monkeypatch). The stage reports:

    - ``detection_ms`` — kill to the phi-accrual detector marking the
      victim suspect/dead (heartbeat silence, no failure RPC);
    - ``detection_to_reroute_ms`` — kill until BOTH the detector
      tripped and the router rerouted the victim's in-flight requests
      (the drain-and-reroute contract);
    - ``dropped_requests`` — client-visible failures (the acceptance
      bar is ZERO: every in-flight request completes elsewhere);
    - multi-window ``slo_burn_rate_*`` from the time-series ring
      (breaches per request over the fast/slow burn windows spanning
      the incident).

    Gated by ``telemetry_report --gate fleet``. Directly pre-stages
    ROADMAP item 1's acceptance figure."""
    import asyncio

    import numpy as np
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Llama
    from deepspeed_tpu.serving import (AsyncInferenceServer,
                                       InferenceRouter, RouterConfig,
                                       ServingConfig)

    # the stage NEEDS the telemetry plane (detector + ring); own it for
    # the stage when the harness did not pass --telemetry
    owned = not telemetry.is_active()
    if owned:
        telemetry.configure()

    if on_tpu:
        model = Llama(hidden_size=1024, num_layers=12, num_heads=8,
                      num_kv_heads=8, intermediate_size=2816,
                      vocab_size=32000, max_seq_len=2048)
        bs_kv, nb, chunk, B, K = 64, 256, 256, 16, 8
        n_req, rate_rps, p_len, max_new = 32, 8.0, 64, 32
        slo_ttft_ms = 500.0
    else:
        model = Llama(size="tiny", hidden_size=128, num_layers=3,
                      num_heads=4, num_kv_heads=4,
                      intermediate_size=344, vocab_size=2048,
                      max_seq_len=512)
        bs_kv, nb, chunk, B, K = 8, 128, 16, 8, 4
        n_req, rate_rps, p_len, max_new = 32, 8.0, 12, 8
        slo_ttft_ms = 50.0
    dtype = "bfloat16" if on_tpu else "float32"

    def mk(params=None):
        return InferenceEngineV2(model, RaggedInferenceEngineConfig(
            dtype=dtype, kv_block_size=bs_kv, num_kv_blocks=nb,
            max_chunk_size=chunk, max_ragged_sequence_count=B,
            fused_decode_steps=K), params=params)

    e0 = mk()
    e1 = mk(e0.params)
    rng = np.random.default_rng(0)
    vocab = model.config.vocab_size
    prompts = [rng.integers(0, vocab, p_len).tolist()
               for _ in range(n_req)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, n_req))
    # floor: the post-clear detector needs min_heartbeats intervals of
    # in-round cadence before silence can read as suspicion
    kill_at = max(float(arrivals[n_req // 3]), 2.5)
    tel = telemetry  # active by construction above
    incident = {"t_kill": None, "t_detect": None, "t_reroute": None,
                "detect_state": None, "victim_open_at_kill": None}

    def mk_router():
        servers = [AsyncInferenceServer(e, ServingConfig(
            k_steps=K, slo_ttft_ms=slo_ttft_ms)) for e in (e0, e1)]
        # tighter-than-default phi thresholds: the bench WANTS an
        # aggressive detector (it measures incident response, and a
        # false trip would show up as health_skips + a flapping state,
        # both reported)
        return servers, InferenceRouter(servers, RouterConfig(
            health={"phi_suspect": 2.0, "phi_dead": 5.0}))

    async def run(servers, router, kill: bool):
        results = {"done": 0, "dropped": 0, "tokens": 0}
        victim = servers[0].config.replica
        hm = tel.get_health_monitor()

        async def client(i):
            await asyncio.sleep(float(arrivals[i]))
            try:
                h = await router.submit(prompts[i],
                                        max_new_tokens=max_new)
                results["tokens"] += len(await h.tokens())
                results["done"] += 1
            except Exception:   # noqa: BLE001 — a drop IS the figure
                results["dropped"] += 1

        async def killer():
            await asyncio.sleep(kill_at)
            incident["t_kill"] = time.perf_counter()
            victim_open = servers[0].open_requests
            incident["victim_open_at_kill"] = victim_open
            servers[0].kill()
            deadline = incident["t_kill"] + 60.0
            # detection: heartbeat silence alone must trip the
            # detector (no failure notification is consulted)
            while hm.state(victim) not in ("suspect", "dead") \
                    and time.perf_counter() < deadline:
                await asyncio.sleep(0.002)
            if hm.state(victim) in ("suspect", "dead"):
                incident["t_detect"] = time.perf_counter()
                incident["detect_state"] = hm.state(victim)
            # reroute: the victim's in-flight requests resubmitted
            # elsewhere (drain-and-reroute); nothing to wait for if
            # the victim happened to be empty at the kill
            while victim_open and router.stats["reroutes"] == 0 \
                    and time.perf_counter() < deadline:
                await asyncio.sleep(0.002)
            if not victim_open or router.stats["reroutes"]:
                incident["t_reroute"] = time.perf_counter()

        async with router:
            t0 = time.perf_counter()
            jobs = [client(i) for i in range(n_req)]
            if kill:
                jobs.append(killer())
            await asyncio.gather(*jobs)
            wall = time.perf_counter() - t0
            return results, wall, router.metrics()

    try:
        # warm wave (compiles + detector cadence history), no kill
        servers, router = mk_router()
        asyncio.run(run(servers, router, kill=False))
        rt = tel.get_request_recorder()
        if rt is not None:
            rt.clear()
        ts = tel.get_timeseries()
        if ts is not None:
            ts.clear()
        # fresh detector cadence for the measured round: the warm
        # round's replicas answered to the same names, and the
        # inter-round setup gap would poison their interval history
        # (an inflated mean interval inflates detection latency)
        tel.get_health_monitor().clear()

        servers, router = mk_router()
        results, wall, m = asyncio.run(run(servers, router, kill=True))

        burn = {}
        if ts is not None:
            for win, rate in ts.multi_window_burn(
                    "ds_serving_slo_",
                    "ds_serving_requests_total").items():
                burn[f"slo_burn_rate_{win}"] = round(rate, 4)
        t_kill = incident["t_kill"]
        detection_ms = (
            round((incident["t_detect"] - t_kill) * 1e3, 1)
            if incident["t_detect"] else None)
        reroute_ms = (
            round((max(incident["t_reroute"], incident["t_detect"])
                   - t_kill) * 1e3, 1)
            if incident["t_reroute"] and incident["t_detect"] else None)
        survivors = [n for n, s in m.get("health", {}).items()
                     if s not in ("suspect", "dead")]
        placed = [m["replicas"][n]["placed"] for n in survivors
                  if n in m.get("replicas", {})]
        skew = (round(max(placed) / (sum(placed) / len(placed)), 3)
                if placed else None)
        return {"metric": "fleet_detection_to_reroute_ms",
                "value": reroute_ms, "unit": "ms",
                "detection_ms": detection_ms,
                "detection_state": incident["detect_state"],
                "requests": n_req, "completed": results["done"],
                "victim_open_at_kill": incident["victim_open_at_kill"],
                "dropped_requests": results["dropped"],
                "zero_drops": bool(results["dropped"] == 0),
                "reroutes": m["reroutes"],
                "health_skips": m["health_skips"],
                "replica_skew": skew,
                "health_states": m.get("health", {}),
                "tokens_per_sec": round(results["tokens"]
                                        / max(wall, 1e-9), 1),
                "arrival_rate_rps": rate_rps,
                "slo_ttft_ms_target": slo_ttft_ms, **burn}
    finally:
        if owned:
            telemetry.shutdown()


def serving_bench(ds, on_tpu: bool):
    """Serving class (BASELINE configs 1-2 / FastGen): greedy batch
    decode on the Llama-340M-class model. Reports the v1 engine's
    compiled decode loop (the CUDA-graph analogue — one dispatch per
    batch); the v2 per-tick scheduler pays one host round trip per
    tick, and its tick RTT is reported beside it."""
    import numpy as np
    from deepspeed_tpu.models import Llama
    if on_tpu:
        model = Llama(hidden_size=1024, num_layers=12, num_heads=8,
                      num_kv_heads=8, intermediate_size=2816,
                      vocab_size=32000, max_seq_len=2048)
        B, P, N = 24, 256, 64
    else:
        model = Llama(size="tiny", max_seq_len=256)
        B, P, N = 2, 16, 4
    e = ds.init_inference(model, dtype="bfloat16" if on_tpu else "float32",
                          max_out_tokens=1024 if on_tpu else 64)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, model.config.vocab_size,
                                       size=(B, P)))
    np.asarray(e.generate(prompts, max_new_tokens=N))   # warmup/compile
    np.asarray(e.generate(prompts, max_new_tokens=1))   # warm 1-token

    def v1_pair(reps):
        """(full-decode, one-token) wall times, best-of-reps each —
        their difference isolates (N-1) compiled decode steps."""
        dt_ = dt1_ = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = e.generate(prompts, max_new_tokens=N)
            np.asarray(out)
            dt_ = min(dt_, time.perf_counter() - t0)
            t0 = time.perf_counter()
            out1 = e.generate(prompts, max_new_tokens=1)
            np.asarray(out1)
            dt1_ = min(dt1_, time.perf_counter() - t0)
        return dt_, dt1_

    dt, dt1 = v1_pair(3 if on_tpu else 1)
    # v2 scheduler tick RTT (one bucketed decode tick through put())
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    e2 = InferenceEngineV2(model, RaggedInferenceEngineConfig(
        dtype="bfloat16" if on_tpu else "float32", kv_block_size=64,
        num_kv_blocks=256, max_chunk_size=256))
    n = min(24, B)
    uids = list(range(n))
    # same prompt length as the v1 decode measurement so the two
    # per-step figures compare at matched context
    e2.put(uids, [prompts[i].tolist() for i in range(n)])

    def one_tick():
        e2.schedule(uids, [[1]] * n, do_checks=False)
        res = e2.tick()
        # decode ticks finish every sequence's single pending token, so
        # res is non-empty; the float() forces a device->host sync
        float(jnp.sum(next(iter(res.values()))))

    p50, p99 = _tick_percentiles(one_tick, 24 if on_tpu else 4)
    # compute-basis per-token step time from the COMPILED decode loop:
    # marginal cost of (N-1) extra decode steps, so prefill + dispatch
    # are subtracted out. The host-in-loop v2 tick p50/p99 above
    # additionally pays the host round trip per tick.
    decode_step_ms = max(dt - dt1, 1e-9) / max(N - 1, 1) * 1e3

    # v2 paged-step device time (the paged kernel reads only LIVE
    # pages, vs the v1 static cache scanning all max_out_tokens slots —
    # the FastGen memory-read advantage at realistic context lengths)
    make_chain, args = _decode_chain_setup(model, e2, uids,
                                           use_kernel=on_tpu)
    long_n, short_n = (64, 8) if on_tpu else (4, 2)
    chain_l, chain_s = make_chain(long_n), make_chain(short_n)
    pools = e2.pools
    for c in (chain_l, chain_s):                       # compile + warm
        lgs, pools = c(e2.params, pools, *args)
        float(jnp.sum(lgs))

    def chain_pair_ms(params, pools, args, reps=3):
        return _chain_pair_ms(chain_l, chain_s, params, pools, args,
                              long_n, short_n, reps)

    # paired windows: each window measures the v1 step AND the paged
    # step back-to-back, so host-timing drift hits both sides alike;
    # the per-window delta distribution carries the claim (CI95 must
    # exclude zero — VERDICT r4 #7)
    n_windows = 5 if on_tpu else 2
    v1_steps, v2_steps = [], []
    for _ in range(n_windows):
        w_dt, w_dt1 = v1_pair(2 if on_tpu else 1)
        v1_steps.append(max(w_dt - w_dt1, 1e-9) / max(N - 1, 1) * 1e3)
        ms, pools = chain_pair_ms(e2.params, pools, args,
                                  reps=2 if on_tpu else 1)
        v2_steps.append(ms)
    deltas = [a - b for a, b in zip(v1_steps, v2_steps)]
    d_mean, d_ci = _mean_ci95(deltas)
    v2_step_ms = sorted(v2_steps)[len(v2_steps) // 2]   # median window

    # short-context check (paged must also still win where it already
    # did): same differencing at ~32-token contexts
    short = {}
    if on_tpu:
        e3 = InferenceEngineV2(model, RaggedInferenceEngineConfig(
            dtype="bfloat16", kv_block_size=64, num_kv_blocks=256,
            max_chunk_size=256))
        e3.put(uids, [prompts[i, :32].tolist() for i in range(n)])
        _, args3 = _decode_chain_setup(model, e3, uids, use_kernel=True)
        pools3 = e3.pools
        for c in (chain_l, chain_s):
            lgs, pools3 = c(e3.params, pools3, *args3)
            float(jnp.sum(lgs))
        ms3, pools3 = chain_pair_ms(e3.params, pools3, args3)
        short["v2_paged_step_ms_32ctx"] = round(ms3, 2)

    # fused multi-step decode (ISSUE 1): K ticks per host dispatch with
    # in-graph sampling + termination — the tick RTT is paid once per K
    # tokens, so the per-token figure collapses toward the compute
    # floor. e2 is reused (flush releases the tick-grown sequences);
    # the chain measurements above never donate e2.pools
    e2.flush(uids)
    fused = _fused_decode_metrics(
        e2, [prompts[i].tolist() for i in range(n)],
        k=8 if on_tpu else 4, n_dispatches=12 if on_tpu else 3)

    # the SLA comes from ServingConfig (ISSUE 19 satellite: the gate
    # and the config must agree), not a literal in this stage
    slo_ms = _bench_serving_slo().slo_itl_ms
    return {"metric": "serving_decode_tokens_per_sec",
            **short, **fused,
            "value": round(B * N / dt, 1), "unit": "tokens/s/chip",
            "batch": B, "with_prefill": round(B * (N + P) / dt, 1),
            "decode_step_ms_compute": round(decode_step_ms, 2),
            "v1_step_ms_windows": [round(x, 2) for x in v1_steps],
            "v2_step_ms_windows": [round(x, 2) for x in v2_steps],
            "v1_minus_paged_delta_ms": round(d_mean, 3),
            "paged_delta_ci95_ms": round(d_ci, 3),
            # claimed only when the paired-window CI excludes zero
            "paged_wins": bool(d_mean - d_ci > 0),
            "v2_paged_step_ms_compute": round(v2_step_ms, 2),
            "v2_paged_tokens_per_sec_compute": round(
                n * 1e3 / v2_step_ms, 1),
            "v2_tick_p50_ms": round(p50, 1),
            "v2_tick_p99_ms": round(p99, 1),
            "slo_ms": slo_ms,
            "tokens_per_sec_at_slo": round(
                B * 1e3 / max(decode_step_ms, slo_ms), 1)}


def prefix_bench(ds, on_tpu: bool):
    """Automatic prefix caching (ISSUE 4): shared-system-prompt serving.

    N requests share a long system prefix and differ only in a short
    unique tail. Served sequentially against (a) a cache-disabled
    engine and (b) a prefix-cache engine whose first request warms the
    chain, the cached path must cut prefill tokens >=50% and TTFT with
    it. TTFT here is the put() wall time — prefill through first-token
    logits — the exact cost prefix reuse removes. The warm engine's
    ``max_cached_blocks`` is sized so unique tail blocks churn through
    the LRU, exercising (and reporting) eviction."""
    import numpy as np
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Llama
    if on_tpu:
        model = Llama(hidden_size=1024, num_layers=12, num_heads=8,
                      num_kv_heads=8, intermediate_size=2816,
                      vocab_size=32000, max_seq_len=2048)
        bs, nb, chunk = 64, 256, 256
        shared_len, uniq_len, n_req = 1024, 64, 8
    else:
        model = Llama(size="tiny", max_seq_len=256)
        bs, nb, chunk = 8, 128, 16
        shared_len, uniq_len, n_req = 64, 8, 6
    shared_blocks = shared_len // bs
    rng = np.random.default_rng(0)
    vocab = model.config.vocab_size
    shared = rng.integers(0, vocab, shared_len).tolist()
    prompts = [shared + rng.integers(0, vocab, uniq_len).tolist()
               for _ in range(n_req)]

    def serve(enabled):
        e = InferenceEngineV2(model, RaggedInferenceEngineConfig(
            dtype="bfloat16" if on_tpu else "float32",
            kv_block_size=bs, num_kv_blocks=nb, max_chunk_size=chunk,
            prefix_cache={"enabled": enabled, "min_match_blocks": 1,
                          "max_cached_blocks": shared_blocks + 4}))
        # warming request: compiles the prefill buckets on both engines
        # and (cache on) seeds the shared chain — excluded from timing
        e.put([10 ** 6], [prompts[0]])
        e.flush(10 ** 6)
        e.reset_serving_metrics()
        ttfts = []
        for i, p in enumerate(prompts):
            t0 = time.perf_counter()
            lg = e.put([i], [p])
            float(jnp.max(lg))           # force the device->host sync
            ttfts.append((time.perf_counter() - t0) * 1e3)
            e.flush(i)
        ttfts.sort()
        p50 = ttfts[len(ttfts) // 2]
        p99 = ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))]
        return p50, p99, e.serving_metrics()

    cold_p50, cold_p99, cold_m = serve(False)
    warm_p50, warm_p99, warm_m = serve(True)
    # mirror the cache counters into the telemetry registry (the put()
    # prefill path has no fused dispatch to flush them) so the stage's
    # --telemetry artifacts carry ds_serving_prefix_* series
    from deepspeed_tpu.utils.telemetry_probe import active_telemetry
    tel = active_telemetry()
    reg = tel.get_registry() if tel is not None else None
    if reg is not None:
        tel.bridges.collect_serving(reg, warm_m)
    total_prompt_tokens = sum(len(p) for p in prompts)
    reduction = warm_m["prefill_tokens_saved"] / total_prompt_tokens
    return {"metric": "prefix_cache_warm_ttft_p50_ms",
            "value": round(warm_p50, 2), "unit": "ms",
            "ttft_cold_p50_ms": round(cold_p50, 2),
            "ttft_cold_p99_ms": round(cold_p99, 2),
            "ttft_warm_p99_ms": round(warm_p99, 2),
            "ttft_speedup_p50": round(cold_p50 / max(warm_p50, 1e-9), 2),
            "prefill_token_reduction": round(reduction, 3),
            "prefill_tokens_saved": warm_m["prefill_tokens_saved"],
            "prompt_tokens_total": total_prompt_tokens,
            "prefix_hits": warm_m["prefix_hits"],
            "prefix_misses": warm_m["prefix_misses"],
            "prefix_hit_rate": round(warm_m["prefix_hit_rate"], 3),
            "prefix_evictions": warm_m["prefix_evictions"],
            "prefix_cached_blocks": warm_m["prefix_cached_blocks"],
            "shared_prefix_tokens": shared_len, "requests": n_req}


def spec_bench(ds, on_tpu: bool):
    """Speculative decoding (ISSUE 9): prompt-lookup drafting + the
    in-graph 1+draft_len verify on a repetitive decode workload.

    The workload decodes LONG greedy continuations: past a short
    burn-in, greedy decode settles into a repeating cycle — the extreme
    form of the agentic/templated traffic PLD targets (tool-call
    loops, JSON scaffolds, copied context), where the continuation is
    predictable from the row's own recent history. Spec-on and
    spec-off runs share the model/engine config and greedy sampling,
    and the stage asserts BIT-PARITY of outputs before reporting any
    number — speculation may only change how many tokens land per
    forward, never which tokens.

    Gated via ``telemetry_report --diff --gate serving``:
    ``spec_tokens_per_sec`` / ``tokens_per_sec_spec_off`` (+1),
    ``acceptance_rate`` (+1), ``tokens_per_dispatch`` — mean tokens
    COMMITTED per scheduled (row, tick) slot, the >1.5 acceptance
    figure — (+1), and ``spec_overhead_ms`` (-1): p50 per-dispatch
    wall of a spec-ON engine on a SHORT non-repetitive workload where
    drafts essentially never land, i.e. the full price of drafting +
    the widened verify forward with no speculation win to hide it
    (``spec_overhead_delta_ms``, the difference vs spec-off on the
    same workload, rides along un-gated — on a compute-bound CPU rig
    it is real and positive; dispatch-bound TPU serving is where it
    vanishes)."""
    import numpy as np
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Llama
    if on_tpu:
        model = Llama(hidden_size=1024, num_layers=12, num_heads=8,
                      num_kv_heads=8, intermediate_size=2816,
                      vocab_size=32000, max_seq_len=2048)
        bs, nb, chunk = 64, 512, 256
        B, P, N = 8, 64, 768
    else:
        # long horizon on purpose: the tiny random-weight model needs a
        # burn-in (~150 ticks here) before its greedy continuation
        # settles into the cycle the drafter feeds on, and the stage
        # must measure mostly steady state (a production agentic
        # workload is repetitive from the first tool echo, not after a
        # burn-in)
        model = Llama(size="tiny", max_seq_len=768)
        bs, nb, chunk = 8, 512, 32
        B, P, N = 4, 16, 720
    K, L = 4, 6
    spec_cfg = {"enabled": True, "draft_len": L, "min_ngram": 2,
                "history_window": 64}
    rng = np.random.default_rng(0)
    vocab = model.config.vocab_size
    prompts = [rng.integers(0, vocab, P).tolist() for _ in range(B)]

    def eng(spec_on):
        return InferenceEngineV2(model, RaggedInferenceEngineConfig(
            dtype="bfloat16" if on_tpu else "float32",
            kv_block_size=bs, num_kv_blocks=nb, max_chunk_size=chunk,
            speculative={**spec_cfg, "enabled": spec_on}))

    def run(spec_on):
        e = eng(spec_on)
        e.generate_fused(prompts, max_new_tokens=2 * K,
                         k_steps=K)                  # compile the path
        e.reset_serving_metrics()
        t0 = time.perf_counter()
        out = e.generate_fused(prompts, max_new_tokens=N, k_steps=K)
        wall = time.perf_counter() - t0
        return out, wall, e.serving_metrics()

    out_off, wall_off, m_off = run(False)
    out_on, wall_on, m_on = run(True)
    assert out_on == out_off, "speculative greedy output diverged"
    n_tok = sum(len(o) for o in out_on)
    tps_on = n_tok / max(wall_on, 1e-9)
    tps_off = n_tok / max(wall_off, 1e-9)

    # draft-miss overhead probe: SHORT random continuations (burn-in
    # regime, no cycle for the n-gram index to hit) through the raw
    # fused-decode dispatch, spec-on vs spec-off
    ov_on = _fused_decode_metrics(eng(True), prompts, k=K,
                                  n_dispatches=6)
    ov_off = _fused_decode_metrics(eng(False), prompts, k=K,
                                   n_dispatches=6)

    # mirror the serving counters into the live registry so the
    # stage's --telemetry artifacts carry the ds_serving_spec_* series
    from deepspeed_tpu.utils.telemetry_probe import active_telemetry
    tel = active_telemetry()
    reg = tel.get_registry() if tel is not None else None
    if reg is not None:
        tel.bridges.collect_serving(reg, m_on)
    return {"metric": "spec_decode_tokens_per_sec",
            "value": round(tps_on, 1), "unit": "tokens/s/chip",
            "spec_tokens_per_sec": round(tps_on, 1),
            "tokens_per_sec_spec_off": round(tps_off, 1),
            "speedup_vs_spec_off": round(tps_on / max(tps_off, 1e-9),
                                         2),
            "greedy_parity": True,
            "acceptance_rate": round(m_on["spec_acceptance_rate"], 3),
            "tokens_per_dispatch": round(m_on["tokens_per_dispatch"],
                                         3),
            "spec_proposed_tokens": m_on["spec_proposed_tokens"],
            "spec_accepted_tokens": m_on["spec_accepted_tokens"],
            "spec_hit_slots": m_on["spec_hit_slots"],
            "spec_overhead_ms": ov_on["fused_tick_p50_ms"],
            "spec_overhead_delta_ms": round(
                ov_on["fused_tick_p50_ms"]
                - ov_off["fused_tick_p50_ms"], 2),
            "draft_len": L, "min_ngram": 2, "k_steps": K,
            "batch": B, "new_tokens": N,
            "decoded_tokens": n_tok}


def kvquant_bench(ds, on_tpu: bool):
    """Quantized KV cache (ISSUE 12): int8 pools with per-vector
    scales, dequant fused into the paged-decode attention.

    Four figures, each against an UNQUANTIZED engine of the same model
    and compute dtype:

    - ``max_resident_batch`` (gated +1): concurrent (prompt + budget)
      requests the pool admits at EQUAL KV pool bytes — the quantized
      allocator is sized in quantized bytes, so the same HBM budget
      holds proportionally more blocks (the 2-4x resident-requests
      headline; exact ratio = full-precision over quantized
      bytes/token, reported as ``resident_batch_ratio``).
    - ``kv_bytes_per_token`` (gated -1): storage cost per cached token
      in the active format (deterministic layout arithmetic).
    - ``tokens_per_sec_int8`` vs ``tokens_per_sec_fp`` (equal pool
      bytes) and ``tokens_per_sec_fp_equal_blocks`` (a full-precision
      pool with the SAME block count the quantized pool holds, i.e.
      what matching the quantized engine's resident capacity costs
      unquantized): greedy fused decode at matched batch. CAVEAT (CPU
      rig): interpret-mode Pallas pays a pool-BYTES-proportional
      emulation cost per dispatch plus emulated dequant multiplies, so
      int8-vs-fp at equal bytes reads SLOWER here — the honest CPU
      figure is the equal-blocks one (same resident capacity: the
      int8 pool is ~2x faster AND 3-4x smaller). On TPU the dequant
      is an in-register VPU multiply against halved-to-quartered pool
      HBM traffic; re-baseline there.
    - accuracy: ``greedy_parity_horizon`` — tokens until the first
      greedy divergence vs the fp pool (min over the batch; the
      horizon the ISSUE pins) — and ``spec_acceptance_delta``: the
      prompt-lookup acceptance rate must move <2% absolute when the
      verify forward reads quantized KV (speculation reads the same
      pool as plain decode, so the drafter/acceptance machinery sees
      quantization only through the logits)."""
    import numpy as np
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Llama
    if on_tpu:
        model = Llama(hidden_size=1024, num_layers=12, num_heads=8,
                      num_kv_heads=8, intermediate_size=2816,
                      vocab_size=32000, max_seq_len=2048)
        bs, nb, chunk = 64, 128, 256
        B, P, N, K = 8, 128, 64, 8
        n_spec = 512
    else:
        model = Llama(size="tiny", max_seq_len=768)
        bs, nb, chunk = 8, 128, 32
        B, P, N, K = 4, 16, 32, 4
        n_spec = 320
    dtype = "bfloat16" if on_tpu else "float32"
    rng = np.random.default_rng(0)
    vocab = model.config.vocab_size
    prompts = [rng.integers(0, vocab, P).tolist() for _ in range(B)]

    def eng(quant, grow=True, **over):
        kv = ({"enabled": True, "dtype": "int8", "grow_pool": grow}
              if quant else {"enabled": False})
        kw = dict(dtype=dtype, kv_block_size=bs, num_kv_blocks=nb,
                  max_chunk_size=chunk, max_ragged_sequence_count=64,
                  kv_cache=kv)
        kw.update(over)
        return InferenceEngineV2(model,
                                 RaggedInferenceEngineConfig(**kw))

    e_fp = eng(False)
    e_q = eng(True)
    # equal-budget accounting: the quantized pool must not exceed the
    # fp pool's bytes while holding more blocks
    assert e_q.kv_pool_bytes() <= e_fp.kv_pool_bytes(), \
        (e_q.kv_pool_bytes(), e_fp.kv_pool_bytes())
    bpr = -(-(P + N) // bs)          # blocks one resident request pins
    resident_fp = e_fp.num_kv_blocks // bpr
    resident_q = e_q.num_kv_blocks // bpr
    ratio = resident_q / max(resident_fp, 1)
    if dtype == "float32":
        # CPU rig: fp32 -> int8(+scales) is >= 2x by construction; a
        # regression here means the scale layout grew
        assert ratio >= 2.0, (resident_q, resident_fp)

    def timed_decode(e):
        """Greedy fused decode at MATCHED batch (both engines hold >= B
        requests): best-of-3 tokens/s over warmed drives."""
        e.generate_fused(prompts, max_new_tokens=2 * K,
                         k_steps=K)                  # compile the path
        e.reset_serving_metrics()
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            out = e.generate_fused(prompts, max_new_tokens=N, k_steps=K)
            wall = time.perf_counter() - t0
            best = max(best, sum(len(o) for o in out) / max(wall, 1e-9))
        return out, best

    out_fp, tps_fp = timed_decode(e_fp)
    out_q, tps_q = timed_decode(e_q)
    # equal-RESIDENT-CAPACITY comparison: a full-precision pool sized
    # to the quantized pool's block count (3-4x the bytes)
    _, tps_fp_big = timed_decode(
        eng(False, num_kv_blocks=e_q.num_kv_blocks)) \
        if e_q.num_kv_blocks != e_fp.num_kv_blocks else (out_fp, tps_fp)
    horizon = min(
        next((i for i, (a, b) in enumerate(zip(of, oq)) if a != b),
             len(of))
        for of, oq in zip(out_fp, out_q))

    # spec acceptance under quantized KV: the spec stage's repetitive
    # long-horizon workload (greedy cycles past burn-in), fp vs int8
    # pools. MANY streams on purpose: per-stream steady-state
    # acceptance depends on which cycle the (slightly different) token
    # stream settles into, so the comparable figure is the average —
    # 12+ streams holds the fp-vs-int8 delta under the 2% acceptance
    # bound (4 streams showed 4% of pure cycle-assignment noise).
    # grow_pool=False: equal block COUNT, so both sides run the same
    # admission schedule and the int8 pool's smaller bytes keep the
    # interpret-mode dispatch affordable.
    b_s, n_s = (8, 384) if on_tpu else (12, n_spec)
    sp_prompts = [rng.integers(0, vocab, P).tolist() for _ in range(b_s)]
    nb_s = -(-(P + n_s) // bs) * b_s

    def spec_accept(quant):
        e = eng(quant, grow=False, num_kv_blocks=nb_s,
                speculative={"enabled": True, "draft_len": 4,
                             "min_ngram": 2})
        e.generate_fused(sp_prompts, max_new_tokens=2 * K, k_steps=K)
        e.reset_serving_metrics()
        e.generate_fused(sp_prompts, max_new_tokens=n_s, k_steps=K)
        return e.serving_metrics()["spec_acceptance_rate"]

    acc_fp = spec_accept(False)
    acc_q = spec_accept(True)

    # mirror the kv gauges into the stage's --telemetry artifacts
    from deepspeed_tpu.utils.telemetry_probe import active_telemetry
    tel = active_telemetry()
    reg = tel.get_registry() if tel is not None else None
    if reg is not None:
        tel.bridges.collect_serving(reg, e_q.serving_metrics())
    return {"metric": "kvquant_max_resident_batch", "value": resident_q,
            "unit": "requests", "kv_dtype": e_q.kv_dtype,
            "max_resident_batch": resident_q,
            "resident_batch_fp": resident_fp,
            "resident_batch_ratio": round(ratio, 2),
            "kv_bytes_per_token": round(e_q.kv_bytes_per_token(), 2),
            "kv_bytes_per_token_fp": round(e_fp.kv_bytes_per_token(), 2),
            "kv_pool_bytes": e_q.kv_pool_bytes(),
            "kv_pool_bytes_fp": e_fp.kv_pool_bytes(),
            "kv_num_blocks": e_q.num_kv_blocks,
            "kv_num_blocks_fp": e_fp.num_kv_blocks,
            "tokens_per_sec_int8": round(tps_q, 1),
            "tokens_per_sec_fp": round(tps_fp, 1),
            "tokens_per_sec_fp_equal_blocks": round(tps_fp_big, 1),
            "greedy_parity_horizon": horizon,
            "decode_horizon": N,
            "spec_acceptance_int8": round(acc_q, 3),
            "spec_acceptance_fp": round(acc_fp, 3),
            "spec_acceptance_delta": round(abs(acc_q - acc_fp), 4),
            "batch": B, "prompt_tokens": P, "k_steps": K}


def moe_serving_bench(ds, on_tpu: bool):
    """MoE serving (reference: inference/v2 cutlass_ops moe_gemm +
    mixed_gemm). Decode MoE is EXPERT-WEIGHT-READ bound: every live
    expert's weights stream from HBM for a handful of tokens, so the
    routing overhead vs a dense model has a floor set by BYTES — for
    this config (8 experts, top-2) the expert tier reads ~8x the dense
    MLP weights, giving a computed bf16 floor ~1.9x at batch 16, which
    is exactly what r3 measured (1.93). The lever that moves the floor
    is weight-only int8 expert quantization (quantize_moe_experts;
    XLA fuses the dequant into the expert GEMM) — both rows are
    measured here. The sort-by-expert grouped dispatch
    (moe_ffn_grouped) exists for reference parity but measured SLOWER
    than the einsum on v5e decode (ragged_dot lowering), so the einsum
    stays the serving default."""
    import numpy as np
    from deepspeed_tpu.models import Llama, Mixtral
    if on_tpu:
        kw = dict(hidden_size=1024, num_layers=12, num_heads=8,
                  num_kv_heads=8, intermediate_size=2816,
                  vocab_size=32000, max_seq_len=2048)
        moe = Mixtral(num_experts=8, moe_top_k=2, **kw)
        dense = Llama(**kw)
        B, P, N = 16, 128, 64
    else:
        moe = Mixtral(size="tiny", max_seq_len=256)
        dense = Llama(size="tiny", max_seq_len=256)
        B, P, N = 2, 16, 4
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, moe.config.vocab_size,
                                       size=(B, P)))

    def make_engine(model, **ikw):
        e = ds.init_inference(model,
                              dtype="bfloat16" if on_tpu else "float32",
                              max_out_tokens=512 if on_tpu else 64,
                              **ikw)
        np.asarray(e.generate(prompts, max_new_tokens=N))  # warm
        return e

    def timed(e, reps):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = e.generate(prompts, max_new_tokens=N)
            np.asarray(out)
            best = min(best, time.perf_counter() - t0)
        return best

    e_bf16 = make_engine(moe)
    e_int8 = make_engine(moe, quantize_moe_experts=True)
    e_dense = make_engine(dense)
    # paired windows (bf16 vs int8 back-to-back; the int8 claim is made
    # only when the per-window delta's CI95 excludes zero — r4 #7)
    n_windows = 5 if on_tpu else 2
    t_bf16, t_int8 = [], []
    for _ in range(n_windows):
        t_bf16.append(timed(e_bf16, 2 if on_tpu else 1))
        t_int8.append(timed(e_int8, 2 if on_tpu else 1))
    dense_t = timed(e_dense, 3 if on_tpu else 1)
    deltas = [a - b for a, b in zip(t_bf16, t_int8)]
    d_mean, d_ci = _mean_ci95(deltas)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    moe_tps = B * N / med(t_bf16)
    moe_q_tps = B * N / med(t_int8)
    dense_tps = B * N / dense_t
    return {"metric": "mixtral_serving_decode_tokens_per_sec",
            "value": round(moe_q_tps, 1), "unit": "tokens/s/chip",
            "batch": B, "dense_equiv_tokens_per_sec": round(dense_tps, 1),
            "routing_overhead": round(dense_tps / max(moe_q_tps, 1e-9), 2),
            "experts_int8": True,
            "bf16_tokens_per_sec": round(moe_tps, 1),
            "bf16_routing_overhead": round(
                dense_tps / max(moe_tps, 1e-9), 2),
            "bf16_s_windows": [round(x, 3) for x in t_bf16],
            "int8_s_windows": [round(x, 3) for x in t_int8],
            "bf16_minus_int8_delta_s": round(d_mean, 4),
            "int8_delta_ci95_s": round(d_ci, 4),
            "int8_wins": bool(d_mean - d_ci > 0)}


def _moe_dispatch_bytes(traffic: dict) -> dict:
    """{axis: bytes} of the MoE dispatch exchange in a FORWARD-only
    trace: all-to-all + reduce-scatter on the token (dp/fsdp/zps) axes.
    Forward-only keeps the figure clean — no grad-transpose collectives
    and (under ZeRO-3) the param gathers are all-gathers, excluded by
    op. The combine all-gather is excluded the same way (its wire stays
    float; the int8 protocol covers dispatched activations only)."""
    out: dict = {}
    for (axis, op), row in traffic.items():
        if op not in ("all_to_all", "reduce_scatter"):
            continue
        if not set(axis.split("+")) <= {"dp", "fsdp", "zps"}:
            continue
        out[axis] = out.get(axis, 0) + row["bytes"]
    return out


def moe_train_bench(ds, on_tpu: bool):
    """Ep-sharded MoE training (ISSUE 16): the Mixtral `ref` config on
    an ep×zps×fsdp mesh with the explicit dispatch/combine exchange
    (runtime/comm/moe_alltoall.py) engaged, meshsan contract in raise
    mode. Reports MFU on ACTIVE-params accounting vs an
    equal-active-params dense model, the HLO-accounted per-axis
    dispatch bytes for the fp32 vs int8 a2a wire (the slow-link cut is
    the acceptance figure, >= 2x at <= 1e-2 loss rel err), and the
    loss trajectory gap between wires.

    Needs >= 8 devices (ep=2 x zps=2 x fsdp=2); smaller hosts
    self-provision a virtual 8-device CPU mesh in a subprocess (the
    zeropp recipe) and relay the child's record."""
    if len(jax.devices()) < 8:
        if os.environ.get("DS_TPU_MOE_TRAIN_CHILD"):
            return {"metric": "moe_train_mfu",
                    "skipped": "virtual mesh provisioning failed"}
        import subprocess
        env = dict(os.environ)
        env["DS_TPU_MOE_TRAIN_CHILD"] = "1"
        env.pop("JAX_PLATFORM_NAME", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--stage", "moe_train"],
            capture_output=True, text=True, timeout=600, env=env)
        for line in proc.stderr.splitlines():
            if line.startswith("# moe_train {"):
                return json.loads(line[len("# moe_train "):])
        raise RuntimeError(
            f"moe_train child produced no record (rc={proc.returncode}): "
            + proc.stderr[-400:])

    from deepspeed_tpu.models import Llama, Mixtral
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.profiling.flops_profiler.profiler import \
        lower_compiled
    from deepspeed_tpu.telemetry import collectives as coll
    seq = 512 if on_tpu else 64
    batch = 8
    steps = 3

    def run(wire: str):
        mesh_mod.reset_topology()
        engine, _, _, _ = ds.initialize(
            model=Mixtral(size="ref", max_seq_len=seq),
            config={"train_batch_size": batch,
                    "optimizer": {"type": "AdamW",
                                  "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 3},
                    "mesh": {"fsdp": -1, "zps": 2, "ep": 2},
                    "moe": {"wire_dtype": wire},
                    "telemetry": {"enabled": True,
                                  "executable_ledger": True},
                    "meshsan": {"enabled": True, "mode": "raise"},
                    "steps_per_print": 10 ** 9})
        assert engine._moe_dispatcher is not None, \
            "ep-sharded dispatcher did not engage"
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (batch, seq + 1), 0,
            engine.module.config.vocab_size)
        data = (tokens[:, :-1], tokens[:, 1:])
        # forward-only HLO walk: the dispatch exchange without the
        # grad-transpose collectives riding the same axes
        compiled = lower_compiled(engine._eval_loss,
                                  engine.state["params"], data)
        disp = _moe_dispatch_bytes(coll.traffic_matrix(
            coll.analyze_hlo(compiled.as_text(), mesh=engine.mesh)))
        losses = [float(engine.train_batch(data)) for _ in range(steps)]
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch(data)
        float(loss)
        tps = steps * batch * seq / (time.perf_counter() - t0)
        cfg = engine.module.config
        mesh_mod.reset_topology()
        return disp, losses, tps, cfg

    fp_disp, fp_losses, fp_tps, moe_cfg = run("fp32")
    q_disp, q_losses, _q_tps, _ = run("int8")
    # slow-link = the dispatch payload NOT on the fast (zps) hop
    slow = lambda d: sum(b for a, b in d.items()  # noqa: E731
                         if set(a.split("+")) != {"zps"})
    fp_slow, q_slow = slow(fp_disp), slow(q_disp)
    wire_cut = fp_slow / q_slow if q_slow else 0.0
    loss_rel = max(abs(a - b) / max(abs(b), 1e-9)
                   for a, b in zip(q_losses, fp_losses))

    # equal-ACTIVE-params dense baseline: top-2 of 8 swiglu experts
    # run per token, so a dense MLP of 2x the expert width matches the
    # active FFN params exactly (router + parked experts excluded)
    c = moe_cfg
    dense = Llama(hidden_size=c.hidden_size, num_layers=c.num_layers,
                  num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                  intermediate_size=c.moe_top_k * c.intermediate_size,
                  vocab_size=c.vocab_size, max_seq_len=seq,
                  tie_embeddings=False)
    mesh_mod.reset_topology()
    dense_tps, _ = _train_tput(
        ds, dense, {"zero_optimization": {"stage": 3},
                    "mesh": {"fsdp": -1, "zps": 2}},
        batch, seq, steps=steps)
    mesh_mod.reset_topology()

    moe_mfu = _mfu_fields(fp_tps, moe_cfg, seq)
    dense_mfu = _mfu_fields(dense_tps, dense.config, seq)
    return {
        "metric": "moe_train_mfu", "value": moe_mfu["mfu"], "unit": "MFU"
                  " (active-params accounting)",
        "moe_mfu": moe_mfu["mfu"],
        "dense_mfu": dense_mfu["mfu"],
        "mfu_vs_dense": round(
            moe_mfu["mfu"] / max(dense_mfu["mfu"], 1e-9), 3),
        "tokens_per_sec": round(fp_tps, 1),
        "dense_tokens_per_sec": round(dense_tps, 1),
        "active_params": moe_cfg.num_active_params(),
        "dense_params": dense.config.num_params(),
        "dispatch_bytes_per_axis": {k: int(v) for k, v in fp_disp.items()},
        "dispatch_bytes_per_axis_int8": {k: int(v)
                                         for k, v in q_disp.items()},
        "dispatch_slow_bytes_fp32": int(fp_slow),
        "dispatch_slow_bytes_int8": int(q_slow),
        "dispatch_wire_cut_slow": round(wire_cut, 2),
        "loss_rel_err_int8_wire": round(loss_rel, 5),
        "losses": [round(x, 5) for x in fp_losses],
        "losses_int8_wire": [round(x, 5) for x in q_losses],
        "meshsan": "green (raise mode)",
    }


def moe_serve_bench(ds, on_tpu: bool):
    """Expert-sharded fused MoE decode (ISSUE 16): the Mixtral `ref`
    config through the v2 paged FUSED decode loop with the grouped
    expert GEMM (moe_ffn_grouped — exact top-k, no capacity padding)
    and weight-only int8 experts, vs (a) the per-tick decode loop of
    the SAME engine (greedy bit-parity is the correctness figure) and
    (b) an equal-ACTIVE-size dense model on the identical rig (the
    throughput step-up figure: int8 experts cut the expert-weight-read
    floor that routing pays). CAVEAT (CPU rig): moe_vs_dense reads < 1
    here — the honest CPU story is that top-2-of-8 experts stream ~4x
    the FFN weight bytes of the equal-active dense twin and interpret-
    mode ragged_dot adds routing overhead that XLA:CPU cannot fuse
    away; int8 experts halving those bytes plus the fused grouped GEMM
    are exactly the TPU levers (MoE per-token FLOPs stay a fraction of
    dense at equal quality), so the step-up figure re-baselines on TPU
    like serve7b. Greedy parity and the int8-expert path are the
    rig-independent claims this stage gates."""
    import numpy as np
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Llama, Mixtral
    if on_tpu:
        moe = Mixtral(size="ref", max_seq_len=2048)
        B, P, N, K = 8, 128, 64, 8
        bs, nb, chunk = 64, 128, 256
    else:
        moe = Mixtral(size="ref", max_seq_len=512)
        B, P, N, K = 2, 16, 24, 4
        bs, nb, chunk = 16, 96, 32
    c = moe.config
    dense = Llama(hidden_size=c.hidden_size, num_layers=c.num_layers,
                  num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                  intermediate_size=c.moe_top_k * c.intermediate_size,
                  vocab_size=c.vocab_size, max_seq_len=c.max_seq_len,
                  tie_embeddings=False)
    dtype = "bfloat16" if on_tpu else "float32"
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, c.vocab_size, P).tolist()
               for _ in range(B)]

    def eng(model, **over):
        kw = dict(dtype=dtype, kv_block_size=bs, num_kv_blocks=nb,
                  max_chunk_size=chunk, max_ragged_sequence_count=16)
        kw.update(over)
        return InferenceEngineV2(model,
                                 RaggedInferenceEngineConfig(**kw))

    e_moe = eng(moe, moe_grouped_dispatch=True,
                quantize_moe_experts=True)
    assert e_moe.model.moe_serving_dispatch is True
    assert "w_up_q" in e_moe.params["layers"]["experts"]
    e_dense = eng(dense)

    def timed_fused(e):
        e.generate_fused(prompts, max_new_tokens=2 * K, k_steps=K)
        best = 0.0
        out = None
        for _ in range(3):
            t0 = time.perf_counter()
            out = e.generate_fused(prompts, max_new_tokens=N, k_steps=K)
            wall = time.perf_counter() - t0
            best = max(best, sum(len(o) for o in out) / max(wall, 1e-9))
        return out, best

    out_fused, moe_tps = timed_fused(e_moe)
    _, dense_tps = timed_fused(e_dense)
    # greedy bit-parity: the fused in-graph loop vs the per-tick
    # scheduler driving the same engine (same model copy, same pools)
    out_tick = e_moe.generate(prompts, max_new_tokens=N)
    horizon = min(
        next((i for i, (a, b) in enumerate(zip(of, ot)) if a != b),
             len(of))
        for of, ot in zip(out_fused, out_tick))
    parity = all(list(of) == list(ot)
                 for of, ot in zip(out_fused, out_tick))
    return {
        "metric": "moe_serve_fused_tokens_per_sec",
        "value": round(moe_tps, 1), "unit": "tokens/s/chip",
        "tokens_per_sec": round(moe_tps, 1),
        "dense_tokens_per_sec": round(dense_tps, 1),
        "moe_vs_dense": round(moe_tps / max(dense_tps, 1e-9), 3),
        "greedy_parity": bool(parity),
        "greedy_parity_horizon": int(horizon),
        "decode_horizon": N,
        "experts_int8": True, "grouped_dispatch": True,
        "batch": B, "prompt_tokens": P, "k_steps": K,
        "active_params": c.num_active_params(),
        "dense_params": dense.config.num_params(),
    }


def serve7b_int8(ds, on_tpu: bool):
    """Serve a 7B on ONE 16 GiB v5e (VERDICT r4 #5; reference serving
    headline: FastGen Llama-2-70B on 4xA100, blogs/deepspeed-fastgen/
    README.md:139, and the ZeRO-Inference weight-quantization recipe).

    Weight-only int8 (linear/quantization.py quantize_dense_params)
    puts the 6.74B-param dense tree at ~6.6 GiB beside a 2 GiB paged
    KV pool. Weights are INITIALIZED ON DEVICE in bf16 and quantized
    leaf-by-leaf with donation (peak HBM ~= bf16 tree + one leaf), so
    nothing model-scale crosses the host link. Reported: decode
    tokens/s from the chain-differenced paged step +
    host-in-loop tick p50/p99 (which include the host round trip)."""
    if not on_tpu:
        return {"metric": "serve7b_int8", "skipped": "cpu rig"}
    import functools as _ft

    import numpy as np
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Llama
    import jax.numpy as jnp

    model = Llama(hidden_size=4096, num_layers=32, num_heads=32,
                  num_kv_heads=32, intermediate_size=11008,
                  vocab_size=32000, max_seq_len=2048, tie_embeddings=False,
                  param_dtype=jnp.bfloat16)
    # generate each leaf ALREADY quantized on device: the full-size
    # bf16 tree never exists in HBM (13.4 GiB + temps + int8 would
    # exceed the 16 GiB chip)
    from deepspeed_tpu.linear.quantization import _q_leaf
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    @_ft.partial(jax.jit, static_argnums=(1,))
    def _rand_q(key, shape):
        w = jax.random.normal(key, shape, jnp.bfloat16) * 0.02
        return _q_leaf(w, jnp.bfloat16)

    @_ft.partial(jax.jit, static_argnums=(1, 2))
    def _rand(key, shape, dtype):
        return jax.random.normal(key, shape, dtype) * 0.02

    from deepspeed_tpu.linear.quantization import quantizable_leaf

    def build(tree, path=()):
        import zlib
        out = {}
        for k, v in tree.items():
            if isinstance(v, dict):
                out[k] = build(v, path + (k,))
                continue
            key = jax.random.fold_in(            # stable across runs
                jax.random.PRNGKey(7),
                zlib.crc32("/".join(path + (k,)).encode()))
            if ("embed" not in path and v.ndim >= 2
                    and quantizable_leaf(v.shape, v.ndim, path)):
                q, s = _rand_q(key, v.shape)
                out[k + "_q"], out[k + "_s"] = q, s
            else:
                out[k] = _rand(key, v.shape, v.dtype)
        return out

    params = build(abstract)
    # decode is WEIGHT-READ bound at this scale (step time ~flat in
    # batch: 19.5 ms at B=8, 18.6 ms at B=12), so batch rides free
    # until the KV pool + weights hit HBM (B=16/88 blocks OOMs).
    # SplitFuse chunk 64: the blocked-flash kernel carries ALL heads per
    # grid block, and 32 heads x 256-token chunks overflow the 16 MiB
    # VMEM scoped allocation (head-split grids are the follow-up)
    B, P = 12, 256
    e2 = InferenceEngineV2(model, RaggedInferenceEngineConfig(
        dtype="bfloat16", kv_block_size=64, num_kv_blocks=64,
        max_chunk_size=64, max_ragged_sequence_count=B), params=params)
    int8_gib = sum(l.size for l in jax.tree.leaves(e2.params)
                   if l.dtype == jnp.int8) / 2 ** 30
    uids = list(range(B))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 32000, P).tolist() for _ in range(B)]
    e2.put(uids, prompts)

    def one_tick():
        e2.schedule(uids, [[1]] * B, do_checks=False)
        res = e2.tick()
        float(jnp.sum(next(iter(res.values()))))

    p50, p99 = _tick_percentiles(one_tick, 16)

    # device-truth decode step: chain-differenced (shared probe)
    step_ms = _decode_step_probe(model, e2, uids, True, 32, 8, 3)

    # fused multi-step decode (ISSUE 1 acceptance): the per-tick p50
    # above pays one host round trip PER TOKEN; the fused loop pays it once
    # per K tokens. Fresh KV state — the tick phase grew the sequences,
    # and the 64-block pool is sized to the fused horizon at context P.
    e2.flush(uids)
    K = 8
    fused = _fused_decode_metrics(e2, prompts, k=K, n_dispatches=6)

    # ISSUE 6 acceptance: N-deep chained serving with in-graph
    # admission + one host read per chain. decode_fused above blocks on
    # every dispatch (RTT per K tokens); the chained loop pays the RTT
    # once per chain of `depth` dispatches, so its per-step tick should
    # sit within 2x decode_step_ms_compute — and its host dispatches
    # per token at equal greedy outputs strictly below the PR 1 figure.
    e2.flush(list(range(B)))
    e2._config.max_inflight_dispatches = 4
    e2._config.fused_admission = True
    _chained_serve_metrics(e2, prompts, K, max_new=64)   # warm/compile
    chained = _chained_serve_metrics(e2, prompts, K, max_new=64)
    # ISSUE 9: the same chained/ring serving pass with speculative
    # decoding on (prompt-lookup drafting + in-graph verify) — reported
    # NEXT TO the chained-tick numbers so the spec-on delta is read at
    # matched batch/context/depth. Random-weight greedy decode cycles
    # in steady state, so the drafter has real hits here; acceptance on
    # genuine weights is workload-dependent (see docs/serving.md).
    from deepspeed_tpu.inference.v2.engine_v2 import SpeculativeConfig
    e2._config.speculative = SpeculativeConfig(
        enabled=True, draft_len=4, min_ngram=2)
    _chained_serve_metrics(e2, prompts, K, max_new=64)   # warm spec fns
    spec_ch = _chained_serve_metrics(e2, prompts, K, max_new=64)
    spec_m = e2.serving_metrics()
    spec = {f"spec_{k}": v for k, v in spec_ch.items()
            if k not in ("chain_depth", "fused_admission")}
    spec["spec_acceptance_rate"] = round(
        spec_m["spec_acceptance_rate"], 3)
    spec["spec_tokens_per_dispatch"] = round(
        spec_m["tokens_per_dispatch"], 3)
    return {"metric": "serve7b_int8_decode_tokens_per_sec",
            **spec,
            "value": round(B * 1e3 / step_ms, 1), "unit": "tokens/s/chip",
            "batch": B, "params_b": round(
                model.config.num_params() / 1e9, 2),
            "weights_int8_gib": round(int8_gib, 2),
            "context_tokens": P,
            "decode_step_ms_compute": round(step_ms, 2),
            # host-in-loop per-tick scheduler (the BENCH_r05 "tick_p50"
            # baseline: one RTT per token); the serving tick_p50_ms now
            # comes from the chained loop below
            "per_tick_p50_ms": round(p50, 1),
            "per_tick_p99_ms": round(p99, 1),
            **fused,
            "fused_step_ms": round(fused["fused_tick_p50_ms"] / K, 2),
            **chained,
            "tick_note": "per-tick pays one host round trip per token; "
                         "decode_fused pays it once per K tokens; the "
                         "chained serving loop (tick_p50_ms) once per "
                         "chain of depth dispatches"}


def llama7b_streamed(ds, on_tpu: bool):
    """ZeRO-Infinity tier (BASELINE config 2 / north-star capability):
    a Llama-7B-parity model trains on ONE chip with all layer matrices +
    Adam state resident in pinned_host (~81 GiB), streamed per layer
    through HBM inside the compiled step (runtime/infinity.py; reference
    stage3.py:1926 + swap_tensor/). Host residency is asserted from the
    live arrays. Transfer-bound by design: the step rides PCIe, so MFU
    is reported honestly alongside tokens/s."""
    from deepspeed_tpu.models import Llama
    if on_tpu:
        # loss_chunk=256 (fused chunked cross-entropy) keeps the [B,S,V]
        # logits slab out of HBM — that is what unlocks micro=12 (r4's
        # micro=12 spilled activations at 0.042 MFU with full logits;
        # micro=14 still OOMs). Per-token cost at ga-saturation is the
        # per-micro weight stream, so micro 8 -> 12 is a direct 1.25x.
        model = Llama(hidden_size=4096, num_layers=32, num_heads=32,
                      num_kv_heads=32, intermediate_size=11008,
                      vocab_size=32000, max_seq_len=2048,
                      remat_policy="segments", attn_impl="flash",
                      loss_chunk=256, tie_embeddings=False)
        # ga=24 amortizes the fixed master+moments stream further
        # (runs once per step). stream_dtype stays "master": the bf16
        # stream stack's +12 GiB pinned (60.3 GiB total) did not hold
        # on the r5 host, whose stable pinned envelope ended just above
        # the 48.2 GiB master+moments footprint (r4 measured the same
        # config net-negative before the cliff). Not re-measured on
        # the current machines.
        # Measured r5 ladder (ga, micro): (16,8) 0.309 -> (16,10)
        # 0.345 -> (16,12)+loss_chunk 0.388 -> (24,12) 0.395 MFU.
        micro, ga, seq, steps = 12, 24, 2048, 1
        batch = micro * ga
    else:
        model = Llama(size="tiny", max_seq_len=128, tie_embeddings=False)
        micro, ga, seq, steps = 2, 1, 128, 2
        batch = micro * ga
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
                              **({} if on_tpu else {"stream": True})},
            "offload_optimizer": {"device": "cpu",
                                  "moment_dtype": "bfloat16"}},
        "steps_per_print": 10 ** 9})
    from deepspeed_tpu.runtime.infinity import StreamedZeroEngine
    assert isinstance(engine, StreamedZeroEngine), type(engine)
    rpt = engine.host_memory_report()
    if on_tpu:
        assert rpt["host_fraction"] > 0.85, rpt
    tokens = jax.random.randint(jax.random.PRNGKey(0),
                                (batch, seq + 1), 0,
                                model.config.vocab_size)
    data = (tokens[:, :-1], tokens[:, 1:])
    loss = float(engine.train_batch(data))      # compile + step 1
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = float(engine.train_batch(data))
    dt = (time.perf_counter() - t0) / steps
    tps = batch * seq / dt
    return {"metric": "llama7b_streamed_train_tokens_per_sec",
            "value": round(tps, 1), "unit": "tokens/s/chip",
            "params_b": round(model.config.num_params() / 1e9, 2),
            "host_state_gib": round(rpt["pinned_host"] / 2 ** 30, 1),
            "host_fraction": round(rpt["host_fraction"], 3),
            "grad_accumulation": ga,
            "step_s": round(dt, 2), "loss": round(loss, 4),
            **_mfu_fields(tps, model.config, seq)}


def nvme_streamed(ds, on_tpu: bool):
    """ZeRO-Infinity NVMe tier (VERDICT r3 missing #1; reference:
    swap_tensor/partitioned_param_swapper.py + stage3.py:1926): master
    weights and Adam moments live on DISK (12 bytes/param), paged per
    layer through the native AIO op into the C++ CPU Adam, so model
    size is bounded by NVMe capacity — not host RAM (the one
    capability row where the reference could train something the r3
    repo could not). Host RAM holds only the bf16 stream stack phase A
    reads (2 bytes/param) + a transient grad stack. Measured at ~0.9B
    params; the same path scales to any size the disk holds.

    Measurement path (VERDICT r4 #4): the trajectory runs HOST-SIDE in
    a subprocess on the local CPU backend — compute, pinned staging and
    the AIO swap files all on one machine, so the disk traffic is
    real and the step times are CPU-backend times. The
    config is >=1B parameters with >90% of optimizer state paged from
    disk; a 20-step decreasing-loss run of the same tool is committed
    at artifacts/nvme_1b_trajectory.json."""
    import json as _json
    import os
    import subprocess
    import sys as _sys
    steps = 4 if on_tpu else 2
    env = dict(os.environ)
    env.pop("JAX_PLATFORM_NAME", None)
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "nvme_1b_trajectory.py")
    if not on_tpu:   # CPU smoke: the tiny in-process path is covered by
        env["DS_NVME_TRAJ_TINY"] = "1"   # tests; keep the row cheap
    try:
        proc = subprocess.run([_sys.executable, tool, str(steps)],
                              capture_output=True, text=True, env=env,
                              timeout=3600)
    except subprocess.TimeoutExpired as e:
        return {"metric": "nvme_streamed_train_tokens_per_sec",
                "error": f"host-side trajectory timed out after 3600s "
                         f"({(e.stderr or '')[-200:]})"}
    if proc.returncode != 0:
        return {"metric": "nvme_streamed_train_tokens_per_sec",
                "error": proc.stderr[-500:]}
    res = _json.loads(proc.stdout.strip().splitlines()[-1])
    out = {"metric": "nvme_streamed_train_tokens_per_sec",
           "value": res["tokens_per_sec"], "unit": "tokens/s (host-side)",
           **{k: res[k] for k in (
               "params_b", "offloaded_fraction", "nvme_state_gib",
               "host_state_gib", "nvme_read_gib_per_step",
               "nvme_written_gib_per_step", "step_s", "loss_first",
               "loss_last", "steps", "platform")}}
    art = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "artifacts", "nvme_1b_trajectory.json")
    if os.path.exists(art):
        with open(art) as f:
            traj = _json.load(f)
        out["trajectory_20step"] = {k: traj[k] for k in (
            "steps", "loss_first", "loss_last", "decreasing")
            if k in traj}
    return out


def domino_bench(ds, on_tpu: bool):
    """Domino overlap evidence on real hardware (VERDICT r3 weak #5).

    One chip cannot time a tp all-reduce over ICI, so the claim 'XLA
    overlaps chunk i's collective with chunk i+1's compute'
    (runtime/domino.py) is evidenced with the resource that IS
    observable single-chip: a pinned_host DMA round trip as the
    pending-reduction proxy. Like an ICI collective, the DMA rides a
    non-MXU resource, so IF the latency-hiding scheduler interleaves
    chunks, chunked wall time approaches max(compute, transfer) rather
    than their sum. overlap_ratio < 1 is the measured evidence;
    single-chip limits are documented in COVERAGE.md."""
    import functools

    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    if not on_tpu:
        return {"metric": "domino_overlap_ratio", "skipped": "cpu rig"}
    dev = jax.devices()[0]
    dev_sh = SingleDeviceSharding(dev)
    host_sh = SingleDeviceSharding(dev, memory_kind="pinned_host")
    # shapes picked so per-chunk compute ~= per-chunk transfer (~7 ms
    # each): overlap is only visible when neither resource dominates
    d, rows, n_micro, k_gemm = 4096, 2048, 4, 16
    w = jax.random.normal(jax.random.PRNGKey(0), (d, d), jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, d), jnp.bfloat16)

    def attn_like(p, xc):
        for _ in range(k_gemm):
            xc = xc @ p
        return xc

    def dma_reduce(y):
        # chunk's pending tp-reduction proxy: D2H + H2D round trip
        return jax.device_put(jax.device_put(y, host_sh), dev_sh)

    def run(n, x):
        def step(xc, _):
            chunks = jnp.split(xc, n, axis=0)
            outs = [dma_reduce(attn_like(w, c)) for c in chunks]
            y = jnp.concatenate(outs, axis=0)
            # data dependency between scan steps: no dead-code elision
            return y / (1 + jnp.max(jnp.abs(y))), ()
        y, _ = jax.lax.scan(step, x, None, length=8)
        return y

    times = {}
    for n in (1, n_micro):
        f = jax.jit(functools.partial(run, n))
        float(jnp.sum(f(x)))             # warm compile incl. the sum
        t0 = time.perf_counter()
        float(jnp.sum(f(x)))             # forced device->host sync
        times[n] = time.perf_counter() - t0
    ratio = times[n_micro] / times[1]
    return {"metric": "domino_overlap_ratio", "value": round(ratio, 3),
            "unit": "chunked/unchunked wall time (<1 = overlap)",
            "unchunked_ms": round(times[1] * 1e3, 1),
            "chunked_ms": round(times[n_micro] * 1e3, 1),
            "n_micro": n_micro, "proxy": "pinned_host DMA round trip"}


def _aot_wire_bytes(engine, batch):
    """{axis: collective payload bytes} + {axis: wire bytes/element} of
    the engine's compiled train step, from the AOT HLO walk (no
    dispatch; the compile lands in jax's executable cache so the
    subsequent measured steps reuse it)."""
    from deepspeed_tpu.profiling.flops_profiler.profiler import \
        lower_compiled
    from deepspeed_tpu.telemetry import collectives as coll
    compiled = lower_compiled(engine._train_step, engine.state, batch)
    traffic = coll.traffic_matrix(
        coll.analyze_hlo(compiled.as_text(), mesh=engine.mesh))
    by_axis: dict = {}
    for (axis, _op), row in traffic.items():
        by_axis[axis] = by_axis.get(axis, 0) + row["bytes"]
    return by_axis, coll.axis_wire_width(traffic)


def _sharded_dp_bytes(by_axis: dict) -> int:
    """Payload on the sharded-DP axes (fsdp/zps and combinations) —
    the traffic the ZeRO++ wire protocol quantizes."""
    return sum(b for axis, b in by_axis.items()
               if set(axis.split("+")) <= {"fsdp", "zps"})


def zeropp_bench(ds, on_tpu: bool):
    """ZeRO++ wire-protocol stage (ISSUE 8): the same fsdp×zps ZeRO-3
    training config compiled with the fp32 wire vs the quantized +
    hierarchical wire (qwZ + qgZ int8, stochastic rounding, two-hop
    gathers), reporting per-axis HLO-accounted collective bytes, the
    sharded-DP byte reduction, tokens/s, and the loss trajectory gap.
    The ``--gate comms`` family of ``telemetry_report --diff`` watches
    these fields across rounds (collective bytes must not regress,
    tokens/s ±5%).

    Needs >=4 devices for a real zps split; on a smaller host the
    stage self-provisions a virtual 8-device CPU mesh in a subprocess
    (the dryrun_multichip recipe) and relays the child's record."""
    if len(jax.devices()) < 4:
        if os.environ.get("DS_TPU_ZEROPP_CHILD"):
            return {"metric": "zeropp_wire_reduction",
                    "skipped": "virtual mesh provisioning failed"}
        import subprocess
        env = dict(os.environ)
        env["DS_TPU_ZEROPP_CHILD"] = "1"
        env.pop("JAX_PLATFORM_NAME", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--stage", "zeropp"],
            capture_output=True, text=True, timeout=600, env=env)
        for line in proc.stderr.splitlines():
            if line.startswith("# zeropp {"):
                return json.loads(line[len("# zeropp "):])
        raise RuntimeError(
            f"zeropp child produced no record (rc={proc.returncode}): "
            + proc.stderr[-400:])

    from deepspeed_tpu.models import GPT2
    from deepspeed_tpu.parallel import mesh as mesh_mod
    n = len(jax.devices())
    seq = 256 if on_tpu else 64
    batch = 2 * n
    steps = 3

    def run(quantized: bool):
        mesh_mod.reset_topology()
        zero = {"stage": 3}
        if quantized:
            zero.update({"zero_quantized_weights": True,
                         "zero_quantized_gradients": True,
                         "zero_quantized_dtype": "int8",
                         "zero_quantized_rounding": "stochastic",
                         "zero_hierarchical_allgather": True})
        engine, _, _, _ = ds.initialize(
            model=GPT2(size="tiny", max_seq_len=seq),
            config={"train_batch_size": batch,
                    "gradient_accumulation_steps": 1,
                    "optimizer": {"type": "AdamW",
                                  "params": {"lr": 1e-3}},
                    "gradient_clipping": 1.0,
                    "zero_optimization": zero,
                    "mesh": {"fsdp": -1, "zps": 2},
                    "steps_per_print": 10 ** 9})
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (batch, seq + 1), 0,
            engine.module.config.vocab_size)
        data = (tokens[:, :-1], tokens[:, 1:])
        by_axis, width = _aot_wire_bytes(engine, data)
        losses = [float(engine.train_batch(data)) for _ in range(steps)]
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch(data)
        float(loss)
        tps = steps * batch * seq / (time.perf_counter() - t0)
        mesh_mod.reset_topology()
        return by_axis, width, losses, tps

    fp32_axis, fp32_width, fp32_losses, fp32_tps = run(quantized=False)
    q_axis, q_width, q_losses, q_tps = run(quantized=True)
    fp32_dp = _sharded_dp_bytes(fp32_axis)
    q_dp = _sharded_dp_bytes(q_axis)
    reduction = (1.0 - q_dp / fp32_dp) if fp32_dp else 0.0
    loss_rel = max(abs(a - b) / max(abs(b), 1e-9)
                   for a, b in zip(q_losses, fp32_losses))
    return {
        "metric": "zeropp_wire_reduction_sharded_dp",
        "value": round(reduction, 4),
        # the gate-visible name: --gate comms matches flattened numeric
        # KEYS, and the "metric" string leaf is dropped by the
        # flattener, so the acceptance figure must be a field name
        "wire_reduction": round(reduction, 4),
        "unit": "1 - quantized/fp32 collective bytes (fsdp+zps axes)",
        "wire_bytes_per_axis": {k: int(v) for k, v in q_axis.items()},
        "wire_bytes_per_axis_fp32": {k: int(v)
                                     for k, v in fp32_axis.items()},
        "wire_bytes_sharded_dp": int(q_dp),
        "wire_bytes_sharded_dp_fp32": int(fp32_dp),
        "wire_bytes_per_el": {k: round(v, 3) for k, v in q_width.items()},
        "tokens_per_sec": round(q_tps, 1),
        "tokens_per_sec_fp32_wire": round(fp32_tps, 1),
        "loss_rel_err_vs_fp32_wire": round(loss_rel, 5),
        "losses": [round(x, 5) for x in q_losses],
        "losses_fp32_wire": [round(x, 5) for x in fp32_losses],
    }


def numsan_bench(ds, on_tpu: bool):
    """numsan overhead stage (ISSUE 18): the same training config run
    three ways — no numsan block at all, the block present but
    disabled, and armed in warn mode (per-leaf grad stats folded into
    the compiled step + the deferred host check) — reporting

    - ``numsan_overhead_pct``: armed-vs-off tokens/s delta (the ≤3%
      acceptance figure; the armed step adds one fused per-leaf
      count/max reduction and a deferred-by-one-dispatch host check);
    - ``extra_executables``: backend-compile events of the
      disabled-block run minus the no-block run — MUST be 0 (the
      disabled path traces byte-identical graphs; the ``--gate
      numerics`` family zero-tolerates this field);
    - the sanitizer's own counters from the armed run (checked steps,
      violations — a healthy run reports 0 violations).
    """
    from deepspeed_tpu.models import GPT2
    from deepspeed_tpu.telemetry import bridges
    bridges.install_jax_compile_listener()
    seq = 1024 if on_tpu else 64
    batch = 8 if on_tpu else _cpu_batch()
    steps = 10 if on_tpu else 3
    model_kw = dict(max_seq_len=seq)

    # run 1 also warms every process-level jit cache (module-level
    # helpers compile once per process, not per engine) so the later
    # compile-count comparison sees per-engine executables only
    off_tps, _ = _train_tput(ds, GPT2(size="tiny", **model_kw), {},
                             batch, seq, steps,
                             windows=2 if on_tpu else 1)
    # executable-count parity check (warm vs warm): a second no-block
    # run vs a numsan-key-present-but-disabled run must compile the
    # SAME number of executables — the disabled path is byte-identical
    c0 = bridges.compile_event_count()
    _train_tput(ds, GPT2(size="tiny", **model_kw), {}, batch, seq, 1)
    c1 = bridges.compile_event_count()
    _train_tput(ds, GPT2(size="tiny", **model_kw),
                {"numsan": {"enabled": False}}, batch, seq, 1)
    c2 = bridges.compile_event_count()

    on_tps, _ = _train_tput(ds, GPT2(size="tiny", **model_kw),
                            {"numsan": {"enabled": True, "mode": "warn"}},
                            batch, seq, steps,
                            windows=2 if on_tpu else 1)
    from deepspeed_tpu.analysis.numsan import get_numsan
    san = get_numsan()
    counters = dict(san.counters) if san is not None else {}
    overhead = (off_tps - on_tps) / off_tps * 100.0 if off_tps else 0.0
    return {
        "metric": "numsan_overhead_pct",
        "value": round(overhead, 2),
        "unit": "% tokens/s lost with the sanitizer armed (warn mode)",
        "tokens_per_sec": round(on_tps, 1),
        "tokens_per_sec_numsan_off": round(off_tps, 1),
        "extra_executables": int((c2 - c1) - (c1 - c0)),
        "numsan_checked_steps": int(counters.get("checked_steps", 0)),
        "numsan_violations": int(counters.get("violations", 0)),
    }


def offload_smoke(ds, on_tpu: bool):
    """ZeRO-Offload tier on real hardware. Sweeps the Twin-Flow
    `ratio` (reference offload_config.py:93): 1.0 = everything in
    pinned_host, 0.5 = largest half of the optimizer-tier bytes on host,
    0.0 = all-HBM baseline. Host residency is ASSERTED from the live
    arrays (engine.host_memory_report) — a silently-degraded placement
    fails the bench instead of reporting fiction (VERDICT r2 weak #3)."""
    import gc
    from deepspeed_tpu.models import GPT2
    model = (GPT2(size="125m", vocab_size=50304, max_seq_len=256)
             if on_tpu else GPT2(size="tiny", max_seq_len=256))
    batch = 4 if on_tpu else _cpu_batch(1)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (batch, 257), 0,
                                model.config.vocab_size)
    data = (tokens[:, :-1], tokens[:, 1:])
    out = {"metric": "zero_offload_cpu_step_ms", "unit": "ms"}
    for ratio in (1.0, 0.5, 0.0):
        config = {
            "train_batch_size": batch,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {
                "stage": 2,
                "offload_optimizer": {"device": "cpu", "ratio": ratio}},
            "steps_per_print": 10 ** 9,
        }
        engine, _, _, _ = ds.initialize(model=model, config=config)
        float(engine.train_batch(data))
        rpt = engine.host_memory_report()
        if on_tpu:
            # placement must actually hold on real hardware
            assert rpt["host_fraction"] >= 0.9 * min(ratio, 0.99), rpt
            assert ratio > 0.0 or rpt["host_fraction"] == 0.0, rpt
        t0 = time.perf_counter()
        for _ in range(3):
            loss = engine.train_batch(data)
        float(loss)
        key = {1.0: "value", 0.5: "ratio05_ms", 0.0: "in_hbm_ms"}[ratio]
        out[key] = round((time.perf_counter() - t0) / 3 * 1e3, 1)
        out[{1.0: "host_frac", 0.5: "ratio05_host_frac",
             0.0: "in_hbm_host_frac"}[ratio]] = round(
                 rpt["host_fraction"], 3)
        del engine
        gc.collect()
    return out


def autotune_bench(ds, on_tpu: bool):
    """Planner stage (ISSUE 7): run the ledger-driven autotuner on the
    headline training config — calibrate effective FLOPs/s on the
    hand-tuned base, AOT-rank the mesh x microbatch x ZeRO x remat grid
    without dispatching a step, measure the top-3, and report the
    chosen plan next to its prediction error and the baseline
    throughput. Plan artifact: artifacts/autotune_plan.json (render
    with tools/autotune_report.py); gate with
    ``telemetry_report --diff --gate autotune``."""
    import gc

    from deepspeed_tpu.autotuning import (AutotuningConfig, Planner,
                                          summarize)
    from deepspeed_tpu.models import GPT2

    seq = 1024 if on_tpu else 64
    mb = 8 if on_tpu else 2
    model = (GPT2(size="125m", vocab_size=50304,
                  remat_policy="segments", attn_impl="flash")
             if on_tpu else GPT2(size="tiny", max_seq_len=seq))
    # the hand-tuned headline-stage config is the baseline the chosen
    # plan must beat (or match: it is itself a grid point)
    base = {
        "train_micro_batch_size_per_gpu": mb,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "FusedAdam",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "gradient_clipping": 1.0,
        "steps_per_print": 10 ** 9,
    }

    def make_batch(total):
        tokens = jax.random.randint(jax.random.PRNGKey(0),
                                    (total, seq + 1), 0,
                                    model.config.vocab_size)
        return tokens[:, :-1], tokens[:, 1:]

    cfg = AutotuningConfig(
        enabled=True,
        min_train_micro_batch_size_per_gpu=mb,
        num_tuning_micro_batch_sizes=3,
        zero_stages=[0, 1, 2, 3],
        calibration_steps=4 if on_tpu else 3,
        start_step=2, end_step=5,
        measure_windows=3,
        measure_top_k=3)
    planner = Planner(model, base, cfg, make_batch=make_batch)
    plan = planner.plan()
    os.makedirs("artifacts", exist_ok=True)
    path = plan.save(os.path.join("artifacts", "autotune_plan.json"))
    out = summarize(plan)
    # the acceptance metric is prediction error over the measured
    # TOP-K; the base candidate is also measured (for the baseline
    # ratio below) but its short mb-2 steps are the noisiest — keep
    # its error in the _all figure, not the gated one
    errs_top = [c["prediction_rel_err"] for c in plan.ranked()
                if c.get("prediction_rel_err") is not None
                and c.get("rank", 99) <= cfg.measure_top_k]
    if errs_top:
        if "prediction_rel_err" in out:
            out["prediction_rel_err_all"] = out["prediction_rel_err"]
        out["prediction_rel_err"] = round(max(errs_top), 4)
    out["plan_path"] = path
    out["calibration_flops_per_s"] = round(
        plan.calibration.get("flops_per_s", 0.0), 1)
    # calibration point 1 IS the hand-tuned base config: its measured
    # throughput is the baseline the chosen plan is compared against
    log = planner.trial_log
    if log:
        out["baseline_tokens_per_sec"] = round(log[0]["tokens_per_sec"],
                                               1)
        if out.get("plan_tokens_per_sec"):
            out["plan_vs_baseline"] = round(
                out["plan_tokens_per_sec"]
                / out["baseline_tokens_per_sec"], 4)
    out["config_diff"] = {k: v for k, v in plan.diff().items()
                          if not k.startswith("train_batch_size")}
    del planner, plan
    gc.collect()
    return out


def headline_bench(ds, on_tpu: bool):
    """The stdout-JSON stage: GPT-2 125M training throughput."""
    from deepspeed_tpu.models import GPT2
    seq = 1024 if on_tpu else 128
    batch = 24 if on_tpu else _cpu_batch()
    size = "125m" if on_tpu else "tiny"

    # vocab padded to a multiple of 128 lanes: GPT-2's 50257 fragments the
    # MXU tiling on the logits matmul (worth ~2x step time at 125M).
    # flash attention (in-repo one-pass-backward kernel) + segment remat
    # (attention outside jax.checkpoint so its residuals are kept — no
    # flash fwd rerun in backward): 31% -> 38% -> 46% MFU on v5e across
    # rounds vs full remat + unfused attention.
    model = (GPT2(size=size, vocab_size=50304,
                  remat_policy="segments", attn_impl="flash")
             if on_tpu else GPT2(size=size, max_seq_len=seq))
    # best-of-3 windows: a cold/slow first window has been observed
    # (2.7x on otherwise identical runs); min over windows reports
    # steady-state throughput
    tokens_per_sec, loss = _train_tput(
        ds, model,
        {"gradient_clipping": 1.0, "gradient_accumulation_steps": 1},
        batch, seq, steps=10 if on_tpu else 3,
        windows=3 if on_tpu else 1)
    dt_steps = batch * seq / tokens_per_sec      # seconds per step
    m = _mfu_fields(tokens_per_sec, model.config, seq)
    print(f"# mfu={m['mfu']:.3f} mfu_noncausal={m['mfu_noncausal']:.3f} "
          f"loss={loss:.4f} step_ms={dt_steps * 1e3:.1f}", file=sys.stderr)
    return {
        "metric": "gpt2_125m_train_tokens_per_sec" if on_tpu
                  else "gpt2_tiny_cpu_smoke_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        # the 0.45 north-star target (BASELINE.md §9) is a conventional-
        # accounting claim, so the ratio compares like accounting with
        # like; the primary (causal) MFU rides alongside
        "vs_baseline": round(m["mfu_noncausal"] / 0.45, 4),
        "mfu": m["mfu"],
    }


# the one stdout JSON line the driver parses; filled by the headline
# stage (or with skip/error context when it can't run) and emitted
# exactly once — including from the SIGTERM handler, so a harness-level
# timeout (rc=124) still leaves parseable output behind
_FINAL: dict = {}
_FINAL_LOCK = threading.Lock()
_FINAL_DONE = threading.Event()


def _emit_final() -> None:
    if _FINAL_DONE.is_set():
        return
    # mask SIGTERM while holding the (non-reentrant) lock: the handler
    # also calls _emit_final, and a signal landing inside the critical
    # section would self-deadlock the main thread
    try:
        old = signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGTERM})
    except (ValueError, OSError):   # non-main thread on some platforms
        old = None
    try:
        with _FINAL_LOCK:
            if _FINAL_DONE.is_set():
                return
            if "metric" not in _FINAL:
                # whatever the exit path (SIGTERM/watchdog/fall-through),
                # the one stdout line always carries metric/value keys
                _FINAL.setdefault("error", "headline stage did not run")
                _FINAL.update({"metric": "bench_headline", "value": None})
            print(json.dumps(_FINAL), flush=True)
            _FINAL_DONE.set()
    finally:
        if old is not None:
            signal.pthread_sigmask(signal.SIG_SETMASK, old)


_BENCH_DONE = threading.Event()


def _arm_total_watchdog(total_s: float, grace_s: float = 30.0) -> None:
    """Hard global deadline (BENCH_r05 rc=124): if the stage matrix is
    still running ``grace_s`` seconds past the ``total_s`` budget —
    e.g. a stage wedged inside a C++ XLA compile where SIGALRM never
    fires — emit the stdout JSON and exit 0 from a daemon thread, so
    the driver parses a result instead of a timeout kill. Messages
    report the configured budget, not the budget+grace wait."""
    def run():
        if not _BENCH_DONE.wait(total_s + grace_s):
            _FINAL.setdefault(
                "interrupted",
                f"total budget {total_s:.0f}s exhausted mid-stage")
            # forensics BEFORE the exit (ISSUE 5): when telemetry's
            # flight recorder is live, leave a hang dump (recent
            # dispatches, open spans, ledger, thread stacks) so an
            # rc=124-class wedge is diagnosable post-mortem
            try:
                from deepspeed_tpu.utils.telemetry_probe import \
                    active_telemetry
                mod = active_telemetry()
                if mod is not None:
                    path = mod.dump_flight_record(
                        f"bench total budget {total_s:.0f}s exhausted")
                    if path:
                        print(f"# flight-recorder dump: {path}",
                              file=sys.stderr)
            except Exception:   # noqa: BLE001 - never mask the exit
                pass
            print(f"# total budget {total_s:.0f}s exhausted; exiting "
                  "with the stages completed so far", file=sys.stderr)
            _emit_final()
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(0)
    threading.Thread(target=run, daemon=True,
                     name="bench-total-watchdog").start()


def _arm_watchdog(deadline_s: float) -> None:
    """Emit the stdout JSON from a daemon thread if the headline stage
    hasn't produced it by ``deadline_s``. SIGALRM/SIGTERM handlers only
    run between Python bytecodes — a stage stuck inside one long C++
    XLA compile (the BENCH_r05 rc=124 failure) never returns to the
    interpreter, the harness escalates to SIGKILL, and no JSON lands.
    Threads keep running during C++ calls, so this fires regardless."""
    def run():
        if not _FINAL_DONE.wait(deadline_s):
            _FINAL.setdefault(
                "interrupted",
                f"watchdog: headline not done after {deadline_s:.0f}s "
                "(stage unresponsive to signals, e.g. mid-compile)")
            _emit_final()
    threading.Thread(target=run, daemon=True, name="bench-watchdog").start()


class _StageTimeout(BaseException):
    """BaseException so the SIGALRM raise punches through the broad
    `except Exception` blocks inside stages (e.g. kernel_smoke's
    per-kernel check) instead of being recorded as a kernel FAIL with
    the stage running on unbudgeted."""


def _install_signal_handlers() -> None:
    def on_alarm(signum, frame):
        raise _StageTimeout()

    def on_term(signum, frame):
        _FINAL.setdefault("interrupted", "SIGTERM mid-stage")
        _emit_final()
        sys.stdout.flush()
        os._exit(124)

    signal.signal(signal.SIGALRM, on_alarm)
    signal.signal(signal.SIGTERM, on_term)


def steptrace_bench(ds, on_tpu):
    """Seeded-regression micro-phase (ISSUE 20): drive a fake-clock
    StepTraceRecorder through a healthy plateau, then inject a slow
    collective (excess over the calibrated device baseline on a
    comm-carrying executable) and assert the online changepoint
    finding names the injected component AND its owning executable.
    Pure host arithmetic — runs in milliseconds on any rig; the
    assertions make a detector regression a stage failure, not a
    silent artifact drift."""
    from deepspeed_tpu.telemetry.steptrace import StepTraceRecorder

    class _Clock:
        t = 1000.0

        def __call__(self):
            return self.t

    class _Led:
        compile_seconds: dict = {}

        def collective_bytes_by_axis(self, name):
            return {"dp": 4.2e6}

    clk = _Clock()
    rec = StepTraceRecorder(capacity=256, clock=clk,
                            ledger=lambda: _Led(),
                            regression_window=8,
                            regression_threshold=0.3)
    inject_at, detect_at = 24, None
    for i in range(64):
        rec.step_begin(i + 1)
        clk.t += 0.002
        rec.data_ready()
        clk.t += 0.001
        rec.h2d_done()
        # healthy device window 10 ms; the fault adds 4 ms of exposed
        # comm on the same executable from step `inject_at` on
        clk.t += 0.010 if i < inject_at else 0.014
        rec.dispatch_done("compiled_step")
        clk.t += 0.0005
        rec.step_end()
        if detect_at is None and any(
                f["component"] == "exposed_comm"
                for f in rec.regressions()):
            detect_at = i + 1
    findings = rec.regressions()
    assert findings, "seeded slow-comm fault produced no finding"
    hit = next(f for f in findings if f["component"] == "exposed_comm")
    assert hit["executable"] == "compiled_step", hit
    assert rec.recon_max_rel_err <= 1e-6, rec.recon_max_rel_err
    s = rec.goodput_summary()
    return {"seeded_component": "exposed_comm",
            "finding_component": hit["component"],
            "finding_executable": hit["executable"],
            "finding_step": hit["step"],
            "detect_latency_steps": detect_at - inject_at,
            "recon_max_rel_err": rec.recon_max_rel_err,
            "goodput_fraction": round(s["goodput_fraction"], 4)}


# headline first (its JSON goes out as soon as it lands), kernel_smoke
# BEFORE the slow 7B sections so a harness-level timeout can only cost
# the capability rows, not the kernel evidence
STAGES = [("headline", headline_bench),
          ("llama", llama_bench), ("longctx", longctx_bench),
          ("moe", moe_bench), ("serving", serving_bench),
          ("prefix", prefix_bench),
          ("spec", spec_bench),
          ("kvquant", kvquant_bench),
          ("serve_openloop", serve_openloop_bench),
          ("serve_autotune", serve_autotune_bench),
          ("disagg", disagg_bench),
          ("fleet", fleet_bench),
          ("moe_serving", moe_serving_bench),
          ("moe_train", moe_train_bench),
          ("moe_serve", moe_serve_bench),
          ("offload", offload_smoke),
          ("autotune", autotune_bench),
          ("zeropp", zeropp_bench),
          ("numsan", numsan_bench),
          ("steptrace", steptrace_bench),
          ("domino", domino_bench),
          ("kernel_smoke", lambda *_: kernel_smoke()),
          ("serve7b", serve7b_int8),
          ("llama7b", llama7b_streamed),
          ("nvme", nvme_streamed)]


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="deepspeed_tpu benchmark (one JSON line on stdout; "
                    "'# '-prefixed stage records on stderr)")
    ap.add_argument("--stage", default="",
                    help="comma-separated subset of stages to run "
                         "(default: all; see --list-stages)")
    ap.add_argument("--budget-s", type=int, default=0,
                    help="per-stage wall-clock budget in seconds, "
                         "enforced with SIGALRM (0 = platform default: "
                         "600 on TPU, 240 on CPU)")
    ap.add_argument("--total-budget-s", type=int, default=-1,
                    help="global wall-clock deadline for the whole "
                         "stage matrix: remaining stages are skipped "
                         "(recorded on stderr) once it is reached and "
                         "the final JSON line is always emitted. "
                         "-1 = $DS_BENCH_TOTAL_BUDGET_S or 3300; "
                         "0 disables")
    ap.add_argument("--telemetry", metavar="DIR", default="",
                    help="activate the telemetry subsystem (ISSUE 2) and "
                         "write per-stage artifacts into DIR: "
                         "<stage>.trace.json (Perfetto), <stage>.prom "
                         "(Prometheus text), <stage>.metrics.json")
    ap.add_argument("--list-stages", action="store_true",
                    help="print stage names and exit")
    args = ap.parse_args(argv)
    if args.list_stages:
        print(" ".join(name for name, _ in STAGES))
        return

    import gc

    import deepspeed_tpu as ds
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.telemetry:
        from deepspeed_tpu import telemetry
        # full device-truth stack (ISSUE 5): executable ledger for
        # hbm_peak_bytes stage fields, flight recorder so the
        # total-budget watchdog can leave forensics behind
        telemetry.configure(executable_ledger=True,
                            flight_recorder=True,
                            watchdog_artifact_dir=args.telemetry)

    on_tpu = jax.devices()[0].platform != "cpu"
    budget = args.budget_s or (600 if on_tpu else 240)
    total_budget = args.total_budget_s
    if total_budget < 0:
        total_budget = int(os.environ.get("DS_BENCH_TOTAL_BUDGET_S",
                                          "3300"))
    deadline = (time.monotonic() + total_budget) if total_budget > 0 \
        else None
    selected = {s.strip() for s in args.stage.split(",") if s.strip()}
    unknown = selected - {name for name, _ in STAGES}
    if unknown:
        ap.error(f"unknown stage(s): {sorted(unknown)} "
                 f"(choose from: {' '.join(n for n, _ in STAGES)})")
    _install_signal_handlers()
    # headline runs first (or emits its skip record immediately), so if
    # the JSON hasn't landed one grace period past the stage budget the
    # signal path is wedged — let the watchdog thread put it out
    _arm_watchdog(budget * 1.25 + 60)
    if deadline is not None:
        # backstop for a stage unresponsive even to SIGALRM: emit the
        # JSON and exit 0 shortly after the deadline passes
        _arm_total_watchdog(total_budget)
    try:
        for name, fn in STAGES:
            if selected and name not in selected:
                if name == "headline":
                    _FINAL.update({"metric": "bench_headline",
                                   "value": None,
                                   "skipped": "not in --stage"})
                    _emit_final()
                continue
            remaining = (deadline - time.monotonic()
                         if deadline is not None else budget)
            if remaining <= 5:
                info = {"skipped": f"total budget {total_budget}s "
                                   "exhausted"}
                if name == "headline":
                    _FINAL.update({"metric": "bench_headline",
                                   "value": None, **info})
                    _emit_final()
                print(f"# {name} " + json.dumps(info), file=sys.stderr)
                continue
            signal.alarm(max(1, min(budget, int(remaining))))
            t0 = time.perf_counter()
            try:
                res = fn(ds, on_tpu)
                # disarm before recording: a budget expiring right as
                # fn() returns must not raise mid-emit (double stdout
                # line) or misreport the completed stage as skipped
                signal.alarm(0)
                if name == "headline":
                    _FINAL.update(res)
                    _emit_final()
                else:
                    print(f"# {name} " + json.dumps(res), file=sys.stderr)
            except _StageTimeout:
                info = {"skipped": f"stage budget {budget}s exceeded"}
                if name == "headline":
                    _FINAL.update({"metric": "bench_headline",
                                   "value": None, **info})
                    _emit_final()
                print(f"# {name} " + json.dumps(info), file=sys.stderr)
            except Exception as e:   # noqa: BLE001
                if name == "headline":
                    _FINAL.update({"metric": "bench_headline",
                                   "value": None,
                                   "error": f"{type(e).__name__}: "
                                            f"{str(e)[:160]}"})
                    _emit_final()
                print(f"# {name} FAIL: {type(e).__name__}: "
                      f"{str(e)[:160]}", file=sys.stderr)
            finally:
                signal.alarm(0)
                if args.telemetry:
                    # per-stage artifacts, then a clean slate for the
                    # next stage (written even when the stage timed out
                    # or failed — partial telemetry is still evidence)
                    from deepspeed_tpu import telemetry
                    paths = telemetry.export_artifacts(args.telemetry,
                                                       prefix=name)
                    if paths:
                        print(f"# {name} telemetry: {paths['trace']} "
                              f"{paths['prometheus']}", file=sys.stderr)
                    telemetry.clear()
                    # keep the comms tallies paired with the cleared
                    # span window (log_summary's bandwidth bound)
                    lg = ds.comm.get_comms_logger()
                    if lg is not None:
                        lg.reset()
            print(f"# {name} took {time.perf_counter() - t0:.1f}s",
                  file=sys.stderr)
            gc.collect()
    finally:
        _emit_final()
        _BENCH_DONE.set()


if __name__ == "__main__":
    main()

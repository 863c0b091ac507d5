"""Device-resident multi-tick serving (ISSUE 6): the N-deep dispatch
chain knob, in-graph admission (ring mode), and the async
continuous-batching server — streaming parity with generate_fused,
priority ordering, preemption park/restore, cancel block-leak
regression, and the serving regression gate."""

import asyncio
import importlib.util
import json
import os

import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.serve_loop import FusedServeLoop
from deepspeed_tpu.models import Llama
from deepspeed_tpu.serving import (AsyncInferenceServer, RequestCancelled,
                                   ServingConfig)

PROMPTS = [[1, 2, 3, 4, 5], [9, 8, 7], [6, 7, 8, 9, 10, 11]]


def _engine(model=None, **over):
    model = model or Llama(size="tiny")
    kw = dict(dtype="float32", kv_block_size=8, num_kv_blocks=128,
              max_chunk_size=16)
    kw.update(over)
    return InferenceEngineV2(model, RaggedInferenceEngineConfig(**kw))


def test_max_inflight_knob_validation_and_metric(devices8):
    """The chain-depth knob validates >=1 and surfaces through
    serving_metrics() (ISSUE 6 satellite)."""
    with pytest.raises(Exception, match="greater than or equal"):
        RaggedInferenceEngineConfig(max_inflight_dispatches=0)
    e = _engine(max_inflight_dispatches=3)
    assert e.serving_metrics()["max_inflight_dispatches"] == 3


def test_server_greedy_stream_matches_generate_fused(devices8):
    """Acceptance: tokens streamed by the async server are bit-identical
    to generate_fused for the same engine/prompts, and the engine is
    left leak-free."""
    e = _engine()
    ref = e.generate_fused(PROMPTS, max_new_tokens=10, k_steps=3)

    async def main():
        async with AsyncInferenceServer(e, ServingConfig(k_steps=3)) as s:
            handles = [await s.submit(p, max_new_tokens=10)
                       for p in PROMPTS]
            return [await h.tokens() for h in handles]

    outs = asyncio.run(main())
    assert outs == ref
    assert e.free_blocks == 128 and not e.state_manager.seqs


def test_priority_ordering_under_constrained_pool(devices8):
    """A later-submitted priority-0 request is admitted before
    earlier priority-2 requests when the pool cannot hold everyone."""
    e = _engine(num_kv_blocks=10)   # 4 blocks per (prompt + 24 new) seq
    loop = FusedServeLoop(e, k_steps=4, preemption=False)
    loop.submit([1, 2, 3, 4, 5], 24, priority=2, uid=100)
    loop.submit([2, 3, 4], 24, priority=2, uid=101)
    hi = loop.submit([9, 8, 7], 24, priority=0, uid=102)
    first_seen: list[int] = []
    while loop.has_work():
        for evt in loop.step():
            if evt.tokens and evt.uid not in first_seen:
                first_seen.append(evt.uid)
    assert first_seen[0] == hi, first_seen
    assert set(first_seen) == {100, 101, 102}
    assert e.free_blocks == 10 and not e.state_manager.seqs


def test_preemption_park_restore_roundtrip(devices8):
    """A high-priority arrival preempts the running low-priority
    request (KV swap-out); the victim restores later and its final
    stream is bit-identical to an unpreempted run."""
    e = _engine(num_kv_blocks=16)
    ref_lo = e.generate_fused([[1, 2, 3, 4, 5]], max_new_tokens=60,
                              k_steps=4)[0]
    ref_hi = e.generate_fused([[9, 8, 7]], max_new_tokens=60,
                              k_steps=4)[0]

    async def main():
        async with AsyncInferenceServer(e, ServingConfig(k_steps=4)) as s:
            lo = await s.submit([1, 2, 3, 4, 5], max_new_tokens=60,
                                priority=2)
            # let the low-priority request start decoding first
            first_lo = await lo.__anext__()
            hi = await s.submit([9, 8, 7], max_new_tokens=60, priority=0)
            out_hi = await hi.tokens()
            out_lo = [first_lo] + await lo.tokens()
            return out_lo, out_hi, s.metrics()

    out_lo, out_hi, m = asyncio.run(main())
    assert m["preemptions"] >= 1 and m["restores"] >= 1, m
    assert out_hi == ref_hi
    assert out_lo == ref_lo
    assert e.free_blocks == 16 and not e.state_manager.seqs


def test_preemption_frees_decode_row_when_rows_bound(devices8):
    """When decode ROWS (max_ragged_sequence_count), not KV blocks, are
    the binding constraint, a higher-priority arrival still preempts a
    lower-priority occupant to free its row."""
    e = _engine(max_ragged_sequence_count=1)   # ample blocks, one row
    loop = FusedServeLoop(e, k_steps=4)
    lo = loop.submit([1, 2, 3, 4, 5], 40, priority=2)
    for _ in range(3):                         # let lo start decoding
        loop.step()
    hi = loop.submit([9, 8, 7], 10, priority=0)
    finish_order: list[int] = []
    while loop.has_work():
        for evt in loop.step():
            if evt.finished:
                assert evt.error is None, evt
                finish_order.append(evt.uid)
    assert loop.counters["preemptions"] >= 1, loop.counters
    assert finish_order[0] == hi, finish_order
    assert set(finish_order) == {lo, hi}
    assert e.free_blocks == 128 and not e.state_manager.seqs


def test_cancel_mid_stream_releases_blocks(devices8):
    """Client cancel mid-stream ends the iterator with
    RequestCancelled and returns every KV block to the pool (leak
    regression)."""
    e = _engine()

    async def main():
        async with AsyncInferenceServer(e, ServingConfig(k_steps=2)) as s:
            h = await s.submit([1, 2, 3, 4, 5], max_new_tokens=100)
            got = []
            with pytest.raises(RequestCancelled):
                async for t in h:
                    got.append(t)
                    if len(got) >= 3:
                        h.cancel()
            # the flush lands at the next dispatch boundary
            for _ in range(200):
                if e.free_blocks == 128:
                    break
                await asyncio.sleep(0.02)
            return got

    got = asyncio.run(main())
    assert got
    assert e.free_blocks == 128 and not e.state_manager.seqs


def test_fused_admission_ring_greedy_parity(devices8):
    """Ring mode (in-graph admission + device-ring drain) emits
    bit-identical greedy tokens to the default chain driver, with
    fewer host-blocking reads (one drain per chain)."""
    ref = _engine().generate_fused(PROMPTS, max_new_tokens=10, k_steps=3)
    e = _engine(fused_admission=True, max_inflight_dispatches=3)
    got = e.generate_fused(PROMPTS, max_new_tokens=10, k_steps=3)
    assert got == ref
    assert e.free_blocks == 128 and not e.state_manager.seqs
    m = e.serving_metrics()
    assert m["dispatches_per_token"] <= 0.25, m


def test_ring_mode_eos_swap_constrained_and_stochastic(devices8):
    """Ring-mode wrinkles: in-graph EOS + staged-slot swap under a
    constrained pool matches the per-tick driver, and stochastic
    decode stays dispatch-schedule-invariant across modes."""
    model = Llama(size="tiny")
    probe = _engine(model)
    free = probe.generate([[1, 2, 3, 4, 5]], max_new_tokens=10)[0]
    eos = free[4]
    ref = _engine(model).generate([[1, 2, 3, 4, 5], [9, 8, 7]],
                                  max_new_tokens=10, eos_id=eos)
    e = _engine(model, fused_admission=True)
    got = e.generate_fused([[1, 2, 3, 4, 5], [9, 8, 7]],
                           max_new_tokens=10, k_steps=4, eos_id=eos)
    assert got == ref
    # constrained pool: the second prompt is pre-staged and swapped
    # into the first one's slot in-graph
    p = [list(range(10)), list(range(12))]
    ref2 = _engine(model, num_kv_blocks=6).generate(p, max_new_tokens=12)
    e2 = _engine(model, num_kv_blocks=6, fused_admission=True)
    got2 = e2.generate_fused(p, max_new_tokens=12, k_steps=3)
    assert got2 == ref2
    assert e2.free_blocks == 6
    # stochastic invariance across chain and ring disciplines
    kw = dict(max_new_tokens=8, temperature=0.9, top_k=50, seed=13)
    a = _engine(model).generate_fused(PROMPTS[:2], k_steps=2, **kw)
    b = _engine(model, fused_admission=True).generate_fused(
        PROMPTS[:2], k_steps=4, **kw)
    assert a == b


def test_ring_mode_in_graph_swap_occupies_slot(devices8):
    """With more prompts than decode rows, ring mode refills a finished
    row INSIDE the compiled loop: the staged request's tokens appear
    without an intervening host-side operand rebuild, and outputs stay
    bit-identical to the chain driver."""
    model = Llama(size="tiny")
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8], [9, 10]]
    ref = _engine(model, max_ragged_sequence_count=2).generate_fused(
        prompts, max_new_tokens=6, k_steps=3)
    e = _engine(model, max_ragged_sequence_count=2, fused_admission=True,
                max_inflight_dispatches=4)
    got = e.generate_fused(prompts, max_new_tokens=6, k_steps=3)
    assert got == ref
    assert e.free_blocks == 128 and not e.state_manager.seqs


def _load_telemetry_report():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(repo, "tools",
                                         "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serving_regression_gate(tmp_path):
    """tools/telemetry_report.py --diff --gate serving: only the
    serving SLO families participate, per-metric direction-aware
    thresholds apply, exit 1 on regression."""
    tr = _load_telemetry_report()
    a = {"tick_p50_ms": 20.0, "dispatches_per_token": 0.12,
         "ttft_p99_ms": 300.0, "itl_p99_ms": 25.0,
         "chained_tokens_per_sec": 500.0, "fused_occupancy": 0.95,
         "unrelated_series": 1.0}
    pa = tmp_path / "a.json"
    pa.write_text(json.dumps(a))
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps({**a, "tick_p50_ms": 19.0,
                              "unrelated_series": 99.0}))
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({**a, "tick_p50_ms": 25.0,
                               "ttft_p99_ms": 400.0}))
    assert tr.main(["--diff", str(pa), str(ok), "--gate", "serving"]) == 0
    assert tr.main(["--diff", str(pa), str(bad), "--gate", "serving"]) == 1
    diff = tr.diff_snapshots(str(pa), str(bad), gate="serving")
    assert all(r["metric"] != "unrelated_series" for r in diff["rows"])
    assert {r["metric"] for r in diff["regressions"]} == {
        "tick_p50_ms", "ttft_p99_ms"}
    # tick_p50_ms within its 10% gate but past the generic 5% must pass
    edge = tmp_path / "edge.json"
    edge.write_text(json.dumps({**a, "tick_p50_ms": 21.5}))
    assert tr.main(["--diff", str(pa), str(edge),
                    "--gate", "serving"]) == 0
    # speculative-decoding family (ISSUE 9): acceptance_rate /
    # tokens_per_dispatch gate upward, spec_overhead_ms downward
    sa = {"acceptance_rate": 0.9, "tokens_per_dispatch": 2.5,
          "spec_overhead_ms": 40.0}
    ps = tmp_path / "sa.json"
    ps.write_text(json.dumps(sa))
    sbad = tmp_path / "sbad.json"
    sbad.write_text(json.dumps({"acceptance_rate": 0.8,
                                "tokens_per_dispatch": 1.2,
                                "spec_overhead_ms": 60.0}))
    diff2 = tr.diff_snapshots(str(ps), str(sbad), gate="serving")
    assert {r["metric"] for r in diff2["regressions"]} == {
        "acceptance_rate", "tokens_per_dispatch", "spec_overhead_ms"}
    sok = tmp_path / "sok.json"
    sok.write_text(json.dumps({"acceptance_rate": 0.92,
                               "tokens_per_dispatch": 2.6,
                               "spec_overhead_ms": 39.0}))
    assert tr.main(["--diff", str(ps), str(sok),
                    "--gate", "serving"]) == 0
    # per-request component breakdown (ISSUE 10): the OVERHEAD
    # components gate downward at 15%; decode_active scales with
    # output length and must NOT participate
    ca = {"queue_wait_p99_ms": 100.0, "boundary_gap_p50_ms": 10.0,
          "prefill_p99_ms": 50.0, "preempt_stall_p99_ms": 5.0,
          "decode_active_p99_ms": 200.0}
    pca = tmp_path / "ca.json"
    pca.write_text(json.dumps(ca))
    cbad = tmp_path / "cbad.json"
    cbad.write_text(json.dumps({**ca, "queue_wait_p99_ms": 130.0,
                                "prefill_p99_ms": 70.0,
                                "decode_active_p99_ms": 900.0}))
    diff3 = tr.diff_snapshots(str(pca), str(cbad), gate="serving")
    assert {r["metric"] for r in diff3["regressions"]} == {
        "queue_wait_p99_ms", "prefill_p99_ms"}
    assert all(r["metric"] != "decode_active_p99_ms"
               for r in diff3["rows"])
    # within the 15% component gate (but past the generic 5%): passes
    cok = tmp_path / "cok.json"
    cok.write_text(json.dumps({**ca, "boundary_gap_p50_ms": 11.0,
                               "preempt_stall_p99_ms": 5.5}))
    assert tr.main(["--diff", str(pca), str(cok),
                    "--gate", "serving"]) == 0

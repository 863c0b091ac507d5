"""SLO-driven serving control plane (ISSUE 19): fake-clock feedback-
controller state machine, admission shedding,
offline serving planner determinism/crossovers/roundtrip, the new
serving gate rows, and the controller-armed load-step end-to-end."""

import asyncio
import importlib.util
import json
import os

import pytest

from deepspeed_tpu.autotuning.config import AutotuningConfig
from deepspeed_tpu.autotuning.serving import (ServingCalibration,
                                              ServingCandidate,
                                              ServingCostModel,
                                              ServingPlan,
                                              ServingPlanner,
                                              TrafficModel)
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.serve_loop import FusedServeLoop
from deepspeed_tpu.models import Llama
from deepspeed_tpu.serving import (Action, AsyncInferenceServer,
                                   ControllerConfig, RequestFailed,
                                   ServingConfig, ServingController,
                                   Signals)

_ = Action  # re-exported decision record; imported for API coverage


class FakeClock:
    """Deterministic monotonic clock for controller cadence tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _ctl(cfg=None, **kw):
    kw.setdefault("chain_depth", 2)
    kw.setdefault("draft_len", 4)
    kw.setdefault("shed_depth", 0)
    kw.setdefault("clock", FakeClock())
    return ServingController(cfg or ControllerConfig(
        enabled=True, min_shed_depth=4, max_shed_depth=64,
        step_up_after=3), **kw)


HEALTHY = Signals(burn_rate=0.0, slo_ttft_ms=1000.0, slo_itl_ms=50.0)
BURNING = Signals(burn_rate=0.5, slo_ttft_ms=1000.0, slo_itl_ms=50.0)


def test_controller_burn_tightens_admission_first():
    """High SLO burn with no decode saturation signal sheds at the
    queue: halving from max_shed_depth down to the floor, never
    touching the decode-path knobs."""
    calls = []
    c = _ctl(set_shed_depth=calls.append)
    for want in (32, 16, 8, 4):
        a = c.update(BURNING)
        assert (a.action, a.value) == ("shed_tighten", want)
    assert calls == [32, 16, 8, 4]
    # at the floor with decode healthy: hold (no further action)
    assert c.update(BURNING) is None
    assert (c.chain_depth, c.draft_len) == (2, 4)
    assert c.action_counts() == {"shed_tighten": 4}


def test_controller_queue_pressure_signals():
    """Both admission-pressure signals trip shed_tighten: queue_wait
    p99 past queue_wait_frac of the TTFT SLO, and the telemetry-free
    open-requests fallback; one knob moves per interval even when
    every signal trips at once."""
    c = _ctl()
    a = c.update(Signals(queue_wait_p99_ms=600.0, slo_ttft_ms=1000.0))
    assert a.action == "shed_tighten" and "queue_wait" in a.reason
    # fallback: open requests far beyond the live admission bound
    c2 = _ctl()
    a2 = c2.update(Signals(open_requests=100, shed_depth=8))
    assert a2.action == "shed_tighten" and "open" in a2.reason
    # everything bad at once: still exactly one knob per interval
    c3 = _ctl()
    a3 = c3.update(Signals(burn_rate=0.9, queue_wait_p99_ms=900.0,
                           itl_p99_ms=400.0, slo_ttft_ms=1000.0,
                           slo_itl_ms=50.0))
    assert a3.action == "shed_tighten"
    assert (c3.chain_depth, c3.draft_len) == (2, 4)


def test_controller_saturation_steps_depth_then_draft():
    """Decode saturation (ITL p99 past saturation_ratio x SLO) walks
    the decode-path knobs in priority order: chain depth down to the
    floor, then drafts off; ITL above SLO but inside the ratio band is
    the hysteresis hold."""
    c = _ctl()
    sat = Signals(itl_p99_ms=200.0, slo_itl_ms=50.0)
    a = c.update(sat)
    assert (a.action, c.chain_depth) == ("depth_down", 1)
    a = c.update(sat)
    assert (a.action, c.draft_len) == ("draft_off", 0)
    assert c.update(sat) is None        # both floors reached
    # 60ms > 50ms SLO but < 75ms ratio threshold: band, no action
    c2 = _ctl()
    assert c2.update(Signals(itl_p99_ms=60.0, slo_itl_ms=50.0)) is None
    assert (c2.chain_depth, c2.draft_len) == (2, 4)


def test_controller_recovery_reverse_order_and_hysteresis():
    """Recovery needs step_up_after consecutive healthy intervals per
    step and relaxes in REVERSE priority (drafts on, depth up,
    admission loosened last); a mid-streak unhealthy interval resets
    the streak so jittered load cannot flap a knob."""
    c = _ctl()
    for sig in (BURNING, Signals(itl_p99_ms=200.0, slo_itl_ms=50.0),
                Signals(itl_p99_ms=200.0, slo_itl_ms=50.0)):
        c.update(sig)
    assert (c.shed_depth, c.chain_depth, c.draft_len) == (32, 1, 0)
    # burn in the (burn_low, burn_high] band is "not healthy": resets
    # the streak without moving anything
    band = Signals(burn_rate=0.05, slo_ttft_ms=1000.0, slo_itl_ms=50.0)
    assert c.update(HEALTHY) is None
    assert c.update(HEALTHY) is None
    assert c.update(band) is None
    assert c.update(HEALTHY) is None
    assert c.update(HEALTHY) is None
    a = c.update(HEALTHY)               # 3rd consecutive healthy
    assert (a.action, c.draft_len) == ("draft_on", 4)
    for _ in range(2):
        assert c.update(HEALTHY) is None
    a = c.update(HEALTHY)
    assert (a.action, c.chain_depth) == ("depth_up", 2)
    # shed relaxes last; doubling 32 with a base of 0 (shedding off at
    # rest) crosses max_shed_depth, so it switches fully off
    seen = []
    for _ in range(40):
        a = c.update(HEALTHY)
        if a is not None:
            assert a.action == "shed_relax"
            seen.append(a.value)
        if c.shed_depth == 0:
            break
    assert seen == [0]
    assert c.update(HEALTHY) is None    # fully recovered: steady
    # a configured base bound is the relax ceiling: 16 -> 8 under
    # pressure, back to exactly 16 on recovery, never past it
    cb = _ctl(shed_depth=16)
    assert cb.update(BURNING).value == 8
    for _ in range(2):
        assert cb.update(HEALTHY) is None
    a = cb.update(HEALTHY)
    assert (a.action, a.value, cb.shed_depth) == ("shed_relax", 16, 16)
    for _ in range(6):
        assert cb.update(HEALTHY) is None   # at rest: no more actions


def test_controller_maybe_step_rate_limits_on_fake_clock():
    """maybe_step gates on interval_s without wall-clock sleeps: the
    signal reader is only invoked when an interval has elapsed."""
    clock = FakeClock()
    c = _ctl(ControllerConfig(enabled=True, interval_s=1.0,
                              min_shed_depth=4, max_shed_depth=64),
             clock=clock)
    reads = []

    def read():
        reads.append(clock.t)
        return BURNING

    assert c.maybe_step(read).action == "shed_tighten"
    clock.t = 0.5
    assert c.maybe_step(read) is None
    clock.t = 1.0
    assert c.maybe_step(read).action == "shed_tighten"
    assert reads == [0.0, 1.0]
    assert [a.t for a in c.actions] == [0.0, 1.0]


def _bare_server(loop, **cfg):
    """An engine-less AsyncInferenceServer exercising only the
    event-loop admission path (submit/shed bookkeeping — the worker
    thread never starts)."""
    s = AsyncInferenceServer.__new__(AsyncInferenceServer)
    s.__init__(None, ServingConfig(**cfg))
    s._accepting = True
    s._aloop = loop
    return s


def test_shed_fast_fails_counted_never_silent():
    """Past the admission bound a submit fails FAST: the handle is
    already finished with a RequestFailed naming the shed, the shed
    counter moves, and no request state leaks into the open set."""
    async def run():
        s = _bare_server(asyncio.get_running_loop(), shed_queue_depth=2)
        s._open = 2
        h = await s.submit([1, 2, 3])
        with pytest.raises(RequestFailed, match="shed"):
            await h.tokens()
        assert s._shed_count == 1 and s._open == 2
        assert h.uid not in s._handles
        # under the bound: admitted normally
        s._open = 1
        h2 = await s.submit([1, 2, 3])
        assert s._open == 2 and h2.uid in s._handles

    asyncio.run(run())


def test_shed_default_off_admits_unbounded():
    """shed_queue_depth=0 (the default) preserves the pre-ISSUE-19
    admission behavior byte-for-byte: every submit is admitted no
    matter how deep the queue already is."""
    assert ServingConfig().shed_queue_depth == 0

    async def run():
        s = _bare_server(asyncio.get_running_loop())
        s._open = 500
        h = await s.submit([1, 2, 3])
        assert s._open == 501 and h.uid in s._handles
        assert s._shed_count == 0

    asyncio.run(run())


# -- offline planner ---------------------------------------------------

_CAL = ServingCalibration(decode_tick_s=0.004, dispatch_overhead_s=0.002,
                          prefill_tokens_per_s=20_000.0, source="test")


def _traffic(rate, accept=0.0):
    return TrafficModel(arrival_rate_rps=rate, prompt_tokens=16,
                        output_tokens=8, draft_acceptance=accept)


def _planner(traffic, **grids):
    cfg = AutotuningConfig(
        serving_k_steps=grids.get("k_steps", [2, 4]),
        serving_chain_depths=grids.get("chain_depths", [1, 2]),
        serving_ring_modes=[True],
        serving_draft_lens=grids.get("draft_lens", [0]),
        serving_kv_dtypes=["fp16"],
        serving_shed_depths=grids.get("shed_depths", [0, 8]))
    base_eng = {"fused_decode_steps": 4, "max_inflight_dispatches": 2,
                "fused_admission": True, "num_kv_blocks": 128,
                "kv_block_size": 8}
    return ServingPlanner(cfg, _CAL, traffic,
                          base_engine_config=base_eng,
                          base_serving_config={"k_steps": 4},
                          max_rows=8, kv_block_size=8,
                          base_kv_blocks=128)


def test_planner_deterministic_and_plan_roundtrip(tmp_path):
    """Same config -> byte-identical plan JSON (no timestamps, no RNG
    state), and save/load/apply reproduce the chosen engine + serving
    configs exactly — the artifact is the deployment."""
    tr = _traffic(2.0)
    p1 = _planner(tr).plan()
    p2 = _planner(tr).plan()
    assert p1.to_json() == p2.to_json()
    path = tmp_path / "serving_plan.json"
    p1.save(str(path))
    loaded = ServingPlan.load(str(path))
    assert loaded.to_json() == p1.to_json()
    assert loaded.apply() == p1.apply()
    chosen = loaded.chosen
    eng = loaded.engine_config()
    scfg = loaded.serving_config()
    assert isinstance(eng, RaggedInferenceEngineConfig)
    assert eng.fused_decode_steps == chosen["k_steps"]
    assert eng.max_inflight_dispatches == chosen["chain_depth"]
    assert eng.fused_admission == chosen["ring"]
    assert scfg.shed_queue_depth == chosen["shed_depth"]
    assert scfg.k_steps == chosen["k_steps"]
    # ranks are dense from 0 in candidate order (pruned rows trail)
    assert [c["rank"] for c in loaded.ranked()] == list(
        range(len(loaded.ranked())))
    # a stale/foreign document is rejected, not misread
    with pytest.raises(ValueError, match="serving plan"):
        ServingPlan.from_dict({"version": 1, "kind": "autotune"})


def test_cost_model_depth_and_draft_crossovers():
    """The tentpole's discovery claim, in the model's own arithmetic:
    deep chains amortize host RTT (lower ITL) at low load but lose
    capacity at saturation; drafts win only when they hit — zero
    acceptance pays verify compute for nothing."""
    m = ServingCostModel(_CAL, max_rows=8, kv_block_size=8,
                         base_kv_blocks=128)
    deep = ServingCandidate(k_steps=4, chain_depth=4, ring=True)
    shallow = ServingCandidate(k_steps=4, chain_depth=1, ring=True)
    lo = _traffic(1.0)
    assert m.predict(deep, lo)["itl_s"] < m.predict(shallow, lo)["itl_s"]
    assert m.predict(deep, lo)["capacity_rps"] \
        < m.predict(shallow, lo)["capacity_rps"]
    hi = _traffic(200.0)
    assert m.predict(deep, hi)["goodput_rps"] == 0.0    # rho >= 1
    assert m.predict(deep, hi)["queue_wait_s"] == float("inf")
    draft = ServingCandidate(k_steps=4, chain_depth=1, ring=True,
                             draft_len=4)
    hit = _traffic(1.0, accept=0.5)
    assert m.predict(draft, hit)["itl_s"] \
        < m.predict(shallow, hit)["itl_s"]
    assert m.predict(draft, hit)["capacity_rps"] \
        > m.predict(shallow, hit)["capacity_rps"]
    miss = _traffic(1.0, accept=0.0)
    assert m.predict(draft, miss)["itl_s"] \
        > m.predict(shallow, miss)["itl_s"]
    assert m.predict(draft, miss)["capacity_rps"] \
        < m.predict(shallow, miss)["capacity_rps"]


def test_planner_discovers_shedding_at_saturation():
    """Offered 4x capacity, every unbounded candidate predicts goodput
    0 (infinite queue); the planner must choose an admission-bounded
    candidate whose goodput is its capacity — shedding is discovered
    from the queueing term, not hard-coded."""
    m = ServingCostModel(_CAL, max_rows=8, kv_block_size=8,
                         base_kv_blocks=128)
    cap = m.predict(ServingCandidate(k_steps=4, chain_depth=2,
                                     ring=True), _traffic(1.0)
                    )["capacity_rps"]
    plan = _planner(_traffic(4.0 * cap)).plan()
    chosen = plan.chosen
    assert chosen["shed_depth"] > 0
    assert chosen["predicted_goodput_rps"] > 0
    assert 0.0 < chosen["predicted_shed_frac"] < 1.0
    for row in plan.ranked():
        if row["shed_depth"] == 0:
            assert row["predicted_goodput_rps"] == 0.0
            assert row["predicted_queue_wait_ms"] is None  # infinite
    # at light load shedding buys nothing: the planner must NOT pick a
    # shed candidate over an identical unbounded one
    light = _planner(_traffic(2.0)).plan()
    assert light.chosen["predicted_shed_frac"] == 0.0


def _load_telemetry_report():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(repo, "tools",
                                         "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serving_gate_control_plane_rows(tmp_path):
    """The ISSUE 19 gate rows: goodput_under_slo gates upward at 5%,
    the controlled queue-wait p99 downward at 15%, plan_vs_baseline
    upward at 5% — and the deliberately-saturated control arms
    (uncontrolled_*, ctl_ttft/ctl_itl, baseline_/plan_ latency points)
    never participate."""
    tr = _load_telemetry_report()
    assert tr._gate_rule("loadstep.goodput_under_slo_rps",
                         "serving") == (+1, 0.05)
    assert tr._gate_rule("loadstep.ctl_queue_wait_p99_ms",
                         "serving") == (-1, 0.15)
    assert tr._gate_rule("serve_autotune.serving_plan_vs_baseline",
                         "serving") == (+1, 0.05)
    for excluded in ("loadstep.uncontrolled_qw_p99_ms",
                     "loadstep.uncontrolled_goodput_rps",
                     "loadstep.ctl_ttft_p99_ms",
                     "loadstep.ctl_itl_p99_ms",
                     "serve_autotune.baseline_ttft_p99_ms",
                     "serve_autotune.plan_ttft_p99_ms"):
        assert tr._gate_rule(excluded, "serving") is None, excluded
    a = {"goodput_under_slo_rps": 30.0, "ctl_queue_wait_p99_ms": 300.0,
         "serving_plan_vs_baseline": 1.5,
         "uncontrolled_qw_p99_ms": 4000.0}
    pa = tmp_path / "a.json"
    pa.write_text(json.dumps(a))
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"goodput_under_slo_rps": 25.0,
                               "ctl_queue_wait_p99_ms": 400.0,
                               "serving_plan_vs_baseline": 1.1,
                               "uncontrolled_qw_p99_ms": 90000.0}))
    diff = tr.diff_snapshots(str(pa), str(bad), gate="serving")
    assert {r["metric"] for r in diff["regressions"]} == {
        "goodput_under_slo_rps", "ctl_queue_wait_p99_ms",
        "serving_plan_vs_baseline"}
    assert all(r["metric"] != "uncontrolled_qw_p99_ms"
               for r in diff["rows"])
    assert tr.main(["--diff", str(pa), str(bad),
                    "--gate", "serving"]) == 1
    # inside every threshold (and a 20x worse CONTROL arm): passes
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps({"goodput_under_slo_rps": 29.0,
                              "ctl_queue_wait_p99_ms": 330.0,
                              "serving_plan_vs_baseline": 1.46,
                              "uncontrolled_qw_p99_ms": 90000.0}))
    assert tr.main(["--diff", str(pa), str(ok),
                    "--gate", "serving"]) == 0


def test_serve_loop_runtime_knobs_clamp(devices8):
    """The controller's two decode-path knobs on a live loop: chain
    depth clamps to [1, configured max] with no operand-shape change,
    and draft toggling without a configured speculative model is a
    no-op at 0 (the only compiled family)."""
    model = Llama(size="tiny")
    e = InferenceEngineV2(model, RaggedInferenceEngineConfig(
        dtype="float32", kv_block_size=8, num_kv_blocks=128,
        max_chunk_size=16, max_inflight_dispatches=3))
    loop = FusedServeLoop(e, k_steps=2)
    assert loop.depth == 3 and loop.max_depth == 3
    assert loop.set_chain_depth(5) == 3     # ceiling is the config
    assert loop.set_chain_depth(0) == 1
    assert loop.set_chain_depth(2) == 2
    assert loop.set_draft_len(8) == 0       # no spec model configured
    assert loop.set_draft_len(0) == 0


def test_controller_load_step_e2e_sheds_under_burst(devices8):
    """End-to-end (engine-backed, see conftest._SLOW): shedding off at
    rest, the armed controller discovers the overload from the
    open-request fallback, arms a live admission bound mid-run, and
    late submits fast-fail — every submitted request is accounted
    (completed + shed == submitted, zero silent drops) and the engine
    leaks nothing."""
    model = Llama(size="tiny")
    e = InferenceEngineV2(model, RaggedInferenceEngineConfig(
        dtype="float32", kv_block_size=8, num_kv_blocks=128,
        max_chunk_size=16, max_ragged_sequence_count=2,
        fused_decode_steps=2))
    cfg = ServingConfig(
        k_steps=2, shed_queue_depth=0,
        controller=ControllerConfig(enabled=True, interval_s=0.01,
                                    min_shed_depth=2, max_shed_depth=2,
                                    step_up_after=50))

    async def run():
        prompts = [[1 + i, 2, 3] for i in range(14)]
        async with AsyncInferenceServer(e, cfg) as s:
            first = [await s.submit(p, max_new_tokens=8)
                     for p in prompts[:10]]
            # let the worker-thread controller observe 10 open > 2x the
            # 2-deep bound and arm shedding (generous: a cold-start
            # compile blocks the worker, and the controller steps
            # between serve steps on that same thread)
            for _ in range(1500):
                if s._shed_depth:
                    break
                await asyncio.sleep(0.01)
            assert s._shed_depth == 2, "controller never armed the bound"
            late = [await s.submit(p, max_new_tokens=8)
                    for p in prompts[10:]]
            done = shed = 0
            for h in first + late:
                try:
                    toks = await h.tokens()
                    assert len(toks) == 8
                    done += 1
                except RequestFailed as err:
                    assert "shed" in str(err)
                    shed += 1
            m = s.metrics()
            assert shed == s._shed_count == m["shed_requests"] >= 1
            assert done + shed == len(prompts)      # zero silent drops
            assert m["controller_actions"].get("shed_tighten", 0) >= 1
            assert m["controller_shed_depth"] == 2
        assert e.free_blocks == 128 and not e.state_manager.seqs

    asyncio.run(run())

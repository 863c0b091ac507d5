"""``moe/dispatch.py`` ``record_held_expert_counts`` on hand-made metrics
(ISSUE 68): what a finished step's ``moe_*`` scalars become in the registry,
with and without the sweep's own count (a family from before it records
what it did), and the one host event a step with an extra trip leaves. Host
code only: no engine, no compiled step."""

import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.moe.dispatch import record_held_expert_counts
from deepspeed_tpu.telemetry.registry import MetricsRegistry

HELD = {"moe_held_rows": 2816, "moe_held_done": 2816, "moe_held_calls": 5,
        "moe_held_experts": 8}
BLOCKS = {"moe_held_blocks": 40, "moe_held_block": 768, "moe_load_max": 700,
          "moe_load_min": 90}
SWEEP = {"moe_sweep_trips": 5, "moe_sweep_tiles": 80, "moe_sweep_swept": 95,
         "moe_sweep_tile": 256, "moe_sweep_trips_max": 1}
OLD = {"ds_moe_held_rows_total": 2816, "ds_moe_held_calls_total": 5,
       "ds_moe_held_steps_total": 1, "ds_moe_dropped_rows_total": 0,
       "ds_moe_held_experts": 8, "ds_moe_held_tokens_step_min": 70.4,
       "ds_moe_held_tokens_step_max": 70.4}
NEW = {"ds_moe_sweep_trips_total": 5, "ds_moe_sweep_tile_rows": 256,
       "ds_moe_sweep_trips_step_max": 1,
       "ds_moe_sweep_extra_trip_steps_total": 0}


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    telemetry.shutdown()
    yield
    telemetry.shutdown()


def _scalars(*parts):
    return {k: np.int32(v) for part in parts for k, v in part.items()}


def _events():
    tracer = telemetry.get_tracer()
    return [s for s in (tracer.spans() if tracer else ())
            if s.name == "moe_extra_trip"]


@pytest.mark.parametrize("parts, names", [
    ((HELD,), ()), ((HELD, BLOCKS), ("ds_moe_held_blocks_total",)),
    ((HELD, SWEEP), tuple(NEW)),
    ((HELD, BLOCKS, SWEEP), ("ds_moe_held_blocks_total", *NEW)),
], ids=["held", "held+blocks", "held+sweep", "all"])
def test_a_step_records_what_its_metrics_hold(parts, names):
    """The keys a family returns decide what is recorded: without the
    sweep's count no ``ds_moe_sweep_*`` name appears, with it all five do,
    a step with no extra trip counts 0 such steps and leaves no event."""
    telemetry.configure()
    reg = MetricsRegistry()
    record_held_expert_counts(reg, _scalars(*parts))
    for name, want in OLD.items():
        assert reg.get(name).value() == pytest.approx(want), name
    mine = {n for n in reg.names() if "sweep" in n or "blocks" in n}
    assert mine == set(names) | (
        {"ds_moe_sweep_tiles_total"} if SWEEP in parts else set())
    if SWEEP in parts:
        for name, want in NEW.items():
            assert reg.get(name).value() == want, name
        tiles = reg.get("ds_moe_sweep_tiles_total")
        assert (tiles.value(state="live"), tiles.value(state="swept")) == (
            80, 95)
    assert not _events()


def test_an_extra_trip_step_is_counted_and_leaves_one_event():
    """Three finished steps, the second with a call of two trips: one step
    counted, the most trips of a call kept, and ONE ``moe_extra_trip`` span
    with the step's trips and calls; the steps on either side leave none.
    With telemetry off the counters still move and nothing else is asked."""
    telemetry.configure()
    reg = MetricsRegistry()
    long = dict(SWEEP, moe_sweep_trips=6, moe_sweep_swept=114,
                moe_sweep_trips_max=2)
    for sweep in (SWEEP, long, SWEEP):
        record_held_expert_counts(reg, _scalars(HELD, sweep))
    assert reg.get("ds_moe_held_steps_total").value() == 3
    assert reg.get("ds_moe_sweep_extra_trip_steps_total").value() == 1
    assert reg.get("ds_moe_sweep_trips_total").value() == 16
    assert reg.get("ds_moe_sweep_trips_step_max").value() == 2
    assert reg.get("ds_moe_sweep_tiles_total").value(state="swept") == 304
    events = _events()
    assert [e.args for e in events] == [{"trips": 6, "calls": 5}]
    telemetry.shutdown()
    record_held_expert_counts(reg, _scalars(HELD, long))
    assert reg.get("ds_moe_sweep_extra_trip_steps_total").value() == 2

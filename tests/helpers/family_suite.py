"""The cases every family's step goes through (ISSUE 58), their body once:
eight devices train and the counts or the bias move; the counts land in the
registry one step behind (a traced engine beside the untraced one, or one
device: the row's ``engine["behind"]``); the step's scopes are the lists;
and, where the row says the step is rematted, what its kernels run. A thin
``tests/test_<family>_engine.py`` takes them with ``cases(family, ...)``, so
the families still run on six workers and every case keeps a node id of its
own; all of them read ONE build of the cell's step
(``families.program(family)``), and what only one family asserts is handed
in as a hook or stays a case of that family's file."""

import json
import re

import jax
import numpy as np

import deepspeed_tpu as ds
from deepspeed_tpu import telemetry
from deepspeed_tpu.moe.sharded_moe import BIAS_UPDATE_RATE
from deepspeed_tpu.telemetry import scopes

from helpers.families import (BENCH, DS_CONFIG, FAMILIES, _batch, program,
                              step_scopes)


def routed_bias(engine, group):
    """{slot: a routed layer's selection bias as float32} of ``group``."""
    return {slot: np.asarray(jax.device_get(p["moe"]["router_bias"]),
                             np.float32)
            for slot, p in engine.state["master"]["layers"][group].items()
            if "moe" in p}


def _lowered(engine, batch):
    return engine._train_step.lower(
        engine.state, engine._put_batch(batch)).as_text()


def held_counters(reg, row, steps):
    """What every routed family's registry holds after ``steps`` finished
    steps; returns ``reg.get(name).value()``."""
    value = lambda name: reg.get(name).value()  # noqa: E731
    assert value("ds_moe_held_calls_total") == steps * row.engine["calls"]
    assert value("ds_moe_dropped_rows_total") == 0
    assert value("ds_moe_held_experts") == row.held
    # the sweeps counted themselves (ISSUE 68): a trip a call or more, the
    # live tiles among the swept ones
    assert value("ds_moe_held_steps_total") == steps
    assert value("ds_moe_sweep_trips_total") >= steps * row.engine["calls"]
    tiles = reg.get("ds_moe_sweep_tiles_total")
    assert 1 <= tiles.value(state="live") <= tiles.value(state="swept")
    if "block" in row.engine:
        assert value("ds_moe_held_block_rows") == row.engine["block"]
        assert value("ds_moe_held_blocks_total") >= 1
    return value


def latent_rotations_built(reg, config, rotated: bool = True):
    """What gauge ``ds_rope_calls`` says of a latent-attention step at the
    tiny preset's widths (no lane tile among them): q and k built in XLA's
    form at a head of ``nope + rope`` and never by the kernels
    (``tests/test_rope_kernels.py`` runs the families at the cells'
    128 + 64 / 128, where it reads ``form=kernel, head=192``)."""
    gauge = reg.get("ds_rope_calls")
    assert gauge is not None
    built = {tuple(labels[k] for k in ("form", "head", "rotated"))
             for labels in gauge.label_sets()}
    rope = config.qk_rope_head_dim
    assert built == {("xla", str(config.qk_nope_head_dim + rope),
                      str(rope if rotated else 0))}, built


def cell_metrics_read_the_step(family, paths, but=()):
    """The cell's own metric files read only scopes the step carries;
    ``but`` names those whose scope only the cell's own size opens."""
    cell = json.loads((BENCH / "cells" / (
        FAMILIES[family].engine["cell"] + ".json")).read_text())
    for name in set(cell["per_layer"]) - set(but):
        args = json.loads((BENCH / "layer_metrics" / f"{name}.json"
                           ).read_text())["reducer"]["args"]
        for key in ("pattern", "scope"):
            if key in args and "ds" in args[key]:
                rx = re.compile(args[key])
                assert any(rx.search(p) for p in paths), (name, args[key])


def cases(family, *, trained=None, behind=None, scoped=None, paths=()):
    """{name: test function} of ``family``'s row. ``trained(engine)`` (run
    BEFORE the steps; returns what to run on the step's metrics after
    them), ``behind(engine, batch, reg)`` and ``scoped(hlo, paths, work)``
    are the family's own assertions beside the shared ones (``behind``
    runs the traced or one-device engine's steps itself where the shared
    three steps are not its form: Ouro, Xing4); ``paths`` are scope paths
    some instruction of the step has to start with."""
    row = FAMILIES[family]
    e = row.engine

    def test_engine_trains_on_eight_devices_and_the_counts_or_the_bias_move(
            devices8):
        """``ds.initialize`` under ZeRO-3 bf16 over ``fsdp`` = 8 (the
        kernels per shard), a falling loss, the held experts' counts as
        device scalars of the step, and a selection bias moved by
        ``after_step`` and not by the optimizer: whole rates a step (the
        optimizer's weight decay and AdamW's step would leave no such
        grid)."""
        step = program(family)
        engine, steps = step.engine, e.get("steps", 4)
        assert engine.topology.sizes["fsdp"] == 8
        before = routed_bias(engine, e["bias"][0]) if "bias" in e else None
        then = trained(engine) if trained is not None else None
        losses = [float(engine.train_batch(step.batch))
                  for _ in range(steps)]
        assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
        m = engine._last_metrics
        if row.held:
            assert int(m["moe_held_calls"]) == e["calls"]
            assert int(m["moe_held_experts"]) == row.held
            assert int(m["moe_held_rows"]) == int(m["moe_held_done"]) > 0
            assert e["calls"] <= int(m["moe_sweep_trips"]) <= e["calls"] * int(
                m["moe_sweep_trips_max"])
            assert int(m["moe_held_rows"]) <= int(m["moe_sweep_tiles"]) * int(
                m["moe_sweep_tile"]) <= int(m["moe_sweep_swept"]) * int(
                    m["moe_sweep_tile"])
            low, high = e["per_expert"]
            assert low < int(m["moe_held_rows"]) / (
                e["calls"] * row.held) < high
        if "block" in e:
            assert int(m["moe_held_block"]) == e["block"]
        after = routed_bias(engine, e["bias"][0]) if "bias" in e else None
        for slot in before or ():
            moved = (after[slot] - before[slot]) / BIAS_UPDATE_RATE
            assert moved.shape == e["bias"][1]
            rtol, atol = e["bias_tol"]
            assert np.allclose(moved, np.round(moved), rtol=rtol, atol=atol)
            assert np.abs(moved).max() <= steps + atol
            assert np.any(np.abs(moved) > 0.5)  # some moved, by whole steps
        if then is not None:
            then(m)

    def test_traced_and_untraced_steps_are_one_program_and_the_counts_land():
        """The counts are outputs of the step, so telemetry adds nothing to
        the compiled program (no host callback; the traced engine's lowered
        text is the untraced one's); on, the engine feeds the registry one
        step behind, from scalars the device has already finished. The
        traced engine then runs the untraced engine's executable: the texts
        were just shown equal, and one program is compiled once a file
        (ISSUE 58; the second compile of Kimi-Linear's step was 85 s)."""
        step = program(family)
        engine, batch = step.engine, step.batch
        untraced = _lowered(engine, batch)
        assert "callback" not in untraced
        telemetry.configure()
        traced, *_ = ds.initialize(model=engine.module,
                                   config=dict(DS_CONFIG))
        assert _lowered(traced, batch) == untraced
        traced._train_step = engine._train_step
        reg = telemetry.get_registry()
        if row.held:
            for _ in range(3):
                traced.train_batch(batch)
            held_counters(reg, row, 2)      # two FINISHED steps
        behind(traced, batch, reg)

    def test_one_device_trains_and_the_counts_land_one_step_behind(
            devices8, monkeypatch):
        model = program(family).model
        telemetry.configure()
        monkeypatch.setattr(jax, "devices", lambda *a, **k: devices8[:1])
        engine, *_ = ds.initialize(model=model, config=dict(
            DS_CONFIG, train_batch_size=2, mesh={"fsdp": 1}))
        assert engine.mesh.size == 1
        batch = _batch(model, b=2)
        reg = telemetry.get_registry()
        if e.get("one_device_steps", 3):
            losses = [float(engine.train_batch(batch)) for _ in range(3)]
            assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
            held_counters(reg, row, 2)      # one step behind
        if behind is not None:
            behind(engine, batch, reg)

    def test_step_scopes_are_the_lists_and_each_kernel_lies_in_its_layer():
        """The compiled step carries the row's scope set and no other (the
        lists of ``telemetry/scopes.py`` it is made of) and every path the
        family names is some instruction's."""
        hlo = program(family).hlo
        assert step_scopes(hlo) == row.scopes
        assert row.scopes <= scopes.KNOWN_SCOPES
        work = scopes.op_work(hlo)
        found = {r["scope"] for r in work.values() if r["scope"]}
        for want in paths:
            assert any(p.startswith(want) for p in found), want
        if scoped is not None:
            scoped(hlo, found, work)

    def test_a_rematted_step_runs_the_forward_kernel_once_an_application():
        """ISSUE 47: the engine's train step of a rematted family holds one
        ``ds_flash_fwd`` and one ``ds_flash_bwd`` an attention layer
        application; the same step built on ``policy=None`` holds the
        forward kernel twice."""
        config = program(family).model.config
        assert config.remat and config.remat_policy == "nothing_saveable"
        calls = program(family).step_kernel_calls
        assert calls["ds_flash_fwd"] == calls["ds_flash_bwd"] == row.rematted
        calls = program(family, patch="keep_nothing").step_kernel_calls
        assert calls["ds_flash_bwd"] == row.rematted
        assert calls["ds_flash_fwd"] == 2 * row.rematted

    def test_a_rematted_step_runs_the_scan_twice_where_groups_are_a_loop():
        """ISSUE 51: where the head groups are a loop (Kimi-Linear) a
        layer's forward and the backward rule's own preparation are what
        is left: the preparation's forward and ``ds_kda_fwd`` twice a
        backward kernel, three times on ``policy=None`` (the layer's rerun
        made ``o`` again); ONE group (Qwen3-Next) keeps nothing: three
        under either (the row's ``scan_runs``). The traced step holds
        each of the scan's calls once a layer at any count of groups
        (ISSUE 59: the body of the ONE rolled loop). Every other kernel but
        ``ds_flash_fwd`` runs as often as under ``policy=None``: the
        backward still needs q, k, v, g and beta, so the convolutions rerun,
        and the gated norm (ISSUE 55) runs its forward twice and its
        backward once a layer under either."""
        kept = program(family).step_kernel_calls
        rerun = program(family, patch="keep_nothing").step_kernel_calls
        assert kept["ds_kda_prep_bwd"] == kept["ds_kda_bwd"] > 0
        assert (kept["ds_gated_norm_fwd"], kept["ds_gated_norm_bwd"]) == (
            2 * kept["ds_kda_bwd"], kept["ds_kda_bwd"])
        for fwd, bwd in (("ds_kda_prep_fwd", "ds_kda_prep_bwd"),
                         ("ds_kda_fwd", "ds_kda_bwd")):
            assert kept[fwd] == row.scan_runs * kept[bwd]
            assert rerun[fwd] == 3 * rerun[bwd] == 3 * kept[bwd]
        moved = {"ds_kda_prep_fwd", "ds_kda_fwd", "ds_flash_fwd"}
        assert {k: n for k, n in kept.items() if k not in moved} == \
            {k: n for k, n in rerun.items() if k not in moved}

    # the scopes' case first: it compiles the step, and the training case
    # finds the executable (Kimi-Linear's compile and four steps in one
    # case were 110 s beside five other files' first compiles)
    mine = [test_step_scopes_are_the_lists_and_each_kernel_lies_in_its_layer,
            test_engine_trains_on_eight_devices_and_the_counts_or_the_bias_move]
    if e.get("behind") == "traced":
        mine.append(
            test_traced_and_untraced_steps_are_one_program_and_the_counts_land)
    elif e.get("behind") == "one_device":
        mine.append(test_one_device_trains_and_the_counts_land_one_step_behind)
    if isinstance(row.rematted, int):
        mine.append(
            test_a_rematted_step_runs_the_forward_kernel_once_an_application)
    if row.scan_runs:
        mine.append(
            test_a_rematted_step_runs_the_scan_twice_where_groups_are_a_loop)
    return {fn.__name__: fn for fn in mine}

"""LFM2-MoE (ISSUE 54) through the engine: ``ds.initialize`` under ZeRO-3
bf16 on one device and on eight, the held experts' counts, the expert bias
moving against the load, the step's scopes
(``tests/test_lfm2_moe_reference.py`` holds the model to its reference,
``tests/test_step_pins.py`` every family's train step to its parent's). A
CPU run shows results and counts, never a time."""

import json
import re

import jax
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu import telemetry
from deepspeed_tpu.moe.sharded_moe import BIAS_UPDATE_RATE
from deepspeed_tpu.telemetry import scopes

from helpers.family_cases import BENCH
from helpers.family_cases import DS_CONFIG as _DS_CONFIG
from helpers.family_cases import _batch, _telemetry_isolation  # noqa: F401
from helpers.family_cases import lfm_tiny as _tiny


# ---- the engine ------------------------------------------------------------
@pytest.fixture(scope="module")
def lfm_engine():
    model = _tiny(attn_impl="flash", loss_chunk=64)
    engine, *_ = ds.initialize(model=model, config=dict(_DS_CONFIG))
    return engine, _batch(model, b=8)


def _biases(engine):
    return {slot: np.asarray(jax.device_get(p["moe"]["router_bias"]),
                             np.float32)
            for slot, p in engine.state["master"]["layers"]["tail"].items()}


def test_engine_trains_on_eight_devices_and_moves_the_bias_against_the_load(
        devices8, lfm_engine):
    """``ds.initialize`` under ZeRO-3 bf16 over ``fsdp`` = 8 (the gated
    convolution's and the flash kernels per shard), a falling loss, the
    held experts' counts as device scalars of the step, and the expert
    bias of every routed layer moved by ``after_step`` and not by the
    optimizer: one rate a step, against the sign of its expert's load."""
    engine, batch = lfm_engine
    assert engine.topology.sizes["fsdp"] == 8
    before = _biases(engine)
    losses = [float(engine.train_batch(batch)) for _ in range(4)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    m = engine._last_metrics
    assert int(m["moe_held_calls"]) == 2 and int(m["moe_held_experts"]) == 8
    assert int(m["moe_held_rows"]) == int(m["moe_held_done"]) > 0
    # 8 x 128 tokens x top-4 of 64 experts: 64 a held expert if even
    assert 40 < int(m["moe_held_rows"]) / (2 * 8) < 90
    assert int(m["moe_held_block"]) == 128
    assert int(m["moe_load_max"]) > 64 > int(m["moe_load_min"])
    after = _biases(engine)
    for slot in before:
        moved = (after[slot] - before[slot]) / BIAS_UPDATE_RATE
        # four steps of +-1 rate (0 where the load sat on the mean): the
        # optimizer's weight decay and AdamW's step would leave no such grid
        assert np.allclose(moved, np.round(moved), atol=2e-2), slot
        assert np.abs(moved).max() <= 4 + 2e-2 and np.any(moved != 0)
    params = engine.state["params"]
    stats = {"tail": {"0": {
        "load": np.arange(64, dtype=np.int32), "done": np.int32(0),
        "blocks": np.int32(1), "block": np.int32(128)}}}
    moved, _ = engine.module.after_step(
        {"layers": {"tail": {"0": params["layers"]["tail"]["0"]}}}, stats)
    step = np.asarray(
        moved["layers"]["tail"]["0"]["moe"]["router_bias"], np.float32
    ) - np.asarray(params["layers"]["tail"]["0"]["moe"]["router_bias"],
                   np.float32)
    assert np.all(step[:31] > 0) and np.all(step[33:] < 0)


def test_one_device_trains_and_the_counts_land_one_step_behind(
        devices8, monkeypatch):
    model = _tiny(attn_impl="flash", loss_chunk=64)
    telemetry.configure()
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices8[:1])
    engine, *_ = ds.initialize(model=model, config=dict(
        _DS_CONFIG, train_batch_size=2, mesh={"fsdp": 1}))
    assert engine.mesh.size == 1
    batch = _batch(model, b=2)
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    reg = telemetry.get_registry()
    value = lambda name: reg.get(name).value()  # noqa: E731
    assert value("ds_moe_held_calls_total") == 2 * 2    # one step behind
    assert value("ds_moe_dropped_rows_total") == 0
    assert value("ds_moe_held_experts") == 8
    assert value("ds_moe_held_block_rows") == 128
    assert value("ds_moe_held_blocks_total") >= 1
    assert reg.get("ds_moe_load_step_max").value() >= reg.get(
        "ds_moe_load_step_min").value()


def test_step_scopes_are_the_lists_and_each_kernel_lies_in_its_layer(
        lfm_engine):
    """The step carries ``LFM_SCOPES`` beside the scopes it shares (the
    attention kind under ds.attn with ds.qk_norm and ds.rope, the dense
    layer's ds.mlp, the routed layers' three and their kernels'); the
    gated convolution's kernels lie under ds.gconv/ds.gconv_mix in the
    forward, in remat's rerun and in the backward rule; and the cell's own
    metric files read only scopes the step carries."""
    engine, batch = lfm_engine
    hlo = engine._train_step.lower(
        engine.state, engine._put_batch(batch)).compile().as_text()
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        found.update(re.findall(r"ds\.[A-Za-z0-9_]+", op_name))
    assert found == (set(scopes.DEVICE_SCOPES) | set(scopes.LFM_SCOPES)
                     | {"ds.qk_norm", "ds.rope", "ds.moe_router",
                        "ds.moe_experts", "ds.moe_gmm_fwd", "ds.moe_gmm_bwd",
                        "ds.moe_add_rows"})
    paths = {row["scope"] for row in scopes.op_work(hlo).values()
             if row["scope"]}
    for want in ("fwd:ds.layers/ds.gconv/ds.gconv_in",
                 "fwd:ds.layers/ds.gconv/ds.gconv_mix",
                 "bwd:ds.layers/ds.gconv/ds.gconv_mix",
                 "fwd:ds.layers/ds.gconv/ds.gconv_out",
                 "bwd:ds.layers/ds.gconv/ds.gconv_in",
                 "fwd:ds.layers/ds.attn/ds.flash_fwd",
                 "bwd:ds.layers/ds.attn/ds.flash_bwd",
                 "fwd:ds.layers/ds.attn/ds.qk_norm",
                 "fwd:ds.layers/ds.attn/ds.rope",
                 "fwd:ds.layers/ds.mlp", "bwd:ds.layers/ds.mlp",
                 "fwd:ds.layers/ds.moe_router",
                 "fwd:ds.layers/ds.moe_experts/ds.moe_gmm_fwd",
                 "bwd:ds.layers/ds.moe_experts/ds.moe_gmm_bwd"):
        assert any(p.startswith(want) for p in paths), want
    mix = [p for p in paths if "ds.gconv_mix" in p]
    assert mix and all(re.search(r"ds\.gconv\b.*ds\.gconv_mix\b", p)
                       for p in mix), mix
    cell = json.loads((BENCH / "cells" / "train-conv-s8k-1chip.json"
                       ).read_text())
    for name in cell["per_layer"]:
        args = json.loads((BENCH / "layer_metrics" / f"{name}.json"
                           ).read_text())["reducer"]["args"]
        for key in ("pattern", "scope"):
            if key in args:
                rx = re.compile(args[key])
                assert any(rx.search(p) for p in paths), (name, args[key])

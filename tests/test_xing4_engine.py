"""Xing4 (ISSUE 56) through the engine: ``ds.initialize`` under ZeRO-3 bf16
on one device and on eight, the held experts' counts, the selection bias
moving against the load, the Sinkhorn residual arriving in the step's
metrics and in the registry, the step's scopes
(``tests/test_xing4.py`` holds the model to its reference,
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
from helpers.family_cases import xing_tiny as _tiny


@pytest.fixture(scope="module")
def xing_engine():
    model = _tiny(attn_impl="flash", loss_chunk=64)
    engine, *_ = ds.initialize(model=model, config=dict(_DS_CONFIG))
    return engine, _batch(model, b=8)


def _biases(engine):
    p = engine.state["master"]["layers"]["period"]["0"]["moe"]["router_bias"]
    return np.asarray(jax.device_get(p), np.float32)


def test_engine_trains_on_eight_devices_and_moves_the_bias_against_the_load(
        devices8, xing_engine):
    """``ds.initialize`` under ZeRO-3 bf16 over ``fsdp`` = 8 (the carry
    [B, S, 4 C] pinned as any family's), three steps: the loss falls, the
    held experts' counts and the Sinkhorn residual are device scalars of
    the step, and the selection bias of every routed layer moved by
    ``after_step`` and not by the optimizer: one rate a step, against the
    sign of its expert's load."""
    engine, batch = xing_engine
    assert engine.topology.sizes["fsdp"] == 8
    before = _biases(engine)
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    m = engine._last_metrics
    assert int(m["moe_held_calls"]) == 2 and int(m["moe_held_experts"]) == 8
    assert int(m["moe_held_rows"]) == int(m["moe_held_done"]) > 0
    # 8 x 128 tokens x top-4 of 64 experts: 64 a held expert if even
    assert 40 < int(m["moe_held_rows"]) / (2 * 8) < 90
    assert 0 < float(m["mhc_sinkhorn_residual"]) < 1e-2
    moved = (_biases(engine) - before) / BIAS_UPDATE_RATE
    assert moved.shape == (2, 64)
    # three steps of +-1 rate (0 where the load sat on the mean): the
    # optimizer's weight decay and AdamW's step would leave no such grid
    assert np.allclose(moved, np.round(moved), atol=2e-2)
    assert np.abs(moved).max() <= 3 + 2e-2 and np.any(moved != 0)


def test_one_device_trains_and_the_counter_lands_one_step_behind(
        devices8, monkeypatch):
    model = _tiny(attn_impl="flash", loss_chunk=64)
    telemetry.configure()
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices8[:1])
    engine, *_ = ds.initialize(model=model, config=dict(
        _DS_CONFIG, train_batch_size=2, mesh={"fsdp": 1}))
    assert engine.mesh.size == 1
    batch = _batch(model, b=2)
    reg = telemetry.get_registry()
    engine.train_batch(batch).block_until_ready()
    assert reg.get("ds_mhc_sinkhorn_residual") is None     # one step behind
    losses = [float(engine.train_batch(batch)) for _ in range(2)]
    assert np.all(np.isfinite(losses))
    residual = reg.get("ds_mhc_sinkhorn_residual").value()
    assert 0 < residual < 1e-2
    assert reg.get("ds_moe_held_calls_total").value() == 2 * 2
    assert reg.get("ds_moe_dropped_rows_total").value() == 0
    # the gauge keeps the largest of any step
    type(model).record_step_metrics(reg, {
        **{k: np.float32(1) for k in (
            "moe_held_rows", "moe_held_done", "moe_held_calls",
            "moe_held_experts")}, "mhc_sinkhorn_residual": np.float32(0.5)})
    type(model).record_step_metrics(reg, {
        **{k: np.float32(1) for k in (
            "moe_held_rows", "moe_held_done", "moe_held_calls",
            "moe_held_experts")}, "mhc_sinkhorn_residual": np.float32(0.1)})
    assert reg.get("ds_mhc_sinkhorn_residual").value() == 0.5


def test_step_scopes_are_the_lists_and_the_passes_lie_round_their_sublayer(
        xing_engine):
    """The step carries ``MHC_SCOPES`` beside the scopes it shares (the
    latent attention under ds.attn with ds.rope and the flash kernels'
    two, the leading dense layer's ds.mlp, the routed layers' four and
    their kernels'); the two passes and the coefficients lie under ds.mhc
    in the forward, in remat's rerun and in the backward; and the cell's
    own metric files read only scopes the step carries."""
    engine, batch = xing_engine
    hlo = engine._train_step.lower(
        engine.state, engine._put_batch(batch)).compile().as_text()
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        found.update(re.findall(r"ds\.[A-Za-z0-9_]+", op_name))
    assert found == (set(scopes.DEVICE_SCOPES) | set(scopes.MHC_SCOPES)
                     | {"ds.rope", "ds.moe_router", "ds.moe_experts",
                        "ds.moe_shared", "ds.moe_gmm_fwd", "ds.moe_gmm_bwd",
                        "ds.moe_add_rows"})
    assert set(scopes.MHC_SCOPES) <= scopes.KNOWN_SCOPES
    paths = {row["scope"] for row in scopes.op_work(hlo).values()
             if row["scope"]}
    for want in ("fwd:ds.layers/ds.mhc_spread", "fwd:ds.layers/ds.mhc_fold",
                 "fwd:ds.layers/ds.mhc/ds.mhc_pre",
                 "bwd:ds.layers/ds.mhc/ds.mhc_pre",
                 "fwd:ds.layers/ds.mhc/ds.mhc_coef",
                 "bwd:ds.layers/ds.mhc/ds.mhc_coef",
                 "fwd:ds.layers/ds.mhc/ds.mhc_post",
                 "bwd:ds.layers/ds.mhc/ds.mhc_post",
                 "fwd:ds.layers/ds.attn/ds.flash_fwd",
                 "bwd:ds.layers/ds.attn/ds.flash_bwd",
                 "fwd:ds.layers/ds.attn/ds.rope",
                 "fwd:ds.layers/ds.mlp", "bwd:ds.layers/ds.mlp",
                 "fwd:ds.layers/ds.moe_router", "fwd:ds.layers/ds.moe_shared",
                 "fwd:ds.layers/ds.moe_experts/ds.moe_gmm_fwd",
                 "bwd:ds.layers/ds.moe_experts/ds.moe_gmm_bwd"):
        assert any(p.startswith(want) for p in paths), want
    for part in ("ds.mhc_pre", "ds.mhc_coef", "ds.mhc_post"):
        inside = [p for p in paths if part in p]
        assert inside and all(re.search(rf"ds\.mhc\b.*{part}\b", p)
                              for p in inside), inside
    cell = json.loads((BENCH / "cells" / "train-mhc-s8k-1chip.json"
                       ).read_text())
    for name in cell["per_layer"]:
        metric = json.loads((BENCH / "layer_metrics" / f"{name}.json"
                             ).read_text())
        args = metric["reducer"]["args"]
        pattern = args.get("pattern") or args.get("scope")
        if pattern and "ds" in pattern:
            assert any(re.search(pattern, p) for p in paths), name

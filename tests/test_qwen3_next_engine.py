"""Qwen3-Next (ISSUE 46) through the engine: ``ds.initialize`` under ZeRO-3
bf16 on one device and on eight, the held experts' counts, the step's
scopes (``tests/test_qwen3_next_reference.py`` holds the model to its
reference, ``tests/test_step_pins.py`` every family's train step to its
parent's). A CPU run shows results and counts, never a time."""

import re

import jax
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import scopes

from helpers import hlo_text
from helpers.family_cases import DS_CONFIG as _DS_CONFIG
from helpers.family_cases import _batch, _telemetry_isolation  # noqa: F401
from helpers.family_cases import qnext_tiny as _tiny


# ---- the engine ------------------------------------------------------------
@pytest.fixture(scope="module")
def qnext_engine():
    model = _tiny(attn_impl="flash", loss_chunk=64)
    engine, *_ = ds.initialize(model=model, config=dict(_DS_CONFIG))
    return engine, _batch(model, b=8)


def test_engine_trains_on_eight_devices_and_counts_its_held_rows(
        devices8, qnext_engine):
    """``ds.initialize`` under ZeRO-3 bf16 over ``fsdp`` = 8 (the scan's,
    the convolution's and the flash kernels per shard), a falling loss,
    an ``after_step`` that returns the weights it was given, and the held
    experts' counts as device scalars of the step."""
    engine, batch = qnext_engine
    assert engine.topology.sizes["fsdp"] == 8
    params = {"layers": {"tail": {}}}
    assert engine.module.after_step(params, {})[0] is params
    losses = [float(engine.train_batch(batch)) for _ in range(4)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    m = engine._last_metrics
    assert int(m["moe_held_calls"]) == 4 and int(m["moe_held_experts"]) == 32
    assert int(m["moe_held_rows"]) == int(m["moe_held_done"]) > 0
    # 8 x 128 tokens x top-10 of 512 experts: 20 a held expert if even
    assert 12 < int(m["moe_held_rows"]) / (4 * 32) < 30
    assert int(m["moe_held_block"]) == 128


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
    assert value("ds_moe_held_calls_total") == 2 * 4    # one step behind
    assert value("ds_moe_dropped_rows_total") == 0
    assert value("ds_moe_held_experts") == 32
    assert value("ds_moe_held_block_rows") == 128
    assert value("ds_moe_held_blocks_total") >= 1


def test_step_scopes_are_the_lists_and_each_kernel_lies_in_its_layer(
        qnext_engine):
    """The scan's scope inside ds.gdn and the flash kernels' inside
    ds.attn_gated in the forward, in remat's rerun and in the backward
    rule, so the cell's metrics read this family's kernels alone; the
    three ds.moe_* scopes in both directions."""
    engine, batch = qnext_engine
    hlo = engine._train_step.lower(
        engine.state, engine._put_batch(batch)).compile().as_text()
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        found.update(re.findall(r"ds\.[A-Za-z0-9_]+", op_name))
    assert found == (set(scopes.DEVICE_SCOPES) - {"ds.attn", "ds.mlp"}
                     | set(scopes.GDN_SCOPES) | set(scopes.MIXER_SCOPES)
                     | (set(scopes.KIND_SCOPES) - {"ds.kda", "ds.mla"})
                     | {"ds.rope"})
    paths = {row["scope"] for row in scopes.op_work(hlo).values()
             if row["scope"]}
    for want in ("fwd:ds.layers/ds.gdn/ds.kda_scan",
                 "bwd:ds.layers/ds.gdn/ds.kda_scan",
                 "fwd:ds.layers/ds.gdn/ds.conv",
                 "fwd:ds.layers/ds.gdn/ds.mix_pre",
                 "fwd:ds.layers/ds.gdn/ds.mix_post",
                 "fwd:ds.layers/ds.attn_gated/ds.flash_fwd",
                 "bwd:ds.layers/ds.attn_gated/ds.flash_bwd",
                 "fwd:ds.layers/ds.attn_gated/ds.qk_norm",
                 "fwd:ds.layers/ds.attn_gated/ds.rope",
                 "fwd:ds.layers/ds.moe_shared",
                 "bwd:ds.layers/ds.moe_shared"):
        assert any(p.startswith(want) for p in paths), want
    gdn = re.compile(r"ds\.gdn\b.*ds\.kda_scan\b")
    scans = [p for p in paths if "ds.kda_scan" in p]
    assert scans and all(gdn.search(p) or p.startswith("bwd:") and
                         "ds.kda_scan" in p for p in scans)
    flash = [p for p in paths if "ds.flash_" in p]
    assert flash and all(re.search(r"ds\.attn_gated\b.*ds\.flash_", p)
                         for p in flash), flash
    # ISSUE 55: the gated norm is its kernel pair and no other leaf op
    hlo_text.assert_gated_norm_scope_is_the_kernels(hlo, "ds.gdn",
                                                    ("ds.attn_gated",))

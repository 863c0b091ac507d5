"""Kimi-Linear through the engine (ISSUE 31; a file of its own since PR
41, so that ``--dist loadfile`` gives ``tests/test_kimi_linear.py``'s
long tail a second worker): the compiled ZeRO-3 step of the tiny model,
what it returns and counts, its scopes and kinds, and that the scopes are
metadata. One engine a module; its step stays compiled between cases."""

import re

import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu import telemetry
from deepspeed_tpu.moe.sharded_moe import BIAS_UPDATE_RATE
from deepspeed_tpu.telemetry import scopes

from helpers import hlo_text  # noqa: E402  (tests/helpers)
from helpers.family_cases import DS_CONFIG as _DS_CONFIG
from helpers.family_cases import _batch
from helpers.family_cases import kimi_tiny as _tiny


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    telemetry.shutdown()
    yield
    telemetry.shutdown()


@pytest.fixture(scope="module")
def kimi_engine():
    model = _tiny(attn_impl="flash", loss_chunk=64)
    engine, *_ = ds.initialize(model=model, config=dict(_DS_CONFIG))
    return engine, _batch(model, b=8)


@pytest.fixture(scope="module")
def hlo(kimi_engine):
    return hlo_text.step_hlo(*kimi_engine)


def test_engine_trains_and_only_after_step_moves_the_router_bias(kimi_engine):
    """The optimizer leaves the selection bias alone (not even decayed);
    ``after_step`` moves each by the rate a step, against its load; the
    step returns the held experts' counts as device scalars."""
    engine, batch = kimi_engine
    bias = lambda: np.asarray(  # noqa: E731
        engine.state["master"]["layers"]["period"]["0"]["moe"]
        ["router_bias"]).copy()
    router = lambda: np.asarray(  # noqa: E731
        engine.state["master"]["layers"]["period"]["0"]["moe"]
        ["router"]).copy()
    b0, r0 = bias(), router()
    losses = [float(engine.train_batch(batch)) for _ in range(4)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    moved = np.abs(bias() - b0) / BIAS_UPDATE_RATE
    assert moved.shape == (2, 256) and moved.max() <= 4 + 1e-3
    np.testing.assert_allclose(moved, np.round(moved), atol=1e-3)
    assert 0 < np.mean(moved > 0.5)         # some moved, by whole steps
    assert not np.array_equal(router(), r0)
    m = engine._last_metrics
    assert int(m["moe_held_calls"]) == 4 and int(m["moe_held_experts"]) == 8
    assert int(m["moe_held_rows"]) == int(m["moe_held_done"]) > 0
    # 8 x 128 tokens x top-8 of 256 experts: 32 a held expert if even
    assert 16 < int(m["moe_held_rows"]) / (4 * 8) < 48


def test_traced_and_untraced_steps_are_one_program_and_the_counts_land(
        kimi_engine):
    """The counts are outputs of the step, so telemetry adds nothing to
    the compiled program; on, the engine feeds the registry one step
    behind, from scalars the device has already finished."""
    engine, batch = kimi_engine
    text = lambda e: e._train_step.lower(  # noqa: E731
        e.state, e._put_batch(batch)).as_text()
    untraced = text(engine)
    assert "callback" not in untraced
    telemetry.configure()
    traced, *_ = ds.initialize(model=engine.module, config=dict(_DS_CONFIG))
    assert text(traced) == untraced
    for _ in range(3):
        traced.train_batch(batch)
    reg = telemetry.get_registry()
    calls = reg.counter("ds_moe_held_calls_total").value()
    rows = reg.counter("ds_moe_held_rows_total").value()
    assert calls == 2 * 4       # two finished steps of four routed layers
    assert reg.counter("ds_moe_dropped_rows_total").value() == 0
    assert reg.gauge("ds_moe_held_experts").value() == 8
    low = reg.gauge("ds_moe_held_tokens_step_min").value()
    high = reg.gauge("ds_moe_held_tokens_step_max").value()
    assert 16 < low <= rows / (calls * 8) <= high < 48


def test_step_scopes_are_the_lists(hlo):
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        found.update(re.findall(r"ds\.[A-Za-z0-9_]+", op_name))
    assert found == (set(scopes.DEVICE_SCOPES) - {"ds.attn"}
                     | set(scopes.KIND_SCOPES) | set(scopes.MIXER_SCOPES))
    got = scopes.op_scopes(hlo)
    paths = {p for p in got.values() if p}
    for scope in ("ds.kda/ds.kda_scan", "ds.mla/ds.flash_fwd",
                  "ds.moe_experts", "ds.moe_router", "ds.moe_shared"):
        assert any(p.startswith("fwd:ds.layers") and scope in p
                   for p in paths), scope
    for scope in ("ds.kda_scan", "ds.flash_bwd", "ds.moe_experts"):
        assert any(p.startswith("bwd:ds.layers") and scope in p
                   for p in paths), scope
    # the KDA kernels: the two forwards under fwd: and, run again by remat
    # and by the head group's checkpoint, under bwd:; the recurrence's
    # checkpoint form and the two backward kernels under bwd:; every one
    # of them inside ds.kda_scan, which kda_ms.kda reads
    kernels = {p for p in paths
               if re.search(r"ds\.kda_(prep_)?(fwd|bwd)\b", p)}
    assert all("ds.kda/ds.kda_scan/" in p for p in kernels), kernels
    sides = lambda name: {p.split(":")[0] for p in kernels  # noqa: E731
                          if p.endswith("/" + name)}
    assert sides("ds.kda_fwd") == sides("ds.kda_prep_fwd") == {"fwd", "bwd"}
    assert sides("ds.kda_bwd") == sides("ds.kda_prep_bwd") == {"bwd"}


def test_the_mixer_parts_lie_inside_ds_kda_and_no_kind_is_unknown(
        kimi_engine, hlo):
    """ISSUE 36: the convolution and what lies before and after the scan
    are named inside ds.kda, straight under it in both directions and
    never inside the MLA layer; the layer's pre-norm counts with its
    mixer; and the table of kinds knows every instruction of the step.
    ISSUE 43: the convolution is a kernel pair that holds the SiLU and
    the l2 norms too. ISSUE 55: so is the gated norm: ds.mix_post holds
    ``ds_gated_norm_fwd`` / ``ds_gated_norm_bwd`` and no other leaf op."""
    engine = kimi_engine[0]
    work = scopes.op_work(hlo)
    paths = {row["scope"] for row in work.values()}
    for part in scopes.MIXER_SCOPES:
        mine = {p for p in paths if re.search(rf"{re.escape(part)}\b", p)}
        assert {f"{d}:ds.layers/ds.kda/{part}"
                for d in ("fwd", "bwd")} <= mine, (part, mine)
        if part in ("ds.conv", "ds.mix_post"):  # below: the interpreted
            continue                            # kernels' constants
        assert all("ds.layers/ds.kda/" in p and "ds.mla" not in p
                   for p in mine), (part, mine)
    hlo_text.assert_conv_scope_is_the_kernels(hlo, "ds.kda", ("ds.mla",))
    hlo_text.assert_gated_norm_scope_is_the_kernels(hlo, "ds.kda",
                                                    ("ds.mla",))
    # q, k and v leave ds.conv as [B, S, H d] for the scan: ds.mix_pre
    # holds no bf16 op of their [., ., H, d] any more (g is float32)
    c = engine.module.config
    heads = rf"= bf16\[\d+,\d+,{c.kda_num_heads},{c.kda_head_dim}\]"
    assert not [line for line in hlo.splitlines()
                if re.search(heads, line) and "ds.mix_pre" in line]
    # the layer's pre-norm is the one rsqrt straight under ds.kda (the
    # head's l2 norms and o_norm are the kernels')
    norms = {row["scope"] for name, row in work.items()
             if name.startswith("rsqrt")}
    assert {"fwd:ds.layers/ds.kda", "bwd:ds.layers/ds.kda"} <= norms, norms
    unknown = sorted(n for n, row in work.items() if row["kind"] == "other")
    assert not unknown, unknown


def test_the_named_scopes_are_metadata_and_nothing_else(
        kimi_engine, hlo, monkeypatch):
    """The step compiled with every ``jax.named_scope`` a null context is
    the same optimized program once ``metadata={...}`` is taken out."""
    named, bare = hlo_text.bare_step(*kimi_engine, _DS_CONFIG, monkeypatch,
                                     hlo)
    assert re.search(r"\bds\.[a-z_]+", named) is None     # all metadata
    assert bare == named

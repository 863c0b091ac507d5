"""Qwen3-Next (ISSUE 46) through the engine: ``ds.initialize`` under ZeRO-3
bf16 on one device and on eight, the held experts' counts, the step's
scopes; and the five other cells' families held to the train steps they
had before this PR (``tests/test_qwen3_next.py`` holds the model to its
reference). A CPU run shows results and counts, never a time."""

import hashlib
import re

import jax
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu import telemetry
from deepspeed_tpu.models import (GraniteHybrid, KimiLinear, Mellum, Mistral,
                                  Ouro)
from deepspeed_tpu.telemetry import scopes

from helpers.family_cases import (_batch, _drop_compiled_programs,  # noqa: F401,E501
                                  _telemetry_isolation)
from helpers.family_cases import qnext_tiny as _tiny


# ---- the engine ------------------------------------------------------------
_DS_CONFIG = {
    "train_batch_size": 8, "bf16": {"enabled": True},
    "zero_optimization": {"stage": 3},
    "optimizer": {"type": "AdamW",
                  "params": {"lr": 3e-4, "weight_decay": 0.1}},
    "gradient_clipping": 1.0, "mesh": {"fsdp": -1},
    "steps_per_print": 10 ** 9}


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


# ---- the five other cells' families keep their train steps -----------------
# two layers of each stack; beside each the sha256 of its lowered train step
# AT THE PARENT (commit 142c688, this file's `_step_text` run on that
# checkout): what this PR edits lies on their paths too (`ops/kda.py`
# `chunk_kda` and `kda_prepare`, `moe_ffn_held`'s entry, the scope lists).
# All five hashes are PR 47's, taken again from its tree: every one of these
# steps runs under `nothing_saveable` (`mistral` here at its default policy;
# the `segments` step the Mistral cells run is held to the parent's in
# `tests/test_mellum.py`), which since PR 47 keeps the flash kernel's `o`
# and `lse` (`ops/pallas/_common.py` `KEPT_RESIDUAL`, `_remat_policy`): a
# step holds `ds_flash_fwd` once an attention layer and not twice.
# `kimi_linear`'s and `mellum`'s are PR 48's, taken again from its tree: the
# held sweep's add to tokens is the kernel `ds_moe_add_rows` after one more
# sort and gather, not XLA's scatter-add, which is a routed step's program by
# design; the three families without a routed layer keep PR 47's.
_FAMILIES = {
    "kimi_linear": (KimiLinear, dict(
        num_layers=2, kda_layers=(1,), full_attn_layers=(2,),
        first_k_dense_replace=0, moe_held_experts=8, attn_impl="flash",
        loss_chunk=64, kda_head_groups=2), "a01fbd642111ee72c1f44a792b79b915468e65bff97e6ed0d3247aa40b9da953"),
    "mellum": (Mellum, dict(
        num_layers=2, layer_types=["sliding_attention", "full_attention"],
        moe_held_experts=16, attn_impl="flash", loss_chunk=64),
        "e3c9878334adf0dbb6ed737c78a38f948feaf92ce7c0dd3a3c2848b3aefccbf6"),
    "granite_hybrid": (GraniteHybrid, dict(
        num_layers=2, layer_types=["mamba", "attention"], attn_impl="flash",
        loss_chunk=64), "b577bff512e4102d37f268264abfca574a8a597317e25ad4b7c7d7b427ca3775"),
    "ouro": (Ouro, dict(num_layers=2, attn_impl="flash", loss_chunk=64),
             "2dbcb2addc2f251025db4b1d77ddf3a90d00956b868171db4bc7ffb28ac2c2a6"),
    "mistral": (Mistral, dict(attn_impl="flash", loss_chunk=64,
                              sliding_window=64), "4338c9cbb6e43adf61bfebcf0927a6353f3df99e1f17314838011a5c8baa2901"),
}


def _step_text(family: str) -> str:
    # a kernel is traced once a shape and bound from that trace ever after
    # (``ops/pallas/_common.py`` ``_bind``): one that an earlier test of
    # this file traced under its own model would be bound here
    from deepspeed_tpu.ops.pallas import _common
    _common._TRACED.clear()
    cls, model_kw, _ = _FAMILIES[family]
    model = cls(size="tiny", **model_kw)
    engine, *_ = ds.initialize(model=model, config=dict(_DS_CONFIG))
    tok = np.zeros((8, model.config.max_seq_len), np.int32)
    return engine._train_step.lower(
        engine.state, engine._put_batch((tok, tok))).as_text()


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_the_other_families_steps_are_the_parents_programs(family):
    text = _step_text(family)
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == _FAMILIES[family][2]

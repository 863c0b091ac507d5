"""Laguna (ISSUE 60) through the engine: the shared cases of
``tests/helpers/family_suite.py`` on ONE build of the cell's step and what
only this family asserts: the gate's gauge one step behind, the gate's
scope inside both kinds of attention layer, the cell's metric files on the
step's scopes. A CPU run shows results and counts, never a time."""

import re

from deepspeed_tpu.moe.sharded_moe import held_block

from helpers.families import _telemetry_isolation  # noqa: F401
from helpers.family_suite import cases, cell_metrics_read_the_step


def _trained(engine):
    """No weight moves by ``after_step`` and nothing is kept from the
    optimizer; the step's metrics carry the mean gate of both kinds."""
    params = {"layers": {"tail": {}}}
    assert engine.module.after_step(params, {})[0] is params
    assert not hasattr(engine.module, "optimizer_frozen")

    def then(m):
        assert int(m["moe_held_block"]) == held_block(8 * 128, 10, 256) == 128
        assert 4 * 8 <= int(m["moe_held_blocks"]) <= 4 * 8 * 2
        assert 0 <= int(m["moe_load_min"]) < 40 < int(m["moe_load_max"])
        for kind in ("swa", "full"):
            assert 0.35 < float(m[f"attn_gate_mean_{kind}"]) < 0.65
    return then


def _behind(traced, batch, reg):
    """``ds_attn_gate_mean{kind}`` after two FINISHED steps: fed with the
    held counts, one step behind, from scalars the step returned."""
    value = lambda name, **kw: reg.get(name).value(**kw)  # noqa: E731
    assert value("ds_moe_held_calls_total") == 2 * 4
    gate = reg.get("ds_attn_gate_mean")
    assert sorted(s["kind"] for s in gate.label_sets()) == ["full", "swa"]
    for kind in ("swa", "full"):
        assert 0.35 < value("ds_attn_gate_mean", kind=kind) < 0.65
    assert value("ds_attn_gate_mean", kind="swa") != value(
        "ds_attn_gate_mean", kind="full")
    last = traced._last_metrics     # the third step's: not yet recorded
    assert float(last["attn_gate_mean_swa"]) != value(
        "ds_attn_gate_mean", kind="swa")


def _scoped(hlo, paths, work):
    """Both kernels' scopes, the rotation and the gate lie inside the scope
    of their layer's kind in the forward and in the backward, so one kind's
    kernels and the gate alone can be read; the routed layers' scopes with
    the shared expert's; and every pattern the cell's metric files name
    finds an instruction."""
    for kind in ("swa", "full"):
        for want in (f"fwd:ds.layers/ds.attn_{kind}/ds.flash_fwd",
                     f"bwd:ds.layers/ds.attn_{kind}/ds.flash_bwd",
                     f"fwd:ds.layers/ds.attn_{kind}/ds.rope",
                     f"bwd:ds.layers/ds.attn_{kind}/ds.rope",
                     f"fwd:ds.layers/ds.attn_{kind}/ds.attn_gate",
                     f"bwd:ds.layers/ds.attn_{kind}/ds.attn_gate"):
            assert want in paths, want
        assert f"bwd:ds.layers/ds.attn_{kind}/ds.flash_fwd" not in paths
    kernels = [p for p in paths if "ds.flash_" in p]
    assert all(re.search(r"ds\.attn_(swa|full)/ds\.flash_", p)
               for p in kernels), kernels
    for scope in ("ds.moe_router", "ds.moe_experts", "ds.moe_shared",
                  "ds.mlp"):
        assert {d for d in ("fwd", "bwd") if any(
            p.startswith(d + ":ds.layers") and scope in p
            for p in paths)} == {"fwd", "bwd"}, scope
    cell_metrics_read_the_step("laguna", paths)
    unknown = sorted(n for n, row in work.items() if row["kind"] == "other")
    assert not unknown, unknown


globals().update(cases("laguna", trained=_trained, behind=_behind,
                       scoped=_scoped))

"""Mellum 2 (ISSUE 38) through the engine: the shared cases of
``tests/helpers/family_suite.py`` on ONE build of the cell's step and what
only this family asserts (the cases of ``tests/test_mellum.py`` and, the
rematted step's kernels, of ``tests/test_kept_residuals.py`` until PR 58).
A CPU run shows results and counts, never a time."""

import re

from deepspeed_tpu.moe.sharded_moe import held_block

from helpers.families import _telemetry_isolation  # noqa: F401
from helpers.family_suite import cases


def _trained(engine):
    """``loss(with_stats=True)`` and an ``after_step`` that returns the
    weights it was given, as for the other routed family."""
    params = {"layers": {"tail": {}}}
    assert engine.module.after_step(params, {})[0] is params
    assert not hasattr(engine.module, "optimizer_frozen")

    def then(m):
        assert int(m["moe_held_block"]) == held_block(8 * 128, 8, 64) == 256
        assert 4 * 16 <= int(m["moe_held_blocks"]) <= 4 * 16 * 2
        assert 0 <= int(m["moe_load_min"]) < 128 < int(m["moe_load_max"])
    return then


def _behind(traced, batch, reg):
    value = lambda name: reg.get(name).value()  # noqa: E731
    rows, blocks = (value("ds_moe_held_rows_total"),
                    value("ds_moe_held_blocks_total"))
    assert 0.0 < 1 - rows / (blocks * 256) < 0.75       # the padding
    assert value("ds_moe_load_step_min") < 128 < value("ds_moe_load_step_max")
    assert (value("ds_moe_held_tokens_step_min") <= rows / (8 * 16)
            <= value("ds_moe_held_tokens_step_max"))


def _scoped(hlo, paths, work):
    """Both kernels' scopes lie inside the scope of their layer's kind in
    the forward and in the backward rule, so one kind's kernel time can be
    read alone; the rotation is named inside both, and in remat's rerun,
    which holds no forward kernel (PR 47: the layer keeps its ``o`` and
    ``lse``)."""
    for kind in ("swa", "full"):
        for want in (f"fwd:ds.layers/ds.attn_{kind}/ds.flash_fwd",
                     f"bwd:ds.layers/ds.attn_{kind}/ds.flash_bwd",
                     f"fwd:ds.layers/ds.attn_{kind}/ds.rope",
                     f"bwd:ds.layers/ds.attn_{kind}/ds.rope"):
            assert want in paths, want
        assert f"bwd:ds.layers/ds.attn_{kind}/ds.flash_fwd" not in paths
    kernels = [p for p in paths if "ds.flash_" in p]
    assert all(re.search(r"ds\.attn_(swa|full)/ds\.flash_", p)
               for p in kernels), kernels
    for scope in ("ds.moe_router", "ds.moe_experts"):
        assert {d for d in ("fwd", "bwd") if any(
            p.startswith(d + ":ds.layers") and scope in p
            for p in paths)} == {"fwd", "bwd"}, scope
    # the grouped-matmul kernels (interpreted here) inside the scope
    # moe_ms.mellum reads. Remat's rerun holds no forward sweep: the
    # backward rule keeps the inputs alone and nothing else of the layer
    # reads the sweep's result, so the compiler drops it
    for want in ("fwd:ds.layers/ds.moe_experts/ds.moe_gmm_fwd",
                 "fwd:ds.layers/ds.moe_experts/ds.moe_add_rows",
                 "bwd:ds.layers/ds.moe_experts/ds.moe_gmm_bwd",
                 "bwd:ds.layers/ds.moe_experts/ds.moe_add_rows"):
        assert want in paths, want
    # what the cell's attn_ms.mellum reads: the layer less its kernels
    rx = re.compile(r"ds\.attn_(swa|full)\b(?!.*ds\.flash_)")
    assert any(rx.search(p) for p in paths)
    assert not any(rx.search(p) for p in kernels)
    unknown = sorted(n for n, row in work.items() if row["kind"] == "other")
    assert not unknown, unknown


globals().update(cases("mellum", trained=_trained, behind=_behind,
                       scoped=_scoped))

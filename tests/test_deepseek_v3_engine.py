"""DeepSeek-V3 (ISSUE 64: kanana-2-30b-a3b's block) through the engine: the
shared cases of ``tests/helpers/family_suite.py`` on ONE build of the
cell's step and what only this family asserts: the latent attention's
rotation inside its layer, no merge where the tiny rows are held whole
(``tests/test_deepseek_v3.py`` holds the model to its reference,
``tests/test_step_pins.py`` its train step to its parent's,
``tests/test_flash_spans.py`` a row in spans to ``ds.flash_merge``). A CPU
run shows results and counts, never a time."""

import re

from helpers.families import _telemetry_isolation  # noqa: F401
from helpers.family_suite import (cases, cell_metrics_read_the_step,
                                  latent_rotations_built)


def _behind(engine, batch, reg):
    """The flash call's gauge says one span: the tiny rows are held whole;
    the rotation's says XLA's form: a nope of 16 is no lane tile."""
    gauge = reg.get("ds_flash_segments")
    assert gauge is not None
    assert {gauge.value(**labels) for labels in gauge.label_sets()} == {1.0}
    assert reg.get("ds_moe_dropped_rows_total").value() == 0
    latent_rotations_built(reg, engine.module.config)


def _scoped(hlo, paths, work):
    """The rotation lies inside ds.attn in the forward and in remat's
    rerun; the cell's metric files that read scopes read scopes this step
    carries, but for the merge, which only a row in spans opens."""
    inside = [p for p in paths if "ds.rope" in p]
    assert inside and all(re.search(r"ds\.attn\b.*ds\.rope\b", p)
                          for p in inside), inside
    assert not any("ds.flash_merge" in p for p in paths)
    cell_metrics_read_the_step("deepseek_v3", paths,
                               but=("flash_merge_ms.kan",))


globals().update(cases(
    "deepseek_v3", behind=_behind, scoped=_scoped, paths=(
        "fwd:ds.layers/ds.attn/ds.flash_fwd",
        "bwd:ds.layers/ds.attn/ds.flash_bwd", "fwd:ds.layers/ds.attn/ds.rope",
        "fwd:ds.layers/ds.mlp", "bwd:ds.layers/ds.mlp",
        "fwd:ds.layers/ds.moe_router", "fwd:ds.layers/ds.moe_shared",
        "fwd:ds.layers/ds.moe_experts/ds.moe_gmm_fwd",
        "bwd:ds.layers/ds.moe_experts/ds.moe_gmm_bwd")))

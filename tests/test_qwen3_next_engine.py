"""Qwen3-Next (ISSUE 46) through the engine: the shared cases of
``tests/helpers/family_suite.py`` on ONE build of the cell's step (the
rematted step's kernels were ``tests/test_kept_residuals.py``'s until PR
58) and what only this family asserts
(``tests/test_qwen3_next_reference.py`` holds the model to its reference).
A CPU run shows results and counts, never a time."""

import re

from helpers import hlo_text
from helpers.families import _telemetry_isolation  # noqa: F401
from helpers.family_suite import cases


def _trained(engine):
    """An ``after_step`` that returns the weights it was given."""
    params = {"layers": {"tail": {}}}
    assert engine.module.after_step(params, {})[0] is params
    return None


def _scoped(hlo, paths, work):
    """The scan's scope inside ds.gdn and the flash kernels' inside
    ds.attn_gated in the forward, in remat's rerun and in the backward
    rule, so the cell's metrics read this family's kernels alone."""
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


globals().update(cases(
    "qwen3_next", trained=_trained, scoped=_scoped, paths=(
        "fwd:ds.layers/ds.gdn/ds.kda_scan", "bwd:ds.layers/ds.gdn/ds.kda_scan",
        "fwd:ds.layers/ds.gdn/ds.conv", "fwd:ds.layers/ds.gdn/ds.mix_pre",
        "fwd:ds.layers/ds.gdn/ds.mix_post",
        "fwd:ds.layers/ds.attn_gated/ds.flash_fwd",
        "bwd:ds.layers/ds.attn_gated/ds.flash_bwd",
        "fwd:ds.layers/ds.attn_gated/ds.qk_norm",
        "fwd:ds.layers/ds.attn_gated/ds.rope",
        "fwd:ds.layers/ds.moe_shared", "bwd:ds.layers/ds.moe_shared")))

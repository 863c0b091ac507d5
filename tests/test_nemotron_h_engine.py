"""Nemotron-H (ISSUE 66: Nemotron-3-Super's block) through the engine: the
shared cases of ``tests/helpers/family_suite.py`` on ONE build of the
cell's step and what only this family asserts: every layer is ONE sublayer
(no ``ds.mlp``), the latent's projections stand round the held sweep and
outside it, the Mamba-2 scan runs at two groups inside its mixer
(``tests/test_nemotron_h.py`` holds the model to its reference,
``tests/test_step_pins.py`` its train step to itself). A CPU run shows
results and counts, never a time."""

import re

from helpers.families import _telemetry_isolation  # noqa: F401
from helpers.family_suite import cases, cell_metrics_read_the_step


def _behind(engine, batch, reg):
    assert reg.get("ds_moe_dropped_rows_total").value() == 0


def _scoped(hlo, paths, work):
    """The latent's two projections lie in ds.moe_latent and never inside
    the held sweep; the scan lies inside the mixer; the cell's metric files
    that read scopes read scopes this step carries."""
    latent = [p for p in paths if "ds.moe_latent" in p]
    assert latent and not any("ds.moe_experts" in p for p in latent), latent
    scan = [p for p in paths if re.search(r"ds\.ssd_(fwd|bwd)\b", p)]
    assert scan and all("ds.mamba/ds.ssd" in p for p in scan), scan
    assert not any(re.search(r"ds\.mlp\b", p) for p in paths)
    cell_metrics_read_the_step("nemotron_h", paths)


globals().update(cases(
    "nemotron_h", behind=_behind, scoped=_scoped, paths=(
        "fwd:ds.layers/ds.attn/ds.flash_fwd",
        "bwd:ds.layers/ds.attn/ds.flash_bwd",
        "fwd:ds.layers/ds.mamba/ds.ssd", "bwd:ds.layers/ds.mamba/ds.ssd",
        "fwd:ds.layers/ds.mamba/ds.conv", "fwd:ds.layers/ds.mamba/ds.mix_post",
        "fwd:ds.layers/ds.moe_router", "fwd:ds.layers/ds.moe_latent",
        "bwd:ds.layers/ds.moe_latent", "fwd:ds.layers/ds.moe_shared",
        "fwd:ds.layers/ds.moe_experts/ds.moe_gmm_fwd",
        "bwd:ds.layers/ds.moe_experts/ds.moe_gmm_bwd",
        "fwd:ds.layers/ds.moe_experts/ds.moe_add_rows")))

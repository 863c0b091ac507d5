"""Xing4 (ISSUE 56) through the engine: the shared cases of
``tests/helpers/family_suite.py`` on ONE build of the cell's step and what
only this family asserts: the Sinkhorn residual arriving in the step's
metrics and in the registry, the passes round their sublayer
(``tests/test_xing4.py`` holds the model to its reference,
``tests/test_step_pins.py`` its train step to its parent's). A CPU run
shows results and counts, never a time."""

import re

import numpy as np

from helpers.families import _telemetry_isolation  # noqa: F401
from helpers.family_suite import (cases, cell_metrics_read_the_step,
                                  latent_rotations_built)


def _trained(engine):
    """The carry [B, S, 4 C] is pinned as any family's, and the Sinkhorn
    residual is a device scalar of the step."""
    def then(m):
        assert 0 < float(m["mhc_sinkhorn_residual"]) < 1e-2
    return then


def _behind(engine, batch, reg):
    model = engine.module
    engine.train_batch(batch).block_until_ready()
    assert reg.get("ds_mhc_sinkhorn_residual") is None     # one step behind
    losses = [float(engine.train_batch(batch)) for _ in range(2)]
    assert np.all(np.isfinite(losses))
    residual = reg.get("ds_mhc_sinkhorn_residual").value()
    assert 0 < residual < 1e-2
    assert reg.get("ds_moe_held_calls_total").value() == 2 * 2
    assert reg.get("ds_moe_dropped_rows_total").value() == 0
    latent_rotations_built(reg, model.config)       # no lane tile: XLA's
    # the gauge keeps the largest of any step
    for value in (0.5, 0.1):
        type(model).record_step_metrics(reg, {
            **{k: np.float32(1) for k in (
                "moe_held_rows", "moe_held_done", "moe_held_calls",
                "moe_held_experts")},
            "mhc_sinkhorn_residual": np.float32(value)})
    assert reg.get("ds_mhc_sinkhorn_residual").value() == 0.5


def _scoped(hlo, paths, work):
    """The two passes and the coefficients lie under ds.mhc in the forward,
    in remat's rerun and in the backward."""
    for part in ("ds.mhc_pre", "ds.mhc_coef", "ds.mhc_post"):
        inside = [p for p in paths if part in p]
        assert inside and all(re.search(rf"ds\.mhc\b.*{part}\b", p)
                              for p in inside), inside
    cell_metrics_read_the_step("xing4_0", paths)


globals().update(cases(
    "xing4_0", trained=_trained, behind=_behind, scoped=_scoped, paths=(
        "fwd:ds.layers/ds.mhc_spread", "fwd:ds.layers/ds.mhc_fold",
        "fwd:ds.layers/ds.mhc/ds.mhc_pre", "bwd:ds.layers/ds.mhc/ds.mhc_pre",
        "fwd:ds.layers/ds.mhc/ds.mhc_coef",
        "bwd:ds.layers/ds.mhc/ds.mhc_coef",
        "fwd:ds.layers/ds.mhc/ds.mhc_post",
        "bwd:ds.layers/ds.mhc/ds.mhc_post",
        "fwd:ds.layers/ds.attn/ds.flash_fwd",
        "bwd:ds.layers/ds.attn/ds.flash_bwd", "fwd:ds.layers/ds.attn/ds.rope",
        "fwd:ds.layers/ds.mlp", "bwd:ds.layers/ds.mlp",
        "fwd:ds.layers/ds.moe_router", "fwd:ds.layers/ds.moe_shared",
        "fwd:ds.layers/ds.moe_experts/ds.moe_gmm_fwd",
        "bwd:ds.layers/ds.moe_experts/ds.moe_gmm_bwd")))

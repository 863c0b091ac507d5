"""LFM2-MoE (ISSUE 54) through the engine: the shared cases of
``tests/helpers/family_suite.py`` on ONE build of the cell's step and what
only this family asserts (``tests/test_lfm2_moe_reference.py`` holds the
model to its reference, ``tests/test_step_pins.py`` its train step to its
parent's). A CPU run shows results and counts, never a time."""

import re

import numpy as np

from helpers.families import _telemetry_isolation  # noqa: F401
from helpers.family_suite import cases, cell_metrics_read_the_step


def _trained(engine):
    """The largest and smallest load of any expert straddle the even 64,
    and ``after_step`` moves a bias against the sign of its expert's
    load."""
    def then(m):
        assert int(m["moe_load_max"]) > 64 > int(m["moe_load_min"])
        params = engine.state["params"]
        stats = {"tail": {"0": {
            "load": np.arange(64, dtype=np.int32), "done": np.int32(0),
            "blocks": np.int32(1), "block": np.int32(128)}}}
        moved, _ = engine.module.after_step(
            {"layers": {"tail": {"0": params["layers"]["tail"]["0"]}}}, stats)
        step = np.asarray(
            moved["layers"]["tail"]["0"]["moe"]["router_bias"], np.float32
        ) - np.asarray(params["layers"]["tail"]["0"]["moe"]["router_bias"],
                       np.float32)
        assert np.all(step[:31] > 0) and np.all(step[33:] < 0)
    return then


def _behind(engine, batch, reg):
    assert reg.get("ds_moe_load_step_max").value() >= reg.get(
        "ds_moe_load_step_min").value()


def _scoped(hlo, paths, work):
    """The gated convolution's kernels lie under ds.gconv/ds.gconv_mix in
    the forward, in remat's rerun and in the backward rule."""
    mix = [p for p in paths if "ds.gconv_mix" in p]
    assert mix and all(re.search(r"ds\.gconv\b.*ds\.gconv_mix\b", p)
                       for p in mix), mix
    cell_metrics_read_the_step("lfm2_moe", paths)


globals().update(cases(
    "lfm2_moe", trained=_trained, behind=_behind, scoped=_scoped, paths=(
        "fwd:ds.layers/ds.gconv/ds.gconv_in",
        "fwd:ds.layers/ds.gconv/ds.gconv_mix",
        "bwd:ds.layers/ds.gconv/ds.gconv_mix",
        "fwd:ds.layers/ds.gconv/ds.gconv_out",
        "bwd:ds.layers/ds.gconv/ds.gconv_in",
        "fwd:ds.layers/ds.attn/ds.flash_fwd",
        "bwd:ds.layers/ds.attn/ds.flash_bwd",
        "fwd:ds.layers/ds.attn/ds.qk_norm", "fwd:ds.layers/ds.attn/ds.rope",
        "fwd:ds.layers/ds.mlp", "bwd:ds.layers/ds.mlp",
        "fwd:ds.layers/ds.moe_router",
        "fwd:ds.layers/ds.moe_experts/ds.moe_gmm_fwd",
        "bwd:ds.layers/ds.moe_experts/ds.moe_gmm_bwd")))

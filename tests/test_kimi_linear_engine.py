"""Kimi-Linear through the engine (ISSUE 31): the shared cases of
``tests/helpers/family_suite.py`` and this family's own on ONE build of the
cell's step (``families.program("kimi_linear")``, its KDA heads in two
groups as ``tests/test_step_pins.py`` lowers them): its scopes and kinds,
the rematted step's kernels (``tests/test_kept_residuals.py``'s until PR
58), and that the scopes are metadata."""

import re

import numpy as np

from deepspeed_tpu.telemetry import scopes

from helpers import hlo_text  # noqa: E402  (tests/helpers)
from helpers.families import _telemetry_isolation  # noqa: F401
from helpers.families import program
from helpers.family_suite import cases, latent_rotations_built


def _trained(engine):
    """The optimizer leaves the selection bias alone (not even decayed) and
    moves the router."""
    router = lambda: np.asarray(  # noqa: E731
        engine.state["master"]["layers"]["period"]["0"]["moe"]
        ["router"]).copy()
    r0 = router()

    def then(m):
        assert not np.array_equal(router(), r0)
    return then


def _behind(traced, batch, reg):
    calls = reg.counter("ds_moe_held_calls_total").value()
    rows = reg.counter("ds_moe_held_rows_total").value()
    low = reg.gauge("ds_moe_held_tokens_step_min").value()
    high = reg.gauge("ds_moe_held_tokens_step_max").value()
    assert 16 < low <= rows / (calls * 8) <= high < 48
    # the latent layer's q and k at the tiny widths: XLA's form, unrotated
    latent_rotations_built(reg, traced.module.config, rotated=False)


def _scoped(hlo, paths, work):
    paths = {p for p in scopes.op_scopes(hlo).values() if p}
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


globals().update(cases("kimi_linear", trained=_trained, behind=_behind,
                       scoped=_scoped))


def test_the_mixer_parts_lie_inside_ds_kda_and_no_kind_is_unknown():
    """ISSUE 36: the convolution and what lies before and after the scan
    are named inside ds.kda, straight under it in both directions and
    never inside the MLA layer; the layer's pre-norm counts with its
    mixer; and the table of kinds knows every instruction of the step.
    ISSUE 43: the convolution is a kernel pair that holds the SiLU and
    the l2 norms too. ISSUE 55: so is the gated norm: ds.mix_post holds
    ``ds_gated_norm_fwd`` / ``ds_gated_norm_bwd`` and no other leaf op."""
    step = program("kimi_linear")
    hlo = step.hlo
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
    c = step.model.config
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


def test_the_named_scopes_are_metadata_and_nothing_else():
    """The step compiled with every ``jax.named_scope`` a null context is
    the same optimized program once ``metadata={...}`` is taken out."""
    hlo_text.assert_scopes_are_metadata("kimi_linear")

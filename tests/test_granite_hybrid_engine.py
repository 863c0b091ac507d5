"""Granite 4.0-H (ISSUE 34) through the engine: the shared cases of
``tests/helpers/family_suite.py`` on ONE build of the cell's step and what
only this family asserts (the cases of ``tests/test_granite_hybrid.py`` and,
the rematted step's kernels, of ``tests/test_kept_residuals.py`` until PR
58). A CPU run shows results and counts, never a time."""

import re

import numpy as np

from deepspeed_tpu.telemetry import scopes

from helpers import hlo_text  # noqa: E402  (tests/helpers)
from helpers.families import _telemetry_isolation  # noqa: F401
from helpers.families import program
from helpers.family_suite import cases


def _trained(engine):
    """No ``with_stats``, no ``after_step``, and the tied table's gradient
    reaches it from the lookup and from the head."""
    assert not hasattr(engine.module, "after_step")
    before = np.asarray(engine.state["master"]["embed"]["tokens"]).copy()

    def then(m):
        assert "lm_head" not in engine.state["master"]
        moved = np.abs(np.asarray(
            engine.state["master"]["embed"]["tokens"]) - before)
        assert np.all(moved.max(axis=1) > 0)    # every row: the head's share
    return then


def _scoped(hlo, paths, work):
    by_op = scopes.op_scopes(hlo)
    paths = {p for p in by_op.values() if p}
    for scope in ("ds.mamba/ds.ssd", "ds.attn/ds.flash_fwd", "ds.mlp"):
        assert any(p.startswith("fwd:ds.layers") and scope in p
                   for p in paths), scope
    for scope in ("ds.mamba/ds.ssd", "ds.flash_bwd", "ds.mlp"):
        assert any(p.startswith("bwd:ds.layers") and scope in p
                   for p in paths), scope
    # the scan stands inside the mixer's scope (but for a dozen broadcasts
    # of its constants, the mask and the zero state, which remat's trace
    # names by the innermost scope alone)
    scan = [p for p in by_op.values() if "ds.ssd" in p]
    assert sum("ds.mamba" in p for p in scan) > 0.99 * len(scan)


globals().update(cases("granite_hybrid", trained=_trained, scoped=_scoped))


def test_the_mixer_parts_lie_inside_ds_mamba_and_no_kind_is_unknown():
    """ISSUE 36: the convolution and what lies before and after the scan
    are named inside ds.mamba, straight under it in both directions and
    never inside the attention layer or the FFN (the compiler moves an
    instruction or two of them into the scan's loop, whose path then
    holds theirs); the table of kinds knows every instruction of the
    step. ISSUE 43: the convolution is a kernel pair that holds the SiLU
    too."""
    hlo = program("granite_hybrid").hlo
    work = scopes.op_work(hlo)
    paths = {row["scope"] for row in work.values()}
    for part in scopes.MIXER_SCOPES:
        mine = {p for p in paths if re.search(rf"{re.escape(part)}\b", p)}
        assert {f"{d}:ds.layers/ds.mamba/{part}"
                for d in ("fwd", "bwd")} <= mine, (part, mine)
        if part == "ds.conv":   # below: the interpreted kernels' constants
            continue
        assert all("ds.layers/ds.mamba/" in p and "ds.attn" not in p
                   and "ds.mlp" not in p for p in mine), (part, mine)
    hlo_text.assert_conv_scope_is_the_kernels(
        hlo, "ds.mamba", ("ds.attn", "ds.mlp"))
    unknown = sorted(n for n, row in work.items() if row["kind"] == "other")
    assert not unknown, unknown


def test_the_named_scopes_are_metadata_and_nothing_else():
    """The step compiled with every ``jax.named_scope`` a null context is
    the same optimized program once ``metadata={...}`` is taken out."""
    hlo_text.assert_scopes_are_metadata("granite_hybrid")

"""Ouro's looped stack (ISSUE 42) through the engine: the shared cases of
``tests/helpers/family_suite.py`` on ONE build of the cell's step and what
only this family asserts (the cases of ``tests/test_ouro.py`` and, the
rematted step's kernels, of ``tests/test_kept_residuals.py`` until PR 58).
A CPU run shows results and counts, never a time."""

import re

import jax.numpy as jnp
import numpy as np
import pytest

from helpers.families import _telemetry_isolation  # noqa: F401
from helpers.family_suite import cases


def _trained(engine):
    """``after_step`` hands the exit statistics on as means over the
    micro-batches, and the step returns them as device scalars."""
    params = {"layers": {}}
    stats = {"exit_prob": jnp.ones(4), "exit_nll": jnp.ones(4),
             "exit_entropy": jnp.float32(2), "micro_batches": jnp.float32(2)}
    kept, metrics = engine.module.after_step(params, stats)
    assert kept is params and float(metrics["exit_entropy_mean"]) == 1.0
    assert float(metrics["exit_prob_mean_4"]) == 0.5

    def then(m):
        assert int(m["loop_passes"]) == 4
        assert sum(float(m[f"exit_prob_mean_{i}"]) for i in range(1, 5)) \
            == pytest.approx(1.0, abs=1e-4)
        assert all(np.ndim(m[k]) == 0 for k in m)
        assert 0 < float(m["exit_entropy_mean"]) < np.log(4) + 1e-6
        assert 5 < float(m["exit_nll_mean_1"]) < 7
    return then


def _behind(traced, batch, reg):
    """With telemetry on the engine feeds the model's own recorder one
    step behind."""
    traced.train_batch(batch)
    assert reg.get("ds_loop_passes") is None        # one step behind
    traced.train_batch(batch)
    first = traced._model_metrics_pending
    assert reg.get("ds_loop_passes").value() == 4
    traced.train_batch(batch)
    prob, nll = reg.get("ds_exit_prob_mean"), reg.get("ds_exit_nll_mean")
    got = [prob.value(**{"pass": str(i)}) for i in range(1, 5)]
    assert sum(got) == pytest.approx(1.0, abs=1e-4)
    # the registry holds the step BEFORE the one just dispatched
    assert got[0] == pytest.approx(float(first["exit_prob_mean_1"]))
    assert nll.value(**{"pass": "4"}) == pytest.approx(
        float(first["exit_nll_mean_4"]))
    assert reg.get("ds_exit_entropy_mean").value() == pytest.approx(
        float(first["exit_entropy_mean"]))


def _scoped(hlo, paths, work):
    """ds.loop inside ds.layers with ds.attn / ds.mlp (and the kernels)
    inside it, forward and backward; remat's rerun holds no forward flash
    kernel (PR 47: a layer keeps its ``o`` and ``lse``); ds.exit_gate
    inside ds.loss_head; no op of a kind the table does not know."""
    assert "bwd:ds.layers/ds.loop/ds.attn/ds.flash_fwd" not in paths
    for want in ("fwd:ds.layers/ds.loop/ds.attn/ds.flash_fwd",
                 "bwd:ds.layers/ds.loop/ds.attn/ds.flash_bwd",
                 "fwd:ds.layers/ds.loop/ds.mlp",
                 "bwd:ds.layers/ds.loop/ds.mlp",
                 "fwd:ds.layers/ds.loop"):
        assert want in paths, (want, sorted(paths))
    gate = [p for p in paths if "ds.exit_gate" in p]
    assert gate and all("ds.loss_head/ds.exit_gate" in p for p in gate)
    # what loop_ms.ouro reads: ds.loop less the sublayers
    assert any(re.search(r"ds\.loop\b", p)
               and not re.search(r"ds\.(attn|mlp)\b", p) for p in paths)
    unknown = sorted(n for n, row in work.items() if row["kind"] == "other")
    assert not unknown, unknown


globals().update(cases("ouro", trained=_trained, behind=_behind,
                       scoped=_scoped))

"""Granite 4.0-H (ISSUE 34): Mamba-2 state-space layers with a NoPE
grouped-query attention layer among them, a SwiGLU in every layer, the four
muP multipliers and a tied head, checked on the CPU at tiny sizes against
the plain float32 reference the benchmark keeps
(``benchmark/architectures/granite_hybrid.py``, which imports nothing from
the program). The whole model's loss and gradients against that reference
are ``tests/test_granite_hybrid_reference.py``'s (PR 50), the cell's limits
against planted faults ``tests/test_granite_hybrid_limits.py``'s, and both
callers of the short
convolution's kernels (ISSUE 43) held to their parents' loss and gradients
``tests/test_short_conv_step.py``'s (PR 45: a file is one worker's under
``--dist loadfile``, and this one was 618 s); what they share is
``tests/helpers/family_cases.py``. A CPU run shows results and counts,
never a time."""

import json
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import GraniteHybrid
from deepspeed_tpu.models.stack import stack_plan
from deepspeed_tpu.ops.ssd import chunk_ssd, recurrent_ssd
from deepspeed_tpu.telemetry import scopes

from helpers import hlo_text  # noqa: E402  (tests/helpers)
from helpers.family_cases import DS_CONFIG as _DS_CONFIG
from helpers.family_cases import GRANITE_CONFIG as CONFIG
from helpers.family_cases import (BENCH, _batch, _err,  # noqa: F401
                                  _telemetry_isolation)
from architectures import granite_hybrid as arch  # noqa: E402  (benchmark/,
#                                           on sys.path by family_cases)
from helpers.family_cases import granite_tiny as _tiny
from lib import modelspec  # noqa: E402  (benchmark/, by family_cases)


# ---- the chunked scan against the recurrence -------------------------------
def _ssd_inputs(b=2, s=128, h=4, p=8, g=1, n=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = jnp.exp(jax.random.uniform(k[1], (b, s, h), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    return (jax.random.normal(k[0], (b, s, h, p)), dt,
            -jax.random.uniform(k[2], (h,), minval=1.0, maxval=16.0),
            jax.random.normal(k[3], (b, s, g, n)),
            jax.random.normal(k[4], (b, s, g, n)))


@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("bc_groups", [1, 2, 4])
def test_chunked_ssd_matches_the_recurrence_forward_and_backward(
        bc_groups, chunk):
    """B and C shared by all four heads (as published), by two, by one."""
    args = _ssd_inputs(g=bc_groups)
    want = recurrent_ssd(*args)
    got = chunk_ssd(*args, chunk=chunk)
    assert got.shape == want.shape and _err(got, want) < 1e-5
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    grad = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, r in zip("x dt A B C".split(),
                          grad(lambda *a: chunk_ssd(*a, chunk=chunk)),
                          grad(recurrent_ssd)):
        assert _err(g, r) < 2e-5, name


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_chunked_ssd_stays_finite_and_right_at_the_fastest_decays(dtype):
    """dt at its largest (0.1) times A at its least (-16) is -1.6 a token,
    -410 over a whole chunk of 256: a product exp(a_i) exp(-a_j) would be
    inf x 0 (the float32 overflow PR 31's chip runs found in KDA's decay
    products and no tiny test had); the differences are taken first."""
    x, dt, _, B, C = _ssd_inputs(s=512, h=2, p=8, n=16)
    dt = jnp.full_like(dt, 0.1)
    A = jnp.asarray([-16.0, -1.0])
    want = recurrent_ssd(x, dt, A, B, C)
    cast = lambda v: v.astype(dtype)  # noqa: E731
    got = chunk_ssd(cast(x), dt, A, cast(B), cast(C), chunk=256)
    assert got.dtype == jnp.dtype(dtype)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _err(got.astype(jnp.float32), want) < (
        1e-5 if dtype == "float32" else 2e-2)
    grads = jax.grad(lambda *a: jnp.sum(jnp.square(chunk_ssd(
        *a, chunk=256).astype(jnp.float32))), argnums=(0, 1, 2, 3, 4))(
        cast(x), dt, A, cast(B), cast(C))
    ref = jax.grad(lambda *a: jnp.sum(jnp.square(recurrent_ssd(*a))),
                   argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    for name, g, r in zip("x dt A B C".split(), grads, ref):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert _err(g.astype(jnp.float32), r) < (
            1e-4 if dtype == "float32" else 5e-2), name


def test_chunked_ssd_agrees_with_the_benchmarks_recurrence():
    """The reference's own token-by-token scan (another formulation: a
    state a head, B and C repeated) gives what the program's two forms
    give; a bad shape is refused."""
    args = _ssd_inputs(g=2, seed=5)
    want = arch.ssm_recurrence(*args)
    assert _err(recurrent_ssd(*args), want) < 1e-6
    assert _err(chunk_ssd(*args, chunk=32), want) < 1e-5
    with pytest.raises(ValueError, match="multiple of the chunk"):
        chunk_ssd(*args, chunk=48)
    with pytest.raises(ValueError, match="groups of B and C"):
        x, dt, A, B, C = args       # three heads on two groups of B and C
        chunk_ssd(x[:, :, :3], dt[:, :, :3], A[:3], B, C, chunk=32)


# ---- the configuration, the counts, the plan -------------------------------
def test_the_configuration_file_builds_the_published_model():
    """``lib/modelspec.py`` holds the model as built to every published
    key of the file (``arch.WIDTHS``: the multipliers and the Mamba sizes
    demanded); a preset that drifts fails the run."""
    model = modelspec.build_model(CONFIG, arch, {})
    c = model.config
    assert c.num_params() == 772160448
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    assert n == c.num_params()
    assert (model.lead, model.period, model.repeats, model.left) == (
        0, 1, 5, 5)     # five Mamba layers under one scan, five unrolled
    m = modelspec.reference_model(arch, model)
    assert m["attention_multiplier"] == 1 / 64 and m["logits_scaling"] == 8
    # the program's estimate and the benchmark's count agree to 1%
    assert c.flops_per_token(8192) == pytest.approx(
        arch.train_flops_per_token(m, 8192), rel=0.01)
    drifted = json.loads(json.dumps(CONFIG))
    drifted["residual_multiplier"] = 0.25
    with pytest.raises(ValueError, match="residual_multiplier"):
        modelspec.build_model(drifted, arch, {})
    whole = GraniteHybrid(size="4.0-h-micro").config
    assert 3.0e9 < whole.num_params() < 3.4e9       # "3B"
    assert whole.layer_types.count("attention") == 4
    tiny = _tiny()
    assert tiny.config.num_params() == sum(
        x.size for x in jax.tree.leaves(
            jax.eval_shape(tiny.init, jax.random.PRNGKey(0))))


@pytest.mark.parametrize("kinds,lead,want", [
    ("MMMMMAMMMM", 0, (1, 5, 5)),           # the cell's cut: layers 0 to 9
    ("MMMMMAMMMM" * 4, 0, (10, 4, 0)),      # the published 40 layers
    ("MMAMM", 0, (1, 2, 3)),                # the tiny preset
])
def test_stack_plan_of_the_granite_patterns(kinds, lead, want):
    assert stack_plan(list(kinds), lead) == want


def test_required_operations_by_hand():
    m = modelspec.reference_model(arch, modelspec.build_model(
        CONFIG, arch, {}))
    parts = arch.forward_flops_per_token(m, 8192)
    assert parts["ssd_state"] == 9 * 4 * 64 * 64 * 128
    assert parts["ffn"] == 10 * 6 * 2048 * 8192
    assert parts["head"] == 2 * 2048 * 12544
    assert parts["attention"] == 4 * 64 * 32 * 8193 / 2
    fwd = arch.ssd_call_cost(m, 1, 8192, backward=False)
    assert fwd == {"flops": 9 * 4 * 64 * 64 * 128 * 8192,
                   "bytes": 9 * 8192 * (64 * (2 * 64 * 2 + 4) + 512)}
    bwd = arch.ssd_call_cost(m, 1, 8192, backward=True)
    assert bwd["flops"] == 2 * fwd["flops"] and bwd["bytes"] > fwd["bytes"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert arch.least_seconds(fwd, peaks)[1] == "memory"
    flash = arch.gqa_flash_call_cost(m, 1, 8192, backward=False)
    assert flash["flops"] == 4 * 64 * 32 * 8192 * 8193 // 2
    assert arch.least_seconds(flash, peaks)[1] == "compute"


# ---- the engine ------------------------------------------------------------


@pytest.fixture(scope="module")
def granite_engine():
    model = _tiny(attn_impl="flash", loss_chunk=64)
    engine, *_ = ds.initialize(model=model, config=dict(_DS_CONFIG))
    return engine, _batch(model, b=8)


@pytest.fixture(scope="module")
def hlo(granite_engine):
    return hlo_text.step_hlo(*granite_engine)


def test_engine_trains_through_the_compiled_step(granite_engine):
    """``ds.initialize`` and the engine's compiled step as for every other
    family: no ``with_stats``, no ``after_step``, a falling loss, and the
    tied table's gradient reaches it from the lookup and from the head."""
    engine, batch = granite_engine
    assert not hasattr(engine.module, "after_step")
    before = np.asarray(engine.state["master"]["embed"]["tokens"]).copy()
    losses = [float(engine.train_batch(batch)) for _ in range(4)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert "lm_head" not in engine.state["master"]
    moved = np.abs(np.asarray(
        engine.state["master"]["embed"]["tokens"]) - before)
    assert np.all(moved.max(axis=1) > 0)    # every row: the head's share


def test_step_scopes_are_the_lists(hlo):
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        found.update(re.findall(r"ds\.[A-Za-z0-9_]+", op_name))
    assert found == (set(scopes.DEVICE_SCOPES) | set(scopes.SSM_SCOPES)
                     | set(scopes.MIXER_SCOPES))
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


def test_the_mixer_parts_lie_inside_ds_mamba_and_no_kind_is_unknown(
        hlo):
    """ISSUE 36: the convolution and what lies before and after the scan
    are named inside ds.mamba, straight under it in both directions and
    never inside the attention layer or the FFN (the compiler moves an
    instruction or two of them into the scan's loop, whose path then
    holds theirs); the table of kinds knows every instruction of the
    step. ISSUE 43: the convolution is a kernel pair that holds the SiLU
    too."""
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


def test_the_named_scopes_are_metadata_and_nothing_else(
        granite_engine, hlo, monkeypatch):
    """The step compiled with every ``jax.named_scope`` a null context is
    the same optimized program once ``metadata={...}`` is taken out."""
    named, bare = hlo_text.bare_step(*granite_engine, _DS_CONFIG,
                                     monkeypatch, hlo)
    assert re.search(r"\bds\.[a-z_]+", named) is None     # all metadata
    assert bare == named


def test_importing_the_package_loads_no_state_space_scan():
    """``ops/ssd.py`` and ``ops/kda.py`` are imported where a model's
    stack is traced, so ``deepspeed_tpu.IMPORT_SECONDS`` (the benchmark's
    ``setup_import_s``) does not grow with the families."""
    code = ("import sys, deepspeed_tpu, deepspeed_tpu.models as m; "
            "assert m.GraniteHybrid and m.KimiLinear; "
            "bad = [k for k in sys.modules if k.endswith(('ops.ssd', "
            "'ops.kda', 'ops.pallas.kda'))]; assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=str(BENCH.parent))

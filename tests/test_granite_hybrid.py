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
``tests/helpers/families.py``. A CPU run shows results and counts,
never a time."""

import functools
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import GraniteHybrid
from deepspeed_tpu.models.stack import stack_plan
from deepspeed_tpu.ops.ssd import chunk_ssd, recurrent_ssd

from helpers.families import config_of, tiny
from helpers.families import (BENCH, _batch, _err,  # noqa: F401
                               _telemetry_isolation)
from architectures import granite_hybrid as arch  # noqa: E402  (benchmark/,
#                                           on sys.path by families)
from lib import modelspec  # noqa: E402  (benchmark/, by families)

CONFIG = config_of("granite_hybrid")
_tiny = functools.partial(tiny, "granite_hybrid")


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

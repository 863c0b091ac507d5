"""Kimi-Linear (ISSUE 31): a stack of KDA linear attention, NoPE latent
attention and a held share of sigmoid-routed experts, checked on the CPU
at tiny sizes against the plain float32 reference the benchmark keeps
(``benchmark/architectures/kimi_linear.py``, which imports nothing from
the program). A CPU run shows results and counts, never a time. The cases
that train the engine are ``tests/test_kimi_linear_engine.py`` (PR 41: a
file is one worker's under ``--dist loadfile``, and this one was 1381 s of
the gate's 1470)."""

import gc
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu import telemetry
from deepspeed_tpu.models import KimiLinear, Mistral
from deepspeed_tpu.models.stack import stack_plan
from deepspeed_tpu.moe.sharded_moe import (balance_bias, held_experts_ffn,
                                           moe_ffn_held, sigmoid_top_k)
from deepspeed_tpu.ops import kda as kda_ops
from deepspeed_tpu.ops.kda import chunk_kda, recurrent_kda
from deepspeed_tpu.ops.pallas import kda as kda_kernels
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
from architectures import kimi_linear as arch  # noqa: E402
from lib import modelspec  # noqa: E402

from helpers import kda_reference  # noqa: E402  (tests/helpers)


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    telemetry.shutdown()
    yield
    telemetry.shutdown()


@pytest.fixture(autouse=True)
def _drop_compiled_programs():
    """The KDA tests run the kernels eagerly in interpret mode: a call
    compiles some hundred small programs that no cache ever finds again,
    each a few memory mappings, and a test worker that has run this file
    passed the kernel's 65530 mappings a process and died in XLA's
    compiler (PR 35). Dropping JAX's caches after a test returns them."""
    yield
    jax.clear_caches()
    gc.collect()


def _close(got, want, tol, what=""):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    err = float(jnp.max(jnp.abs(got - want))) / scale
    assert err <= tol, f"{what}: {err} of {scale}"


# ---- the whole model against the plain reference ---------------------------
def _tiny(**kw):
    return KimiLinear(size="tiny", moe_held_experts=8, **kw)


def _batch(model, b=2, s=128, seed=0):
    tok = np.random.default_rng(seed).integers(
        0, model.config.vocab_size, (b, s + 1))
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def _ref_loss(params, tokens, targets, m):
    hidden, _ = arch._forward(params, tokens, m)
    return arch.loss_of(hidden, params["lm_head"], targets)


@pytest.mark.parametrize("variant", ["plain", "flash_chunked_loss_groups",
                                     "no_remat"])
def test_loss_and_gradients_match_the_float32_reference(variant):
    kw = {"plain": {},
          "flash_chunked_loss_groups": dict(attn_impl="flash", loss_chunk=64,
                                            kda_head_groups=2),
          "no_remat": dict(remat=False)}[variant]
    model = _tiny(**kw)
    params = model.init(jax.random.PRNGKey(3))
    tokens, targets = _batch(model)
    m = modelspec.reference_model(arch, model, {"routing_margin": 0.0,
                                                "excluded_share_max": 1.0})
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(_ref_loss)(params, tokens,
                                                     targets, m)
        # jitted: eager, every interpreted kernel call compiles alone
        got, got_g = jax.jit(jax.value_and_grad(model.loss))(
            params, (tokens, targets))
    assert abs(float(got) - float(want)) <= 2e-5 * float(want)
    flat_w = jax.tree_util.tree_leaves_with_path(want_g)
    flat_g = jax.tree_util.tree_leaves_with_path(got_g)
    assert [p for p, _ in flat_w] == [p for p, _ in flat_g]
    for (path, w), (_, g) in zip(flat_w, flat_g):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):
            # selection only: no gradient reaches the correction bias
            assert not np.any(np.asarray(g)), name
            continue
        assert float(jnp.max(jnp.abs(w))) > 0, name
        _close(g, w, 2e-3, name)


def test_reference_logits_match_apply_and_every_position_counts():
    model = _tiny()
    params = model.init(jax.random.PRNGKey(4))
    tokens, targets = _batch(model, b=1)
    m = modelspec.reference_model(arch, model, {"routing_margin": 0.0,
                                                "excluded_share_max": 1.0})
    with jax.default_matmul_precision("highest"):
        loss, tail, counted = arch.reference(params, tokens, targets, m, 32)
        got = jax.jit(model.apply)(params, tokens)[:, -32:]
    assert bool(jnp.all(counted)) and counted.shape == (1, 32)
    _close(got, tail, 1e-4, "tail logits")
    assert abs(loss - float(jax.jit(model.loss)(
        params, (tokens, targets)))) < 1e-4
    # a margin leaves out the positions whose held experts sit near the
    # boundary, and only those
    m["routing_margin"] = 0.05
    with jax.default_matmul_precision("highest"):
        _, _, some = arch.reference(params, tokens, targets, m, 32)
    assert 0 < int(jnp.sum(some)) < 32


@pytest.mark.parametrize("fault", [None, "targets_off_by_one",
                                   "a_chunk_left_out_of_the_count"])
def test_the_cells_loss_limit_catches_a_planted_fault(fault):
    """``check.loss_err`` of the cell's configuration guards the loss
    arithmetic: the program's chunked loss passes it, a loss whose targets
    are shifted once more, or whose mean leaves one chunk's positions out
    of the count, does not (the decision is the benchmark's own)."""
    import json

    from kinds import train_job
    check = json.loads((BENCH / "configs" /
                        "kimi-linear-48b-ep32-zero3-1chip.json").read_text()
                       )["check"]
    model = _tiny(loss_chunk=64)
    params = model.init(jax.random.PRNGKey(3))
    tokens, targets = _batch(model)
    m = modelspec.reference_model(arch, model, check)
    with jax.default_matmul_precision("highest"):
        want = float(_ref_loss(params, tokens, targets, m))
        if fault == "targets_off_by_one":
            targets = jnp.roll(targets, 1, axis=1)
        got = float(jax.jit(model.loss)(params, (tokens, targets)))
    if fault == "a_chunk_left_out_of_the_count":
        got *= targets.size / (targets.size - model.config.loss_chunk)
    numbers = {}
    assert train_job.decide(numbers, want, got, check) == (fault is None)
    assert (numbers["loss_err"] <= check["loss_err"]) == (fault is None)
    assert check["loss_err"] <= 1e-4


# ---- KDA: the chunked form against the recurrence --------------------------
def _kda_inputs(b=2, s=192, h=3, dk=32, dv=16, seed=0):
    rng = np.random.default_rng(seed)
    l2 = lambda x: x / np.sqrt((x ** 2).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = l2(rng.normal(size=(b, s, h, dk))) / np.sqrt(dk)
    k = l2(rng.normal(size=(b, s, h, dk)))
    v = rng.normal(size=(b, s, h, dv))
    g = -np.exp(rng.uniform(-6, 0.5, size=(b, s, h, dk)))
    g[..., 0] = -1.6        # a fast channel: -102 over a chunk of 64
    g[..., 1] = -4.0
    beta = 1 / (1 + np.exp(-rng.normal(size=(b, s, h))))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


@pytest.mark.parametrize("groups", [1, 3])
def test_chunked_kda_matches_the_recurrence_forward_and_backward(groups):
    args = _kda_inputs()
    want = recurrent_kda(*args)
    got = chunk_kda(*args, head_groups=groups)
    _close(got, want, 1e-5, "forward")
    w = jnp.asarray(np.random.default_rng(1).normal(size=want.shape),
                    jnp.float32)
    grad = lambda f: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, r in zip("qkvgb", grad(
            lambda *a: chunk_kda(*a, head_groups=groups)),
            grad(recurrent_kda)):
        assert bool(jnp.all(jnp.isfinite(g))), name
        _close(g, r, 2e-5, f"d{name}")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("per_token", [6.0, 12.0, 20.0])
def test_chunked_kda_stays_finite_where_a_channel_forgets_in_one_token(
        per_token, dtype):
    """Past a log-decay of -5.5 a token a 16-row block's own columns
    overflowed float32 and a training run on the chip went NaN (PR 31):
    8 rows, a clamped exponent and an exact diagonal hold any decay, and
    the kernels (PR 32) get their operands from those; in bfloat16 as the
    cell runs them, too. Two heads are one grid step of the preparation:
    their inverses run side by side in one product (PR 44)."""
    args = _kda_inputs(b=1, s=128, h=2)
    assert kda_kernels._prep_geometry(args[0], args[2], 64)[-1] == 2
    g = args[3].at[..., 0].set(-per_token).at[..., 1].set(-per_token / 2)
    args[3] = g
    tol_o, tol_g = 1e-5, 2e-5
    if dtype == "bfloat16":
        args, tol_o, tol_g = _as_bf16(args), 2e-2, 4e-2
    f32 = lambda f: lambda *a: f(*a).astype(jnp.float32)  # noqa: E731
    want = recurrent_kda(*args)
    got = f32(chunk_kda)(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    _close(got, want, tol_o, "forward")
    grads = jax.grad(lambda *a: jnp.sum(f32(chunk_kda)(*a)),
                     argnums=(0, 1, 2, 3, 4))(*args)
    want_g = jax.grad(lambda *a: jnp.sum(recurrent_kda(*a)),
                      argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("qkvgb", grads, want_g):
        assert bool(jnp.all(jnp.isfinite(a))), name
        _close(a.astype(jnp.float32), b.astype(jnp.float32), tol_g,
               f"d{name}")


def test_chunked_kda_agrees_with_the_benchmarks_recurrence_and_its_shape():
    args = _kda_inputs(b=1, s=128, h=2)
    _close(chunk_kda(*args), arch.kda_recurrence(*args), 1e-5)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        chunk_kda(*[a[:, :100] for a in args])


# ---- KDA: the kernel pair (interpret mode) ---------------------------------
def _as_bf16(args):
    """q, k, v rounded to bfloat16 as the model hands them in; g and beta
    stay float32."""
    return [a.astype(jnp.bfloat16) for a in args[:3]] + list(args[3:])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seq", [192, 256])
@pytest.mark.parametrize("groups", [1, 2])
def test_kda_kernels_match_the_recurrence(groups, seq, dtype, monkeypatch):
    """Outputs and all five gradients through ``ds_kda_fwd`` /
    ``ds_kda_bwd`` with segments of 2 chunks: 4 chunks are two whole
    segments, 3 are padded with one that leaves the state alone."""
    monkeypatch.setattr(kda_kernels, "SEG", 2)
    args = _kda_inputs(s=seq, h=4)
    tol_o, tol_g = 1e-5, 2e-5
    if dtype == "bfloat16":
        args, tol_o, tol_g = _as_bf16(args), 2e-2, 4e-2
    want = recurrent_kda(*args)
    got = chunk_kda(*args, head_groups=groups)
    assert got.dtype == args[2].dtype
    _close(got.astype(jnp.float32), want, tol_o, "forward")
    w = jnp.asarray(np.random.default_rng(1).normal(size=want.shape),
                    jnp.float32)
    grad = lambda f: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w),
        argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, r in zip("qkvgb", grad(
            lambda *a: chunk_kda(*a, head_groups=groups)),
            grad(recurrent_kda)):
        assert g.dtype == r.dtype
        assert bool(jnp.all(jnp.isfinite(g))), name
        _close(g.astype(jnp.float32), r.astype(jnp.float32), tol_g,
               f"d{name}")



# ---- KDA: the preparation's kernel pair (interpret mode) -------------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads_a_step,chunks", [
    (1, 3), (1, 5), (2, 3), (2, 5), (3, 3), (4, 3), (8, 3)])
def test_kda_preparation_kernels_match_the_jax_numpy_preparation(
        heads_a_step, chunks, dtype, monkeypatch):
    """``ds_kda_prep_fwd`` and ``ds_kda_prep_bwd`` against the preparation
    as ``ops/kda.py`` held it in ``jax.numpy`` (``helpers/kda_reference``)
    and its autodiff: the six operands, and the five input gradients from
    six random cotangents. 3 chunks are one grid step of 3, 5 are five
    steps of 1 (a grid step takes a divisor of the chunk count). The heads
    of a grid step take the inverse's float32 products two to a product
    (PR 44): one head runs alone, 2, 4 and 8 are one, two and four pairs,
    3 a pair and a lone head."""
    monkeypatch.setattr(kda_kernels, "PREP_HEADS", heads_a_step)
    monkeypatch.setattr(kda_kernels, "NCK", 4)
    args = _kda_inputs(s=64 * chunks, h=max(2, heads_a_step),
                       b=2 if heads_a_step < 3 else 1)
    tol_o, tol_g = 1e-5, 2e-5
    if dtype == "bfloat16":
        args, tol_o, tol_g = _as_bf16(args), 2e-2, 4e-2
    b, _, h, dk = args[0].shape
    geometry = kda_kernels._prep_geometry(args[0], args[2], 64)
    assert geometry[-2:] == ({3: 3, 5: 1}[chunks], heads_a_step)
    rng = np.random.default_rng(chunks)
    cts = tuple(jnp.asarray(rng.normal(size=x.shape), x.dtype)
                for x in jax.eval_shape(
                    lambda *a: kda_reference.prepare(*a, chunk=64), *args))
    # jitted: eager, every interpreted kernel call compiles alone
    both = lambda f: jax.jit(lambda *a: (  # noqa: E731
        lambda out, pull: (out, pull(cts)))(
            *jax.vjp(lambda *x: f(*x, chunk=64), *a)))(*args)
    want, want_g = both(kda_reference.prepare)
    got, got_g = both(kda_kernels.kda_prepare)
    names = ("u_v", "w", "q_in", "a_qk", "k_out", "shrink")
    for name, x, y in zip(names, got, want):
        assert x.shape == y.shape and x.dtype == y.dtype, name
        assert bool(jnp.all(jnp.isfinite(x))), name
        _close(x.astype(jnp.float32), y.astype(jnp.float32), tol_o, name)
    for name, x, y, a in zip("qkvgb", got_g, want_g, args):
        assert x.shape == a.shape and x.dtype == a.dtype, name
        assert bool(jnp.all(jnp.isfinite(x))), name
        _close(x.astype(jnp.float32), y.astype(jnp.float32), tol_g,
               f"d{name}")


def _strictly_lower(rng, c):
    return jnp.asarray(0.1 * np.tril(rng.normal(size=(c, c)), -1),
                       jnp.float32)


def test_a_pairs_inverse_is_each_heads_inverse_to_the_last_bit():
    """Two heads' [C, C] side by side against the block-diagonal operand
    add exact zeros to each float32 sum: ``T`` of a pair is ``T`` of each
    head taken alone, bit for bit, in a kernel in interpret mode at the
    cell's chunk of 64 (three heads: a pair and a lone one). A chunk of
    128 fills the lanes alone, so nothing pairs. (At a chunk of 16 the
    CPU's own dot sums a contraction of 32 in another order than one of
    16, to 4e-8: XLA's choice of loop, which no MXU shares.)"""
    from jax.experimental import pallas as pl
    c = 64
    rng = np.random.default_rng(c)

    @jax.jit
    def invert(mats):
        def kernel(a_ref, t_ref):
            sides = kda_kernels._inverse_unit_lower(
                [a_ref[h] for h in range(len(mats))])
            assert [x.shape[1] // c for x in sides] == (
                [2] * (len(mats) // 2) + [1] * (len(mats) % 2))
            for h, t in enumerate(kda_kernels._apart(sides)):
                t_ref[h] = t
        return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
            (len(mats), c, c), jnp.float32), interpret=True)(jnp.stack(mats))

    mats = [_strictly_lower(rng, c) for _ in range(3)]
    together = invert(mats)
    for h, a in enumerate(mats):
        np.testing.assert_array_equal(np.asarray(together[h]),
                                      np.asarray(invert([a])[0]))
        _close(together[h] @ (jnp.eye(c) + a), jnp.eye(c), 1e-5,
               "T (I + a) = I")
    wide = [_strictly_lower(rng, 128) for _ in range(2)]
    assert [x.shape for x in kda_kernels._side_by_side(wide)] == [
        (128, 128)] * 2


def test_no_score_matrix_or_inverse_reaches_hbm_and_the_residuals_are_few():
    """``jax.vjp`` of one head group, forward and backward in one jaxpr:
    outside the kernels nothing is a float32 [.., 64, 64] array (the score
    matrices and the inverse live in VMEM; ``a_qk`` and its cotangent are
    in the matmuls' dtype), and the two ``custom_vjp``s keep the five
    inputs and the six operands, nothing else (the segment checkpoints
    are made in the backward)."""
    args = _as_bf16(_kda_inputs(b=1, s=64 * 4, h=2, dk=32, dv=20))
    group = lambda *a: kda_ops._chunk_kda(*a, chunk=64)  # noqa: E731

    def both(*a):
        o, pull = jax.vjp(group, *a)
        return pull(jnp.ones_like(o))

    eqns = list(_walk_eqns(jax.make_jaxpr(both)(*args).jaxpr))
    square = [v.aval for e in eqns for v in e.outvars
              if getattr(v.aval, "shape", ())[-2:] == (64, 64)]
    assert square and all(a.dtype == jnp.bfloat16 for a in square), square
    calls = [e.params["name"] for e in eqns
             if e.primitive.name == "pallas_call"]
    assert sorted(calls) == ["ds_kda_bwd", "ds_kda_fwd", "ds_kda_fwd",
                             "ds_kda_prep_bwd", "ds_kda_prep_fwd"], calls
    _, pull = jax.vjp(group, *args)
    kept = sorted((x.size, str(x.dtype)) for x in jax.tree.leaves(pull))
    ops = kda_kernels.kda_prepare(*args, chunk=64)
    assert kept == sorted((x.size, str(x.dtype)) for x in (*args, *ops))


def _old_step_recurrence(u_v, w, q_in, a_qk, k_out, shrink, out_dtype):
    """The ``lax.scan`` form ``_chunk_kda`` held before the kernels (PR 31),
    on flat heads [BH, N, C, .]: the reference of the six cotangents."""
    dt = w.dtype
    mm = lambda x, y: jnp.matmul(  # noqa: E731
        x.astype(dt), y.astype(dt), preferred_element_type=jnp.float32)

    def step(state, xs):
        u_v, w, q_in, a_qk, k_out, shrink = xs
        u = u_v - mm(w, state)
        o = mm(q_in, state) + mm(a_qk, u)
        state = state * shrink[..., None] + mm(
            jnp.swapaxes(k_out, -1, -2), u)
        return state, o.astype(out_dtype)

    xs = tuple(jnp.swapaxes(x, 0, 1)
               for x in (u_v, w, q_in, a_qk, k_out, shrink))
    init = jnp.zeros((w.shape[0], w.shape[-1], u_v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, init, xs)
    return jnp.swapaxes(o, 0, 1)


@pytest.mark.parametrize("chunks", [4, 5])
@pytest.mark.parametrize("heads_a_step", [1, 2])
def test_kda_backward_kernel_alone_matches_the_old_steps_vjp(
        heads_a_step, chunks, monkeypatch):
    """``ds_kda_bwd`` on segment checkpoints of ``ds_kda_fwd``'s second
    form against ``jax.vjp`` of the scan step, cotangent by cotangent; the
    checkpoints are the states the scan carries into each segment."""
    monkeypatch.setattr(kda_kernels, "SEG", 2)
    monkeypatch.setattr(kda_kernels, "HEADS", heads_a_step)
    bh, c, dk, dv = 4, 16, 32, 16
    rng = np.random.default_rng(chunks)
    rn = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    ops = (rn(bh, chunks, c, dv), 0.3 * rn(bh, chunks, c, dk),
           rn(bh, chunks, c, dk), 0.3 * rn(bh, chunks, c, c),
           0.3 * rn(bh, chunks, c, dk),
           jnp.asarray(rng.uniform(0.2, 1.0, (bh, chunks, dk)), jnp.float32))
    do = rn(bh, chunks, c, dv)
    want_o, pull = jax.vjp(
        lambda *x: _old_step_recurrence(*x, jnp.float32), *ops)
    _close(kda_kernels._forward(ops, jnp.float32, states=False), want_o,
           1e-5, "o")
    ck = kda_kernels._forward(ops, jnp.float32, states=True)
    assert ck.shape == (bh, -(-chunks // 2), dv, dk)
    assert not np.asarray(ck[:, 0]).any()       # S = 0 before chunk 0
    state = jnp.zeros((bh, dk, dv))
    for n in range(2):                          # the state into segment 1
        u = ops[0][:, n] - ops[1][:, n] @ state
        state = state * ops[5][:, n][..., None] + jnp.swapaxes(
            ops[4][:, n], -1, -2) @ u
    _close(jnp.swapaxes(ck[:, 1], -1, -2), state, 1e-5, "checkpoint 1")
    got = kda_kernels._backward(ops, ck, do)
    for name, g, r, x in zip(("du_v", "dw", "dq_in", "da_qk", "dk_out",
                              "dshrink"), got, pull(do), ops):
        assert g.shape == x.shape and g.dtype == x.dtype, name
        _close(g, r, 2e-5, name)


def _walk_eqns(jaxpr):
    """Every equation of ``jaxpr``, through its sub-jaxprs (scan, map,
    remat, custom_vjp) but not into a Pallas kernel's body, whose values
    are VMEM."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _walk_eqns(sub)


def test_no_state_history_reaches_hbm_only_the_segment_checkpoints():
    """``jax.vjp(chunk_kda)``, forward and backward in one jaxpr: nothing
    shaped [..., dk, dv] (or transposed) outside the kernels is larger than
    the segment checkpoints, where the scan's autodiff stacked a state a
    chunk (``SEG`` times as much)."""
    # a value width that no chunk or row block of the scores (8 to 64) has
    b, s, h, dk, dv = 1, 64 * 2 * kda_kernels.SEG, 2, 32, 20
    args = _kda_inputs(b=b, s=s, h=h, dk=dk, dv=dv)

    def both(*a):
        o, pull = jax.vjp(lambda *x: chunk_kda(*x, head_groups=2), *a)
        return pull(jnp.ones_like(o))

    avals = [v.aval for e in _walk_eqns(jax.make_jaxpr(both)(*args).jaxpr)
             for v in e.outvars]
    states = [a for a in avals if getattr(a, "shape", ())[-2:]
              in ((dk, dv), (dv, dk)) and len(a.shape) >= 3]
    checkpoints = b * (h // 2) * 2 * dk * dv        # a group's: 2 segments
    assert states and max(int(np.prod(a.shape)) for a in states) \
        == checkpoints
    assert all(a.dtype == jnp.float32 for a in states)
    # and the scan is gone: a group runs one kernel and no loop
    names = [e.primitive.name for e in _walk_eqns(jax.make_jaxpr(
        lambda *a: kda_ops._chunk_kda(*a, chunk=64))(*args).jaxpr)]
    assert "scan" not in names and "while" not in names
    assert names.count("pallas_call") == 2      # the preparation, the scan


def test_kda_kernels_refuse_on_the_chip_what_mosaic_cannot_tile(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="multiples of 128"):
        kda_kernels._check_chip_shapes(64, 32, 128)
    kda_kernels._check_chip_shapes(64, 128, 256)


# ---- MLA: the flash path (key 24, value 16) against plain softmax ----------
def test_flash_attention_with_a_narrower_value_matches_plain_softmax():
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(size=(2, 256, 4, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, 256, 4, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 256, 4, 16)), jnp.float32)
    want = arch.causal_attention(q, k, v)
    got = flash_attention(q, k, v, causal=True)
    assert got.shape == (2, 256, 4, 16)
    _close(got, want, 1e-5, "forward")
    grad = lambda f: jax.grad(  # noqa: E731
        lambda q, k, v: jnp.sum(f(q, k, v) * w), argnums=(0, 1, 2))(q, k, v)
    for name, g, r in zip("qkv", grad(flash_attention),
                          grad(arch.causal_attention)):
        _close(g, r, 1e-4, f"d{name}")


def test_mla_layer_through_flash_matches_the_plain_layer():
    params = _tiny().init(jax.random.PRNGKey(5))
    p = params["layers"]["tail"]["0"]["mla"]
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 128, 64))
    c = _tiny().config
    want = arch.mla_mixer(p, h, heads=c.num_heads, nope=c.qk_nope_head_dim,
                          rope=c.qk_rope_head_dim, dv=c.v_head_dim,
                          lora=c.kv_lora_rank, eps=c.norm_eps)
    got = _tiny(attn_impl="flash")._mla(p, h, flash_attention)
    _close(got, want, 1e-5)


# ---- the router ------------------------------------------------------------
def test_sigmoid_router_by_hand():
    """Bias in the selection only, renormalised over the chosen, x 2.446."""
    logits = jnp.log(jnp.asarray([[0.8, 0.6, 0.5, 0.2]])
                     / (1 - jnp.asarray([[0.8, 0.6, 0.5, 0.2]])))
    bias = jnp.asarray([0.0, -0.5, 0.0, 0.35])
    idx, w, select = sigmoid_top_k(logits, bias, 2, scaling=2.446)
    # scores + bias = .8, .1, .5, .55: experts 0 and 3, not 0 and 1
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 3]
    np.testing.assert_allclose(np.asarray(select[0]), [.8, .1, .5, .55],
                               rtol=1e-6)
    by_expert = dict(zip(np.asarray(idx[0]).tolist(),
                         np.asarray(w[0]).tolist()))
    # weights from the SCORES .8 and .2, not from scores + bias
    assert by_expert[0] == pytest.approx(2.446 * 0.8 / 1.0, rel=1e-6)
    assert by_expert[3] == pytest.approx(2.446 * 0.2 / 1.0, rel=1e-6)
    _, raw, _ = sigmoid_top_k(logits, bias, 2, renormalise=False)
    assert sorted(np.asarray(raw[0]).tolist()) == pytest.approx([0.2, 0.8])
    # and no gradient reaches the bias
    g = jax.grad(lambda b: jnp.sum(sigmoid_top_k(logits, b, 2)[1]))(bias)
    assert not np.any(np.asarray(g))


# ---- a held share ----------------------------------------------------------
E, K, D, F = 256, 8, 16, 8


def _full_layer():
    """An uncut layer's weights (every one of the E experts) and tokens."""
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    w = lambda *shape: 0.5 * jax.random.normal(next(ks), shape)  # noqa: E731
    params = {"router": w(D, E), "router_bias": jnp.linspace(-0.05, 0.05, E),
              "experts": {"w_gate": w(E, D, F), "w_up": w(E, D, F),
                          "w_down": w(E, F, D)},
              "shared": {"w_gate": w(D, F), "w_up": w(D, F),
                         "w_down": w(F, D)}}
    return params, jax.random.normal(jax.random.PRNGKey(1), (2, 48, D))


def _share(params, x, chip, held=8):
    """``moe_ffn_held`` as chip ``chip`` of E / held runs it."""
    mine = {n: w[held * chip:held * (chip + 1)]
            for n, w in params["experts"].items()}
    return moe_ffn_held(x, params["router"], params["router_bias"], mine,
                        params["shared"], k=K, first_expert=held * chip,
                        scaling=2.446, block=16)


def test_the_shares_add_up_to_the_uncut_layer():
    """32 shares of 8 experts, the shared expert counted once, sum to the
    whole layer, which is the reference's with every expert held."""
    params, x = _full_layer()
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda w: w.astype(jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        whole, _, _ = arch.routed(f32(params), x.reshape(-1, D), top_k=K,
                                  first=0, renormalise=True, scaling=2.446)
        shared = arch._swiglu(params["shared"], x.reshape(-1, D))
        total, load = 0, 0
        for chip in range(E // 8):
            mine = dict(params, experts={n: w[8 * chip:8 * chip + 8]
                                         for n, w in
                                         params["experts"].items()})
            out, counts = _share(params, x, chip)
            out = out.reshape(-1, D)
            # every share counts the same load over ALL the experts, and
            # computes the rows of its own slice of it
            assert int(counts["done"]) == int(
                jnp.sum(counts["load"][8 * chip:8 * chip + 8]))
            load = counts["load"]
            # the program's share is the reference's share
            ref, _, _ = arch.routed(f32(mine), x.reshape(-1, D), top_k=K,
                                    first=8 * chip, renormalise=True,
                                    scaling=2.446)
            _close(out, ref, 1e-5, f"share {chip}")
            total = total + out
    _close(total - (E // 8 - 1) * shared, whole, 1e-5, "sum of shares")
    assert int(jnp.sum(load)) == x.shape[0] * x.shape[1] * K


@pytest.mark.parametrize("skew", ["balanced", "all_to_one_held_expert",
                                  "none_held"])
def test_no_token_is_dropped_under_a_skewed_router(skew):
    params, x = _full_layer()
    held = {n: w[:8] for n, w in params["experts"].items()}
    bias = {"balanced": params["router_bias"],
            # every token's top-8 holds experts 0..7: 8 rows a token here
            "all_to_one_held_expert": jnp.where(jnp.arange(E) < 8, 5.0, 0.0),
            "none_held": jnp.where(jnp.arange(E) < 8, -5.0, 0.0)}[skew]
    xt = x.reshape(-1, D)
    idx, w, _ = sigmoid_top_k(xt @ params["router"], bias, K, scaling=2.446)
    out, done = held_experts_ffn(xt, idx, w, held, 0, 16)
    want_rows = int(jnp.sum(idx < 8))
    assert int(done) == want_rows
    assert want_rows == {"all_to_one_held_expert": xt.shape[0] * 8,
                         "none_held": 0}.get(skew, want_rows)
    dense = sum(jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
                * arch._swiglu({n: v[e] for n, v in held.items()}, xt)
                for e in range(8))
    _close(out, dense, 1e-5) if want_rows else None
    if not want_rows:
        assert not np.any(np.asarray(out))
    # a token routed only to absent experts gets the shared expert alone
    full, counts = moe_ffn_held(x, params["router"], bias, held,
                                params["shared"], k=K, scaling=2.446,
                                block=16)
    _close(full.reshape(-1, D), dense + arch._swiglu(params["shared"], xt),
           1e-5)
    assert int(counts["done"]) == int(jnp.sum(counts["load"][:8])) \
        == want_rows


def test_balance_bias_by_hand_and_it_holds_a_drifting_router():
    """Over the mean load: bias down by the rate; under it: up; at it:
    left. And where the held experts' scores drift down step by step (as
    they do in the cut model, whose absent experts get no gradient), the
    update keeps their load near the mean if its rate is over the
    drift's (0.02 a step in the logit is 0.0023 in the score at the
    top-k boundary), where without it the load collapses."""
    got = balance_bias(jnp.zeros(4), jnp.asarray([9, 1, 5, 5]), 0.001)
    np.testing.assert_allclose(np.asarray(got), [-.001, .001, 0, 0])
    logits = jax.random.normal(jax.random.PRNGKey(2), (4096, E))
    drift = jnp.where(jnp.arange(E) < 8, -0.02, 0.0)    # a step, held only

    def held_load(rate, steps=60):
        bias = jnp.zeros(E)
        for t in range(steps):
            idx, _, _ = sigmoid_top_k(logits + t * drift, bias, K)
            load = jnp.bincount(idx.reshape(-1), length=E)
            bias = balance_bias(bias, load, rate)
        return float(jnp.mean(load[:8])) / (4096 * K / E)

    assert held_load(0.0) < 0.2
    assert held_load(0.001) < 0.6       # a rate under the drift lags it
    assert 0.85 < held_load(0.004) < 1.15


# ---- the stack, the counts, the engine -------------------------------------
@pytest.mark.parametrize("mixers,lead,want", [
    ("KKKM" * 6 + "KKM", 1, (4, 6, 2)),     # the published 27 layers
    ("KKKMK", 1, (1, 2, 2)),                # the cell's cut: layers 1 to 5
    ("KM", 0, (0, 0, 2)),                   # nothing repeats: unrolled
    ("KKKK", 0, (1, 4, 0)),
])
def test_stack_plan(mixers, lead, want):
    assert stack_plan(list(mixers), lead) == want


def test_published_preset_counts():
    """``ModelConfig`` counts a stack of kinds and a held share: the
    issue's 602 M parameters, of which a token computes with the dense
    parts and a quarter of an expert a routed layer."""
    c = KimiLinear(size="48b-a3b", num_layers=5, vocab_size=20480,
                   kda_layers=[1, 2, 3, 5], full_attn_layers=[4],
                   moe_held_experts=8).config
    assert c.num_params() == 602450816
    per = c._kind_params()
    assert (per["kda"], per["mla"], per["dense"], per["expert"]) == (
        39518368, 29114880, 63700992, 7077888)
    assert c.num_params() - c.num_active_params() == int(
        4 * (8 - 8 * 8 / 256) * per["expert"])
    m = {k: getattr(c, a) for k, a in arch.WIDTHS.items()}
    # the program's estimate and the benchmark's count agree to 1%: they
    # differ in norms, biases and the convolutions
    assert c.flops_per_token(16384) == pytest.approx(
        arch.train_flops_per_token(m, 16384), rel=0.01)
    tiny = _tiny()
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(tiny.init, jax.random.PRNGKey(0))))
    assert tiny.config.num_params() == n
    whole = KimiLinear(size="48b-a3b").config
    assert 47e9 < whole.num_params() < 50e9     # "48B"
    assert 2.5e9 < whole.num_active_params() < 3.6e9    # "A3B"


# ---- the one-kind scan is the parent's program -----------------------------
def _parent_final_hidden(self, params, tokens, *, attn_fn=None,
                         positions=None, act_sharding=None):
    """``DecoderLM._final_hidden`` as it stood before the stack of kinds
    was split off into ``_layer_stack`` (commit d200a6f)."""
    import functools

    from deepspeed_tpu.models.transformer import _remat_policy
    from deepspeed_tpu.parallel.mesh import constrain_free
    c = self.config
    pin = (functools.partial(constrain_free, sharding=act_sharding)
           if act_sharding is not None else lambda x: x)
    with jax.named_scope("ds.embed"):
        x = self.embed(params, tokens, positions)
    x = pin(x)

    def body(carry, layer_params):
        x, aux = carry
        x, layer_aux = self.block(layer_params, x, attn_fn=attn_fn,
                                  positions=positions)
        return (pin(x), aux + layer_aux), None

    if c.remat and c.remat_policy != "segments":
        body = jax.checkpoint(body, prevent_cse=False,
                              policy=_remat_policy(c.remat_policy))
    with jax.named_scope("ds.layers"):
        (x, aux), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    with jax.named_scope("ds.loss_head"):
        x = self._norm(x, params["final_norm"]["scale"],
                       params["final_norm"].get("bias"))
    return x, aux


def _mistral_step_text(monkeypatch, parent: bool, **model_kw):
    if parent:
        monkeypatch.setattr(Mistral, "_final_hidden", _parent_final_hidden)
    model = Mistral(size="tiny", **model_kw)
    engine, *_ = ds.initialize(model=model, config={
        "train_batch_size": 8, "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "gradient_clipping": 1.0, "mesh": {"fsdp": -1},
        "steps_per_print": 10 ** 9})
    tok = np.zeros((8, model.config.max_seq_len), np.int32)
    lowered = engine._train_step.lower(engine.state,
                                       engine._put_batch((tok, tok)))
    monkeypatch.undo()
    # no source locations in either (debug_info off); the compiled text
    # with what only says where the code stood taken out
    hlo = lowered.compile().as_text()
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    hlo = "\n".join(l for l in hlo.splitlines()
                    if not re.match(r"^(FileNames|FunctionNames|"
                                    r"FileLocations|StackFrames)\b|^\d+ ",
                                    l.strip()))
    return lowered.as_text(), hlo


@pytest.mark.parametrize("model_kw", [
    dict(), dict(remat_policy="segments", loss_chunk=64, attn_impl="flash")],
    ids=["default", "the_cells_switches"])
def test_mistral_step_is_the_parents_program(monkeypatch, model_kw):
    """With and without this PR's stack code on its path, the compiled
    train step of the mistral tiny preset is one program."""
    mlir_now, hlo_now = _mistral_step_text(monkeypatch, False, **model_kw)
    mlir_parent, hlo_parent = _mistral_step_text(monkeypatch, True,
                                                 **model_kw)
    assert mlir_now == mlir_parent
    assert hlo_now == hlo_parent


@pytest.mark.parametrize("family", ["mistral", "granite_hybrid"])
def test_the_other_architectures_steps_run_nothing_of_kda(monkeypatch,
                                                          family):
    """PR 35 changed ``ops/kda.py`` and ``ops/pallas/kda.py`` alone (and a
    list in ``telemetry/scopes.py``): Mistral's and Granite's lowered train
    steps are the same text with every entry point of the two files made
    to raise, so they are the parent's."""
    from deepspeed_tpu.models.base import get_model_class

    def step_text():
        model = get_model_class(family)(size="tiny")
        engine, *_ = ds.initialize(model=model, config={
            "train_batch_size": 8, "bf16": {"enabled": True},
            "zero_optimization": {"stage": 3},
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "gradient_clipping": 1.0, "mesh": {"fsdp": -1},
            "steps_per_print": 10 ** 9})
        tok = np.zeros((8, model.config.max_seq_len), np.int32)
        return engine._train_step.lower(
            engine.state, engine._put_batch((tok, tok))).as_text()

    def refuse(*a, **kw):
        raise AssertionError("KDA code on another architecture's path")

    now = step_text()
    for module, names in ((kda_ops, ("chunk_kda", "sharded_chunk_kda",
                                     "_chunk_kda", "recurrent_kda")),
                          (kda_kernels, ("kda_prepare", "kda_recurrence",
                                         "_Chunk", "_forward", "_backward"))):
        for name in names:
            monkeypatch.setattr(module, name, refuse)
    assert "loc(" not in now and step_text() == now

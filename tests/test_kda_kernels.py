"""KDA's chunked form and the recurrence's Pallas kernel pair
(``ops/kda.py``, ``ops/pallas/kda.py`` ``ds_kda_fwd`` / ``ds_kda_bwd``, PR
32) against the token-by-token recurrence: interpret mode, tiny shapes. The
preparation's pair is ``tests/test_kda_prep_kernels.py``; their compile for
the chip is ``tests/test_zero_layout.py``. These cases were
``tests/test_kimi_linear.py``'s until PR 45: a file is one worker's under
``--dist loadfile``, so a kernel PR's interpret-mode cases get a file of
their own. A CPU run shows results and counts, never a time."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import kda as kda_ops
from deepspeed_tpu.ops.kda import chunk_kda, recurrent_kda
from deepspeed_tpu.ops.pallas import kda as kda_kernels

from helpers.families import (BENCH, _as_bf16, _close,  # noqa: F401
                               _kda_inputs,
                               _walk_eqns)
from architectures import kimi_linear as arch  # noqa: E402  (benchmark/,
#                                           on sys.path by families)


# ---- KDA: the chunked form against the recurrence --------------------------
@pytest.mark.parametrize("groups", [1, 3])
def test_chunked_kda_matches_the_recurrence_forward_and_backward(groups):
    args = _kda_inputs()
    # jitted: eager, every line round the kernels compiles alone
    want = jax.jit(recurrent_kda)(*args)
    got = jax.jit(lambda *a: chunk_kda(*a, head_groups=groups))(*args)
    _close(got, want, 1e-5, "forward")
    w = jnp.asarray(np.random.default_rng(1).normal(size=want.shape),
                    jnp.float32)
    grad = lambda f: jax.jit(jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3, 4)))(*args)
    for name, g, r in zip("qkvgb", grad(
            lambda *a: chunk_kda(*a, head_groups=groups)),
            grad(recurrent_kda)):
        assert bool(jnp.all(jnp.isfinite(g))), name
        _close(g, r, 2e-5, f"d{name}")


def test_chunked_kda_agrees_with_the_benchmarks_recurrence_and_its_shape():
    args = _kda_inputs(b=1, s=128, h=2)
    _close(jax.jit(chunk_kda)(*args), jax.jit(arch.kda_recurrence)(*args),
           1e-5)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        chunk_kda(*[a[:, :100] for a in args])


# ---- KDA: the kernel pair (interpret mode) ---------------------------------
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
    # jitted: eager, every line round the kernels compiles alone
    want = jax.jit(recurrent_kda)(*args)
    got = jax.jit(lambda *a: chunk_kda(*a, head_groups=groups))(*args)
    assert got.dtype == args[2].dtype
    _close(got.astype(jnp.float32), want, tol_o, "forward")
    w = jnp.asarray(np.random.default_rng(1).normal(size=want.shape),
                    jnp.float32)
    grad = lambda f: jax.jit(jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w),
        argnums=(0, 1, 2, 3, 4)))(*args)
    for name, g, r in zip("qkvgb", grad(
            lambda *a: chunk_kda(*a, head_groups=groups)),
            grad(recurrent_kda)):
        assert g.dtype == r.dtype
        assert bool(jnp.all(jnp.isfinite(g))), name
        _close(g.astype(jnp.float32), r.astype(jnp.float32), tol_g,
               f"d{name}")


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
    _close(kda_kernels._forward(ops, jnp.float32, states=False)[:, :chunks],
           want_o, 1e-5, "o")         # the stack comes at whole segments
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
    # and the scan is gone: one group runs one kernel pair and no loop
    names = [e.primitive.name for e in _walk_eqns(jax.make_jaxpr(
        chunk_kda)(*args).jaxpr)]
    assert "scan" not in names and "while" not in names
    assert names.count("pallas_call") == 2      # the preparation, the scan


def test_kda_kernels_refuse_on_the_chip_what_mosaic_cannot_tile(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="multiples of 128"):
        kda_kernels._check_chip_shapes(64, 32, 128)
    kda_kernels._check_chip_shapes(64, 128, 256)


def test_a_kimi_or_mellum_step_loads_no_state_space_kernels():
    """The kernels' shared helpers are ``ops/pallas/_common.py``'s (PR 45):
    the KDA, short-convolution and grouped-matmul kernels that a Kimi or a
    Mellum step runs import nothing of the Mamba-2 scan's."""
    code = ("import sys; "
            "from deepspeed_tpu.ops.pallas import kda, short_conv, "
            "grouped_matmul, _common; "
            "import deepspeed_tpu.ops.kda, deepspeed_tpu.moe.sharded_moe; "
            "assert kda._bind is short_conv._bind is grouped_matmul._bind "
            "is _common._bind; "
            "bad = [k for k in sys.modules if k.endswith(('ops.ssd', "
            "'ops.pallas.ssd'))]; assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=str(BENCH.parent))

"""The KDA preparation's Pallas kernel pair (``ops/pallas/kda.py``
``ds_kda_prep_fwd`` / ``ds_kda_prep_bwd``, PRs 35 and 44) against the
``jax.numpy`` form it replaced (``tests/helpers/kda_reference.py``), and
its residuals: interpret mode, tiny shapes. These cases were
``tests/test_kimi_linear.py``'s until PR 45 (a file is one worker's under
``--dist loadfile``; the decays at which the ``jax.numpy`` form overflowed
went back there in PR 58: this file was 364 s); the recurrence's pair is
``tests/test_kda_kernels.py``. A CPU run shows results and counts, never a
time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import kda as kda_ops
from deepspeed_tpu.ops.pallas import kda as kda_kernels

from helpers import kda_reference  # noqa: E402  (tests/helpers)
from helpers.families import (_as_bf16, _close,  # noqa: F401
                               _kda_inputs,
                               _walk_eqns)


# ---- KDA: the preparation's kernel pair (interpret mode) -------------------
_WANT = {}      # (q's shape, v's shape, dtype) -> the reference's side


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads_a_step,chunks,rep", [
    (1, 3, 1), (1, 5, 1), (2, 3, 1), (2, 5, 1), (3, 3, 1), (4, 3, 1),
    (8, 3, 1), (2, 5, 2), (4, 3, 2), (4, 3, 4)])
def test_kda_preparation_kernels_match_the_jax_numpy_preparation(
        heads_a_step, chunks, rep, dtype, monkeypatch):
    """``ds_kda_prep_fwd`` and ``ds_kda_prep_bwd`` against the preparation
    as ``ops/kda.py`` held it in ``jax.numpy`` (``helpers/kda_reference``)
    and its autodiff: the six operands, and the five input gradients from
    six random cotangents. 3 chunks are one grid step of 3, 5 are five
    steps of 1 (a grid step takes a divisor of the chunk count). The heads
    of a grid step take the inverse's float32 products two to a product
    (PR 44): one head runs alone, 2, 4 and 8 are one, two and four pairs,
    3 a pair and a lone head. ``rep`` (PR 53): q and k hold a head for every
    ``rep`` of v's, the reference takes them repeated and its autodiff sums
    the cotangents of a key head's copies, which the backward kernel does
    before its one store: a grid step's blocks of q and k are 1, 2 and 1
    key heads wide where v's are 2, 4 and 4 (two key heads under 8 value
    heads of one grid step: ``tests/test_qwen3_next_scan.py``)."""
    monkeypatch.setattr(kda_kernels, "PREP_HEADS", heads_a_step)
    monkeypatch.setattr(kda_kernels, "NCK", 4)
    args = _kda_inputs(s=64 * chunks, h=max(2, heads_a_step),
                       b=2 if heads_a_step < 3 else 1)
    args[:2] = [x[:, :, ::rep] for x in args[:2]]
    tol_o, tol_g = 1e-5, 2e-5
    if dtype == "bfloat16":
        args, tol_o, tol_g = _as_bf16(args), 2e-2, 4e-2
    geometry = kda_kernels._prep_geometry(args[0], args[2], 64)
    assert geometry[-2:] == ({3: 3, 5: 1}[chunks], heads_a_step)
    reference = lambda q, k, *rest, chunk: kda_reference.prepare(  # noqa: E731
        *(jnp.repeat(x, rep, axis=2) for x in (q, k)), *rest, chunk=chunk)
    rng = np.random.default_rng(chunks)
    cts = tuple(jnp.asarray(rng.normal(size=x.shape), x.dtype)
                for x in jax.eval_shape(
                    lambda *a: reference(*a, chunk=64), *args))
    # jitted: eager, every interpreted kernel call compiles alone
    both = lambda f: jax.jit(lambda *a: (  # noqa: E731
        lambda out, pull: (out, pull(cts)))(
            *jax.vjp(lambda *x: f(*x, chunk=64), *a)))(*args)
    # the reference knows no grid: cases that differ by the heads of a grid
    # step alone share its side (one and two heads a step: the same inputs)
    seen = (args[0].shape, args[2].shape, dtype)
    if seen not in _WANT:
        _WANT[seen] = both(reference)
    want, want_g = _WANT[seen]
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
    """``jax.vjp`` of the scan in one head group, forward and backward in
    one jaxpr: outside the kernels nothing is a float32 [.., 64, 64] array
    (the score matrices and the inverse live in VMEM; ``a_qk`` and its
    cotangent are in the matmuls' dtype), and the ONE ``custom_vjp`` over
    the grouped scan (ISSUE 59) keeps the five inputs, nothing else: its
    backward rule makes the six operands again (the preparation's forward
    a second time) and the segment checkpoints."""
    args = _as_bf16(_kda_inputs(b=1, s=64 * 4, h=2, dk=32, dv=20))
    group = kda_ops.chunk_kda

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
                             "ds_kda_prep_bwd", "ds_kda_prep_fwd",
                             "ds_kda_prep_fwd"], calls
    _, pull = jax.vjp(group, *args)
    kept = sorted((x.size, str(x.dtype)) for x in jax.tree.leaves(pull))
    assert kept == sorted((x.size, str(x.dtype)) for x in args)

"""The Mamba-2 scan's Pallas kernel pair (``ops/pallas/ssd.py``, PR 37)
against the ``jax.numpy`` form it replaced (``tests/helpers/
ssd_reference.py``): interpret mode, jitted, tiny shapes. The nine
``chunk_ssd`` cases of ``tests/test_granite_hybrid.py`` hold the kernels to
the token-by-token recurrence; their compile for the chip is in
``tests/test_zero_layout.py``."""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops import ssd as ssd_ops
from deepspeed_tpu.ops.pallas import ssd as kernels
from deepspeed_tpu.ops.ssd import chunk_ssd, sharded_chunk_ssd
from deepspeed_tpu.parallel.mesh import MeshTopology, TopologyConfig

from helpers import ssd_reference  # noqa: E402  (tests/helpers)

NAMES = ("x", "dt", "A", "B", "C")
ALL = tuple(range(5))


def _inputs(b=2, s=128, h=4, p=8, g=1, n=16, seed=0, dtype="float32"):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = jnp.exp(jax.random.uniform(k[1], (b, s, h), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    cast = lambda v: v.astype(dtype)  # noqa: E731
    return (cast(jax.random.normal(k[0], (b, s, h, p))), dt,
            -jax.random.uniform(k[2], (h,), minval=1.0, maxval=16.0),
            cast(jax.random.normal(k[3], (b, s, g, n))),
            cast(jax.random.normal(k[4], (b, s, g, n))))


def _err(got, want):
    got, want = (jnp.asarray(v, jnp.float32) for v in (got, want))
    return float(jnp.max(jnp.abs(got - want))) / (
        float(jnp.max(jnp.abs(want))) + 1e-30)


def _y_and_grads(fn, chunk):
    """y and the five gradients of a quadratic loss, one jitted program."""
    def both(*a):
        loss = lambda *v: 0.5 * jnp.sum(jnp.square(  # noqa: E731
            fn(*v, chunk=chunk).astype(jnp.float32)))
        return (fn(*a, chunk=chunk), *jax.grad(loss, argnums=ALL)(*a))
    return jax.jit(both)


# one head a lane tile (the generic path), two and four (P 64 and 32 of a
# 128-lane tile: their diagonal blocks through the MXU together), sixteen
# heads a grid step (two groups of the one-hot products), several groups
SHAPES = {
    "one_block": dict(b=2, h=2, p=8, g=1, n=16),
    "groups_of_two": dict(h=4, p=8, g=2, n=16),
    "a_group_a_head": dict(h=2, p=8, g=2, n=16),
    "two_heads_a_tile": dict(h=4, p=64, g=2, n=32),
    "four_heads_a_tile": dict(h=4, p=32, g=1, n=16),
    "two_spread_groups": dict(h=16, p=8, g=1, n=16),
    "three_heads": dict(h=3, p=8, g=1, n=16),
}
CASES = [(shape, "float32") for shape in SHAPES] + [
    ("one_block", "bfloat16"), ("two_heads_a_tile", "bfloat16"),
    ("two_spread_groups", "bfloat16")]


@pytest.mark.parametrize("shape,dtype", CASES)
def test_kernels_match_the_jax_numpy_form(shape, dtype):
    """``y`` and all five gradients, in the dtypes and shapes of the
    inputs; bf16 to the rounding of its matmul operands."""
    args = _inputs(**{"b": 1, "s": 96, **SHAPES[shape]}, dtype=dtype)
    got = _y_and_grads(chunk_ssd, 32)(*args)
    want = _y_and_grads(ssd_reference.chunk_ssd, 32)(*args)
    tol = 2e-5 if dtype == "float32" else 3e-2
    assert got[0].dtype == args[0].dtype and got[0].shape == args[0].shape
    assert _err(got[0], want[0]) < tol / 4, "y"
    for name, a, x, y in zip(NAMES, args, got[1:], want[1:]):
        assert x.shape == a.shape and x.dtype == a.dtype, name
        assert bool(jnp.all(jnp.isfinite(x))), name
        assert _err(x, y) < tol, name


def test_several_head_blocks_share_a_groups_b_and_c(monkeypatch):
    """Two heads a grid step: a group's four heads pass in two steps, which
    share its ``C B^T`` and add to one block of dB and dC; the heads of
    the next group start theirs anew."""
    monkeypatch.setattr(kernels, "HEADS", 2)
    args = _inputs(b=1, s=96, h=8, p=8, g=2, n=16, seed=2)
    assert kernels._geometry(8, 2, 8) == (2, 1, 2)
    got = _y_and_grads(chunk_ssd, 32)(*args)
    want = _y_and_grads(ssd_reference.chunk_ssd, 32)(*args)
    for name, x, y in zip(("y",) + NAMES, got, want):
        assert _err(x, y) < 2e-5, name


def test_geometry_follows_the_widths():
    """Heads a block, heads a lane tile, blocks a group: from H, G and P."""
    assert kernels._geometry(64, 1, 64) == (16, 2, 4)       # the cell
    assert kernels._geometry(128, 8, 64) == (16, 2, 1)      # Nemotron-H
    assert kernels._geometry(64, 1, 128) == (16, 1, 4)
    assert kernels._geometry(6, 2, 64) == (3, 1, 1)         # no whole tile
    assert kernels._geometry(12, 2, 64) == (6, 2, 1)
    assert [kernels._row_block(q) for q in (256, 512, 128, 64, 48, 7)] == [
        128, 128, 128, 32, 24, 7]


def test_the_states_form_writes_what_each_chunk_starts_from():
    """The forward kernel's second form against the reference's own scan
    over the chunks: chunk 0 starts from nothing, chunk c from what the
    recurrence holds after c chunks."""
    b, s, h, p, g, n, q = 1, 128, 4, 8, 2, 16, 32
    args = _inputs(b=b, s=s, h=h, p=p, g=g, n=n, seed=3)
    ops, dims = kernels._operands(*args, q)
    ck = jax.jit(lambda *o: kernels._forward(*o, dims, states=True))(*ops)
    hb = kernels._geometry(h, g, p)[0]
    assert ck.shape == (b, s // q, h // hb, n, hb * p)
    assert ck.dtype == jnp.float32 and not np.asarray(ck[:, 0]).any()
    x, dt, A, B, C = (np.asarray(v, np.float64) for v in args)
    state = np.zeros((h, p, n))
    for t in range(s - q):
        grp = np.repeat(B[0, t], h // g, axis=0)            # [h, n]
        state = (state * np.exp(dt[0, t] * A)[:, None, None]
                 + (dt[0, t][:, None] * x[0, t])[:, :, None]
                 * grp[:, None, :])
        if (t + 1) % q == 0:
            got = np.asarray(ck[0, (t + 1) // q])           # [HB, n, hb p]
            got = got.reshape(h // hb, n, hb, p).transpose(0, 2, 3, 1)
            assert _err(got.reshape(h, p, n), state) < 1e-5


def test_grad_through_a_checkpoint_is_the_same():
    """``jax.grad`` through ``jax.checkpoint(chunk_ssd)`` equals the one
    without, bit for bit, and the rematted program holds the backward
    kernel once and both kernel scopes. (Whether the rerun's ``y`` is dead
    is the caller's: in the Granite layer the gate and the gated norm read
    it again, so ``ds_ssd_fwd`` runs twice a layer there.)"""
    args = _inputs(seed=4)
    w = jax.random.normal(jax.random.PRNGKey(1), args[0].shape)
    scan = lambda *a: chunk_ssd(*a, chunk=32)  # noqa: E731
    grad = lambda f: jax.jit(jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * w), argnums=ALL))
    plain, remat = grad(scan), grad(jax.checkpoint(scan))
    for name, x, y in zip(NAMES, remat(*args), plain(*args)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), name)
    hlo = remat.lower(*args).compile().as_text()
    calls = [e.params["name"] for e in _walk_eqns(
        jax.make_jaxpr(remat)(*args).jaxpr)
        if e.primitive.name == "pallas_call"]
    assert sorted(calls).count("ds_ssd_bwd") == 1
    assert "ds.ssd_bwd" in hlo and "ds.ssd_fwd" in hlo


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _walk_eqns(sub)


def test_nothing_chunk_by_chunk_reaches_hbm_and_the_residuals_are_the_inputs():
    """``jax.vjp(chunk_ssd)``, forward and backward in one jaxpr: outside
    the kernels no array is [.., Q, Q] (the decay matrix, ``C B^T`` and
    ``m`` live in VMEM), the only states are the float32 checkpoints, no
    loop is left, and the ``custom_vjp`` keeps its five inputs and nothing
    else."""
    q = 48      # a chunk that no other extent of the shapes has
    args = _inputs(b=1, s=q * 3, h=4, p=8, g=2, n=16, dtype="bfloat16")
    scan = lambda *a: chunk_ssd(*a, chunk=q)  # noqa: E731

    def both(*a):
        y, pull = jax.vjp(scan, *a)
        return pull(jnp.ones_like(y))

    eqns = list(_walk_eqns(jax.make_jaxpr(both)(*args).jaxpr))
    shapes = [v.aval.shape for e in eqns for v in e.outvars
              if hasattr(v.aval, "shape")]
    assert not [s for s in shapes if s[-2:] == (q, q)]
    names = [e.primitive.name for e in eqns]
    assert "scan" not in names and "while" not in names
    calls = [e.params["name"] for e in eqns
             if e.primitive.name == "pallas_call"]
    assert sorted(calls) == ["ds_ssd_bwd", "ds_ssd_fwd", "ds_ssd_fwd"]
    _, pull = jax.vjp(scan, *args)
    kept = sorted((v.size, str(v.dtype)) for v in jax.tree.leaves(pull))
    assert kept == sorted((v.size, str(v.dtype)) for v in args)


def test_kernels_refuse_on_the_chip_what_mosaic_cannot_tile(monkeypatch):
    """With the backend forced to look like the chip: a head block that
    fills no lane tile, a state or a chunk that is no multiple of 128 are
    refused by name, the cell's shape is not."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kernels._check_chip_shapes(256, 8, 64, 128)
    for bad in ((256, 1, 64, 128), (256, 8, 64, 64), (64, 8, 64, 128)):
        with pytest.raises(ValueError, match="multiples of 128"):
            kernels._check_chip_shapes(*bad)
    args = _inputs(b=1, s=64, h=4, p=8)
    with pytest.raises(ValueError, match="not 4 x 8, 16 and 32"):
        jax.eval_shape(lambda *a: chunk_ssd(*a, chunk=32), *args)


@pytest.mark.parametrize("batch", [4, 3])
def test_sharded_scan_on_the_cpu_mesh_matches_one_device(batch):
    """``sharded_chunk_ssd`` under a jit over four devices, the batch over
    ``fsdp`` (an uneven batch replicated): ``y`` and the gradients are one
    device's."""
    mt = MeshTopology(TopologyConfig(fsdp=4), devices=jax.devices()[:4])
    act = mt.sharding(mt.batch_axes(), "sp")
    args = _inputs(b=batch, s=64, seed=6)
    spec = lambda v: NamedSharding(  # noqa: E731
        mt.mesh, P(mt.batch_axes() if batch == 4 and v.ndim > 1 else None))
    placed = tuple(jax.device_put(v, spec(v)) for v in args)
    with mt.mesh:
        got = _y_and_grads(sharded_chunk_ssd(act), 32)(*placed)
    want = _y_and_grads(chunk_ssd, 32)(*args)
    for name, x, y in zip(("y",) + NAMES, got, want):
        assert _err(x, y) < 1e-6, name


def test_chunk_ssd_keeps_its_signature_and_its_scope():
    """The parent's signature, and every op of the forward and of the
    backward under ``ds.ssd`` (the backward rule opens it itself), the
    kernels under ``ds.ssd_fwd`` / ``ds.ssd_bwd`` inside it."""
    import inspect
    sig = inspect.signature(chunk_ssd)
    assert list(sig.parameters) == ["x", "dt", "A", "B", "C", "chunk"]
    assert sig.parameters["chunk"].kind is inspect.Parameter.KEYWORD_ONLY
    assert sig.parameters["chunk"].default == ssd_ops.CHUNK == 256
    args = _inputs(b=1, s=64)
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.square(
        chunk_ssd(*a, chunk=32))), argnums=ALL))
    text = grad.lower(*args).as_text(debug_info=True)
    import re
    locs = [m for m in re.findall(r'loc\("([^"]*)"', text) if "ds." in m]
    kernel = [m for m in locs if "ds.ssd_fwd" in m or "ds.ssd_bwd" in m]
    assert kernel and all(re.search(r"ds\.ssd\)?/(.*/)?ds\.ssd_(fwd|bwd)", m)
                          for m in kernel), kernel[:3]
    assert any("transpose" in m or "ds.ssd_bwd" in m for m in kernel)

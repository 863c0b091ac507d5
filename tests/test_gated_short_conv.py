"""LFM2's gated short convolution (ISSUE 54): the kernel pair
``ds_gated_conv_fwd`` / ``ds_gated_conv_bwd`` of
``ops/pallas/short_conv.py`` in interpret mode against the ``jax.numpy``
form (``tests/helpers/gated_conv_reference.py``): forward and all four
cotangents (dB, dCg, dX as the three column runs of ONE array, and dw).
Their compile for the chip at the cell's widths is
``tests/test_zero_layout.py``'s. A CPU run shows results, never a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import layers as L
from deepspeed_tpu.ops.pallas import _common, short_conv

from helpers.gated_conv_reference import gated_short_conv as reference


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    """Blocks of 64 rows taken 16 at a time, so that a short sequence is
    several blocks of several chunks; a kernel is traced once a geometry
    (``_common._bind``), so no trace of another size is bound here."""
    monkeypatch.setattr(short_conv, "_GATED_SEQ_BLOCK", 64)
    monkeypatch.setattr(short_conv, "_CHUNK", 16)
    _common._TRACED.clear()
    yield
    _common._TRACED.clear()


def _case(b, s, c, n, dtype, seed=0):
    rng = np.random.default_rng(seed)
    bcx = jnp.asarray(rng.normal(size=(b, s, 3 * c)), dtype)
    w = jnp.asarray(rng.uniform(-n ** -0.5, n ** -0.5, size=(n, c)), dtype)
    dy = jnp.asarray(rng.normal(size=(b, s, c)), dtype)
    return bcx, w, dy


def _err(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("b,s,c,n,dtype", [
    (1, 192, 32, 3, jnp.float32),       # three whole blocks
    (2, 192, 32, 3, jnp.bfloat16),      # ... at batch 2, in bfloat16
    (2, 80, 48, 4, jnp.float32),        # 80 is no multiple of 64: blocks
    #                                     of 40, chunks of 8; four taps
    (1, 208, 16, 4, jnp.bfloat16),      # 13 blocks of 16 rows
    (2, 64, 40, 3, jnp.float32),        # one block
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_the_kernel_pair_is_the_jax_numpy_form(b, s, c, n, dtype):
    """Forward and the four cotangents to a rounding of the dtype: both
    forms compute in float32 and round once."""
    bcx, w, dy = _case(b, s, c, n, dtype)
    run = lambda fn: jax.jit(  # noqa: E731
        lambda bcx, w, dy: (lambda y, vjp: (y, *vjp(dy)))(
            *jax.vjp(fn, bcx, w)))(bcx, w, dy)
    y, dbcx, dw = run(L.gated_short_conv)
    want_y, want_dbcx, want_dw = run(reference)
    assert y.shape == (b, s, c) and y.dtype == dtype
    assert dbcx.shape == bcx.shape and dbcx.dtype == dtype
    assert dw.shape == w.shape and dw.dtype == dtype
    tol = 1e-6 if dtype == jnp.float32 else 2 ** -7
    assert _err(y, want_y) <= tol
    for run_, name in enumerate(("dB", "dCg", "dX")):
        cols = slice(run_ * c, (run_ + 1) * c)
        assert _err(dbcx[..., cols], want_dbcx[..., cols]) <= tol, name
    assert _err(dw, want_dw) <= (1e-5 if dtype == jnp.float32 else 2 ** -6)


def test_the_first_rows_see_zeros_and_a_batch_row_sees_only_itself():
    """Causal from zero: row t of the result is a function of rows
    t - (n - 1) .. t of its own sequence alone, across a block's edge too."""
    bcx, w, _ = _case(2, 128, 16, 3, jnp.float32)
    y = L.gated_short_conv(bcx, w)
    moved = bcx.at[1, 70].add(1.0)
    y2 = L.gated_short_conv(moved, w)
    changed = np.argwhere(np.abs(np.asarray(y2 - y)).max(axis=-1) > 0)
    assert {tuple(x) for x in changed} == {(1, 70), (1, 71), (1, 72)}
    b_, cg, x = (np.asarray(bcx[0, :, r * 16:(r + 1) * 16])
                 for r in range(3))
    np.testing.assert_allclose(
        np.asarray(y[0, 0]), cg[0] * np.asarray(w[2]) * b_[0] * x[0],
        rtol=1e-6, atol=1e-7)
    # across the edge of a block (rows 63 | 64)
    u = b_ * x
    np.testing.assert_allclose(
        np.asarray(y[0, 64]), cg[64] * (
            np.asarray(w[0]) * u[62] + np.asarray(w[1]) * u[63]
            + np.asarray(w[2]) * u[64]), rtol=1e-5, atol=1e-6)


def test_what_the_kernels_refuse():
    bcx, w, _ = _case(1, 64, 16, 3, jnp.float32)
    with pytest.raises(ValueError, match=r"not \[B \| Cg \| X\]"):
        L.gated_short_conv(bcx[..., :40], w)
    with pytest.raises(ValueError, match="taps reach past"):
        L.gated_short_conv(bcx, jnp.zeros((10, 16)))
    with pytest.raises(ValueError, match="not a multiple of 8"):
        L.gated_short_conv(bcx[:, :60], w)


def test_the_sharded_form_is_the_bare_one_and_sums_the_taps_gradient(
        devices8):
    """``sharded_gated_short_conv``: per shard of the batch under a
    shard_map over ``fsdp`` = 8; the taps' gradient is summed over the
    shards."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices8), ("fsdp",))
    act = NamedSharding(mesh, P("fsdp", None, None))
    bcx, w, dy = _case(8, 64, 16, 3, jnp.float32)
    conv = L.sharded_gated_short_conv(act)
    loss = lambda fn: lambda bcx, w: jnp.sum(fn(bcx, w) * dy)  # noqa: E731
    with mesh:
        got = jax.jit(jax.value_and_grad(loss(conv), argnums=(0, 1)))(
            jax.device_put(bcx, act), w)
    want = jax.value_and_grad(loss(reference), argnums=(0, 1))(bcx, w)
    assert _err(got[0], want[0]) < 1e-5
    assert _err(got[1][0], want[1][0]) < 1e-5
    assert _err(got[1][1], want[1][1]) < 1e-5

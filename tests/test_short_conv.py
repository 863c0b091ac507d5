"""The short convolution's Pallas kernel pair (``ops/pallas/short_conv.py``,
PR 43) against the ``jax.numpy`` form it replaced (``tests/helpers/
short_conv_reference.py``) in float32: interpret mode, jitted, tiny shapes
with the blocks cut small, so that a sequence is several blocks of several
row chunks. Its compile for the chip is in ``tests/test_zero_layout.py``;
the models' steps with it are in ``tests/test_kimi_linear.py`` and
``tests/test_granite_hybrid.py``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import layers as L
from deepspeed_tpu.ops.pallas import short_conv as kernels

from helpers import short_conv_reference  # noqa: E402  (tests/helpers)

F32 = jnp.float32
TAPS = 4


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    """Blocks of 32 rows in chunks of 16: both carries (the rows before a
    block, the cotangent's rows after it) cross block and chunk edges at
    the tests' sizes (every call is jitted, one program a case: nothing
    is dropped between cases, PR 58)."""
    monkeypatch.setattr(kernels, "_SEQ_BLOCK", 32)
    monkeypatch.setattr(kernels, "_CHUNK", 16)


def _inputs(b, s, c, dtype, bias, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, s, c)), dtype)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, size=(TAPS, c)), dtype)
    bb = jnp.asarray(rng.uniform(-0.5, 0.5, size=(c,)), dtype) if bias \
        else None
    dy = jnp.asarray(rng.normal(size=(b, s, c)), dtype)
    return x, w, bb, dy


def _y_and_grads(fn, x, w, bias, dy, **kw):
    """y and the gradients of <y, dy> by x, w (and bias), one jitted
    program."""
    args = (x, w) if bias is None else (x, w, bias)

    def both(*a):
        loss = lambda *v: jnp.sum(  # noqa: E731
            fn(*v, **kw).astype(F32) * dy.astype(F32))
        return (fn(*a, **kw), *jax.grad(loss, argnums=tuple(
            range(len(a))))(*a))
    return jax.jit(both)(*args)


def _err(got, want):
    got, want = (jnp.asarray(v, F32) for v in (got, want))
    return float(jnp.max(jnp.abs(got - want))) / (
        float(jnp.max(jnp.abs(want))) + 1e-30)


# (batch, sequence, channels, dtype, bias, norm_width, norm_scale); the
# blocks are 32 rows (bf16 and float32 alike), so 96 rows are three, by
# 128 channels (160 channels: by 160)
CASES = {
    "f32_bias_norm64": (2, 96, 256, "float32", True, 64, 0.3),
    "f32_norm128": (2, 96, 256, "float32", False, 128, 1.0),
    "f32_bias_no_norm_odd_width": (2, 96, 160, "float32", True, None, 1.0),
    "bf16_bias_norm64": (1, 32, 256, "bfloat16", True, 64, 1.0),
    "bf16_bias_norm128": (2, 96, 256, "bfloat16", True, 128, 128 ** -0.5),
    "bf16_no_norm": (2, 64, 384, "bfloat16", False, None, 1.0),
    "f32_least_length": (1, 8, 128, "float32", True, 64, 1.0),
    "bf16_least_length": (1, 16, 128, "bfloat16", False, 128, 1.0),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_agree_with_the_jax_numpy_form(case):
    """y, dx, dw and dbias of the kernel pair against the reference's
    autodiff in float32 on the same (rounded) inputs: to float32's
    rounding for float32 inputs, to the output's one bf16 rounding for
    bf16 inputs. The first ``n - 1`` rows see zeros before the start."""
    b, s, c, dtype, bias, width, scale = CASES[case]
    x, w, bb, dy = _inputs(b, s, c, dtype, bias)
    kw = dict(norm_width=width, norm_scale=scale)
    got = _y_and_grads(L.short_conv, x, w, bb, dy, **kw)
    up = lambda v: None if v is None else v.astype(F32)  # noqa: E731
    want = _y_and_grads(short_conv_reference.short_conv, up(x), up(w),
                        up(bb), up(dy), **kw)
    tol = 2e-6 if dtype == "float32" else 2.0 ** -8
    names = ("y", "dx", "dw", "dbias")
    assert len(got) == len(want) == (4 if bias else 3)
    for name, g, r in zip(names, got, want):
        assert g.dtype == jnp.dtype(dtype) and g.shape == r.shape, name
        assert _err(g, r) < tol, (case, name, _err(g, r))
    # zeros before the start: the first n - 1 rows are what the reference
    # makes of those rows alone
    head = short_conv_reference.short_conv(
        up(x)[:, :TAPS - 1], up(w), up(bb), **kw)
    assert _err(got[0][:, :TAPS - 1], head) < tol


def test_the_blocks_follow_from_the_shape():
    """The geometry is read from S, C, the dtype and the norm's width: a
    block of channels is 128 lanes, a block of the
    sequence the largest divisor under ``_SEQ_BLOCK``; at the cells'
    shapes a grid step is 8192 rows by 128 channels in chunks of 256."""
    bf = jnp.bfloat16
    with pytest.MonkeyPatch.context() as mp:
        mp.undo()       # the module's own blocks
        mp.setattr(kernels, "_SEQ_BLOCK", 8192)
        mp.setattr(kernels, "_CHUNK", 256)
        assert kernels._geometry(16384, 4096, bf, 128) == (8192, 128, 256)
        assert kernels._geometry(8192, 4352, bf, None) == (8192, 128, 256)
        assert kernels._geometry(6144, 512, bf, 64) == (6144, 128, 256)
    assert kernels._geometry(96, 256, F32, 64) == (32, 128, 16)
    assert kernels._geometry(32, 512, F32, 128) == (32, 128, 16)
    assert kernels._geometry(24, 160, F32, None) == (24, 160, 8)


@pytest.mark.parametrize("what, shape, kw, message", [
    ("ragged_sequence", (1, 12, 128), {}, "12 is not a multiple of 8"),
    ("ragged_bf16_sequence", (1, 24, 128), dict(dtype="bfloat16"),
     "24 is not a multiple of 16"),
    ("split_head", (1, 16, 192), dict(norm_width=128),
     "192 channels are not whole heads of 128"),
    ("head_across_tiles", (1, 16, 384), dict(norm_width=96),
     "norm_width 96 does not divide the 128 lanes"),
    ("head_wider_than_a_tile", (1, 16, 512), dict(norm_width=256),
     "norm_width 256 does not divide the 128 lanes"),
    ("too_many_taps", (1, 16, 128), dict(taps=10), "10 taps reach past"),
    ("other_channels", (1, 16, 128), dict(w_channels=64),
     r"taps \(4, 64\) for 128 channels"),
    ("narrow_on_the_chip", (1, 16, 160), dict(chip=True),
     "on the chip the channels must be a multiple of 128, not 160"),
])
def test_a_shape_the_kernels_do_not_take_is_refused(monkeypatch, what,
                                                    shape, kw, message):
    kw = dict(kw)
    dtype = kw.pop("dtype", "float32")
    if kw.pop("chip", False):   # nothing is compiled: the check is first
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = jnp.zeros(shape, dtype)
    w = jnp.zeros((kw.pop("taps", TAPS), kw.pop("w_channels", shape[2])),
                  dtype)
    with pytest.raises(ValueError, match=message):
        L.short_conv(x, w, **kw)


def _two_devices():
    from deepspeed_tpu.parallel.mesh import MeshTopology, TopologyConfig
    mt = MeshTopology(TopologyConfig(fsdp=2), devices=jax.devices()[:2])
    return mt, mt.sharding(mt.batch_axes(), "sp")


@pytest.mark.parametrize("batch", [2, 3])
def test_sharded_op_on_the_cpu_mesh_matches_one_device(batch):
    """``sharded_short_conv`` under a jit over two devices, the batch over
    ``fsdp`` (an uneven batch replicated): y, dx and the taps' and the
    bias's gradients, which are sums over the shards, are one device's."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    mt, act = _two_devices()
    x, w, bb, dy = _inputs(batch, 64, 128, "float32", True, seed=5)
    rows = NamedSharding(mt.mesh, P(mt.batch_axes() if batch == 2 else None))
    kw = dict(norm_width=64, norm_scale=0.5)
    with mt.mesh:
        got = _y_and_grads(L.sharded_short_conv(act), jax.device_put(x, rows),
                           w, bb, jax.device_put(dy, rows), **kw)
    want = _y_and_grads(L.short_conv, x, w, bb, dy, **kw)
    for name, g, r in zip(("y", "dx", "dw", "dbias"), got, want):
        assert _err(g, r) < 1e-6, name


def _kernel_calls(jaxpr, per_shard=False):
    """[(kernel, whether a shard_map encloses it)] of the short
    convolution's ``pallas_call``s anywhere under ``jaxpr``."""
    out = []
    for eqn in jaxpr.eqns:
        name = str(eqn.params.get("name", ""))
        if eqn.primitive.name == "pallas_call" and "short_conv" in name:
            out.append((name, per_shard))
            continue
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else (v,)):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    out += _kernel_calls(
                        j, per_shard or eqn.primitive.name == "shard_map")
    return out


def test_the_kimi_model_on_a_two_device_mesh_runs_the_op_per_shard():
    """With ``act_sharding`` on a mesh of two devices ``KimiLinear._mixers``
    hands the layers ``sharded_short_conv``: every kernel call of the loss
    and of its gradient sits in a shard_map, and loss and gradients are
    one device's; at the ONE KDA layer that holds the op (the row's
    ``short_conv`` cut, ISSUE 58: the preset's five layers were the longest
    case of the suite, 136 s; read at the one: a loss 8e-8 apart, a leaf
    6e-7)."""
    from helpers.families import FAMILIES, tiny
    mt, act = _two_devices()
    model = tiny("kimi_linear", **FAMILIES["kimi_linear"].short_conv)
    params = model.init(jax.random.PRNGKey(3))
    tok = np.random.default_rng(0).integers(
        0, model.config.vocab_size, (2, 65))
    batch = (jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:]))
    one = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    sharded = jax.value_and_grad(
        lambda p, b: model.loss(p, b, act_sharding=act))
    with mt.mesh:
        jaxpr = jax.make_jaxpr(sharded)(params, batch)
        two = jax.jit(sharded)(params, batch)
    calls = _kernel_calls(jaxpr.jaxpr)
    assert {name for name, _ in calls} == {"ds_short_conv_fwd",
                                           "ds_short_conv_bwd"}
    assert all(per_shard for _, per_shard in calls), calls
    assert abs(float(one[0]) - float(two[0])) < 1e-5 * float(one[0])
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(one[1]),
            jax.tree_util.tree_leaves_with_path(two[1]), strict=True):
        if np.any(np.asarray(a)):
            assert _err(b, a) < 2e-5, jax.tree_util.keystr(path)

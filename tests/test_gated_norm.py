"""The gated per-head RMSNorm behind a delta-rule scan (ISSUE 55): the
kernel pair ``ds_gated_norm_fwd`` / ``ds_gated_norm_bwd`` of
``ops/pallas/gated_norm.py`` in interpret mode against the two callers'
``jax.numpy`` expressions as they stood before it
(``tests/helpers/gated_norm_reference.py``): the forward to the bit in both
forms, the four cotangents within bf16's rounding, ``o`` as [B, S, H, d]
and as the heads' stack the scan hands over. Their compile for the chip at
the cells' widths is ``tests/test_zero_layout.py``'s. A CPU run shows
results, never a time."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import layers as L
from deepspeed_tpu.ops.pallas import _common, gated_norm

from helpers import gated_norm_reference as reference

KIMI = dict(act="sigmoid", eps=1e-5, round_norm=True)
QWEN = dict(act="silu", eps=1e-6)


@pytest.fixture(autouse=True)
def _small_tiles(monkeypatch):
    """Tiles of 64 rows taken 16 at a time, so that a short sequence is
    several grid steps of several chunks; a kernel is traced once a
    geometry (``_common._bind``), so no trace of another size is bound
    here."""
    monkeypatch.setattr(gated_norm, "_ROWS_FWD", 64)
    monkeypatch.setattr(gated_norm, "_ROWS_BWD", 64)
    monkeypatch.setattr(gated_norm, "_CHUNK", 16)
    _common._TRACED.clear()
    yield
    _common._TRACED.clear()


def _case(form, b, s, h, d, dtype=jnp.bfloat16, seed=0):
    """(the reference, the op, (o, gate, w[, bias]), dy)."""
    rng = np.random.default_rng(seed)
    draw = lambda scale, *shape: jnp.asarray(  # noqa: E731
        scale * rng.normal(size=shape), dtype)
    o, gate, dy = draw(3, b, s, h, d), draw(2, b, s, h * d), draw(
        1, b, s, h * d)
    w = jnp.asarray(1 + 0.1 * rng.normal(size=(d,)), dtype)
    if form == "kimi":
        return (functools.partial(reference.kimi_gated_norm, eps=KIMI["eps"]),
                functools.partial(L.gated_norm, **KIMI),
                (o, gate, w, draw(1, h * d)), dy)
    return (functools.partial(reference.qwen_gated_norm, eps=QWEN["eps"]),
            functools.partial(L.gated_norm, **QWEN), (o, gate, w), dy)


def _run(fn, args, dy):
    """(y, the cotangents of ``args``), one jitted program."""
    return jax.jit(lambda dy, *a: (lambda y, vjp: (y, *vjp(dy)))(
        *jax.vjp(fn, *a)))(dy, *args)


def _by_head(o, groups):
    b, s, h, d = o.shape
    return o.reshape(b, s, groups, h // groups, d).transpose(2, 0, 3, 1, 4)


def _err(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return np.abs(got - want).max() / np.abs(want).max()


SHAPES = [
    (1, 128, 32, 128),      # H d = 4096 (the cells' width), two row tiles
    (2, 192, 2, 128),       # B > 1, three whole tiles
    (2, 200, 4, 128),       # 200 is no multiple of the tile of 64: a last
    #                         partial tile, its rows kept out of the sums
    (1, 64, 1, 128),        # one head, one tile
    (3, 40, 2, 64),         # fewer rows than a tile; a head of 64
]


@pytest.mark.parametrize("b,s,h,d", SHAPES, ids=str)
@pytest.mark.parametrize("form", ["kimi", "qwen"])
def test_the_forward_is_the_callers_expression_to_the_bit(form, b, s, h, d):
    """Both forms: sigmoid + bias + the bf16-rounded norm; SiLU, float32
    from ``o`` to the last cast. Float32 statistics and activations in the
    callers' own order, so not one bit differs."""
    want_fn, fn, args, _ = _case(form, b, s, h, d)
    got, want = jax.jit(fn)(*args), jax.jit(want_fn)(*args)
    assert got.shape == (b, s, h * d) and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("b,s,h,d", SHAPES, ids=str)
@pytest.mark.parametrize("form", ["kimi", "qwen"])
def test_the_cotangents_are_autodiffs_within_bf16s_rounding(form, b, s, h, d):
    """``do``, ``dgate``, ``dw`` and, with a bias, ``db_g`` against
    ``jax.grad`` of the reference: each in its operand's shape and dtype,
    within a rounding or two of bfloat16 of the largest entry (the
    reference rounds its float32 cotangents where its casts stood, the
    kernel once as it writes)."""
    want_fn, fn, args, dy = _case(form, b, s, h, d)
    got, want = _run(fn, args, dy), _run(want_fn, args, dy)
    names = ("y", "do", "dgate", "dw", "db_g")
    for name, arg, g, w_ in zip(names[1:], args, got[1:], want[1:]):
        assert g.shape == arg.shape and g.dtype == arg.dtype, name
        # dw and db_g are sums over all rows: their last bit is a bf16 ulp
        assert _err(g, w_) <= 2 ** -7, (name, _err(g, w_))


@pytest.mark.parametrize("form,groups", [("kimi", 2), ("kimi", 4),
                                         ("qwen", 1)])
def test_the_heads_stack_is_read_and_written_where_it_lies(form, groups):
    """``o`` as ``chunk_kda(by_head=True)`` hands it over, [G, B, H / G,
    S, d]: the same ``y`` and cotangents to the bit, ``do`` in the stack's
    own form."""
    _, fn, args, dy = _case(form, 2, 136, 4, 128)
    want = _run(fn, args, dy)
    stacked = (_by_head(args[0], groups), *args[1:])
    got = _run(fn, stacked, dy)
    assert got[1].shape == stacked[0].shape
    for g, w_ in zip(got, (want[0], _by_head(want[1], groups), *want[2:])):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w_, np.float32))


def test_float32_operands_stay_float32():
    """In float32 nothing is rounded on the way: the reference to 1e-6."""
    want_fn, fn, args, dy = _case("qwen", 1, 96, 2, 128, jnp.float32)
    for g, w_ in zip(_run(fn, args, dy), _run(want_fn, args, dy)):
        assert g.dtype == jnp.float32 and _err(g, w_) <= 2e-6


def test_a_rematted_layer_runs_the_forward_twice_and_the_backward_once():
    """What a rematted layer holds of the pair, by the kernels' names in
    the jaxpr: the forward, remat's rerun (the output matmul's weight
    gradient wants ``y`` again), one backward. The residuals are the
    pair's inputs: without that matmul the rerun holds no kernel at all."""
    _, fn, args, dy = _case("kimi", 1, 64, 2, 128)
    wo = jnp.ones((256, 8), jnp.bfloat16)

    def count(layer, *more):
        loss = lambda *a: jnp.sum(  # noqa: E731
            jax.checkpoint(layer)(*a).astype(jnp.float32))
        # the loss's value keeps the first forward alive
        text = str(jax.make_jaxpr(jax.value_and_grad(
            loss, argnums=tuple(range(4 + len(more)))))(*args, *more))
        return (text.count("name=ds_gated_norm_fwd"),
                text.count("name=ds_gated_norm_bwd"))

    assert count(lambda *a: fn(*a[:4]) @ a[4], wo) == (2, 1)
    assert count(fn) == (1, 1)


@pytest.mark.parametrize("bad,match", [
    (dict(act="tanh"), "none of"),
    (dict(o=(2, 64, 3, 128)), "neither"),          # 3 heads for a gate of 4
    (dict(w=(64,)), "neither"),
    (dict(bias=(128,)), "neither"),
    (dict(o=(3, 2, 1, 64, 128)), "neither"),       # 3 groups of 4 heads
], ids=str)
def test_a_shape_or_activation_the_op_does_not_know_is_refused(bad, match):
    sd = lambda *s: jnp.zeros(s, jnp.bfloat16)  # noqa: E731
    shapes = dict(o=(2, 64, 4, 128), gate=(2, 64, 512), w=(128,), bias=None)
    shapes.update({k: v for k, v in bad.items() if k != "act"})
    with pytest.raises(ValueError, match=match):
        L.gated_norm(*(None if shapes[k] is None else sd(*shapes[k])
                       for k in ("o", "gate", "w", "bias")),
                     act=bad.get("act", "silu"))


def test_per_shard_of_the_batch_is_the_whole():
    """``sharded_gated_norm`` on a mesh of two devices: the batch split,
    the weight's and the bias's cotangents summed over the shards."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:2]), ("fsdp",))
    act = NamedSharding(mesh, P("fsdp", None, None))
    _, fn, args, dy = _case("kimi", 2, 64, 2, 128)
    per_shard = functools.partial(L.sharded_gated_norm(act), **KIMI)
    with mesh:
        got = _run(per_shard, args, dy)
    # a shard's dw and db_g are rounded to bf16 before the shards are summed
    for g, w_ in zip(got, _run(fn, args, dy)):
        assert _err(g, w_) <= 2 ** -7

"""The rotation's kernel pair (ISSUE 62): ``ops/pallas/rope.py``
``ds_rope_fwd`` / ``ds_rope_bwd`` against ``ops/layers.py``
``apply_rotary`` and the flash wrapper's transpose, in interpret mode, every
call jitted (one program a case). Which calls take the pair and which keep
the XLA form, what the gauge says of each, the per-shard form on a
4-device mesh, and two models at a lane-aligned head whose steps hand the
tables over. A CPU run shows results and counts, never a time."""

import functools

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from deepspeed_tpu import telemetry
from deepspeed_tpu.models import get_model_class
from deepspeed_tpu.ops import layers as L
from deepspeed_tpu.ops.pallas import rope
from deepspeed_tpu.ops.pallas.flash_attention import (
    flash_attention, sharded_flash_attention)

from helpers.families import _telemetry_isolation  # noqa: F401

BF, F32 = jnp.bfloat16, jnp.float32


def _tables(s, d, rot, **scaling):
    return L.rotary_tables(
        *L.rotary_embedding(s, d, scaling=dict(
            scaling, partial_rotary_factor=rot / d)), d)


def _qkv(b, s, hq, hk, d, seed=0, dtype=BF):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype)
                 for k, h in zip(ks, (hq, hk, hk)))


def _to_heads(x):
    return x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], x.shape[3])


def _same(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


# (batch, query heads, key heads, head width, rotated width): a whole head
# of 128 at 9 and 1 query heads a key head, Laguna's full layer (64 of
# 128), Qwen3-Next (64 of 256)
SHAPES = [(1, 9, 1, 128, 128), (2, 2, 2, 128, 128), (2, 6, 2, 128, 64),
          (1, 4, 2, 256, 64), (2, 2, 2, 256, 64)]


@pytest.mark.parametrize("b,hq,hk,d,rot", SHAPES)
def test_the_pair_is_apply_rotary_and_the_transpose_bit_for_bit(
        b, hq, hk, d, rot):
    """q, k and their cotangents through the kernels equal the XLA form's
    in every bit: the same float32 products, one rounding; a YaRN table's
    factor rides inside the tables."""
    s = 256
    t = _tables(s, d, rot, rope_type="yarn", factor=4.0,
                original_max_position_embeddings=64)
    assert t.wide is not None and t.rotated == rot
    q, k, _ = _qkv(b, s, hq, hk, d)
    dq, dk = (_to_heads(x) for x in _qkv(b, s, hq, hk, d, seed=1)[:2])

    def pair(q, k):
        return tuple(rope.rotate_to_heads(x, t.wide, rot) for x in (q, k))

    def xla(q, k):
        return tuple(_to_heads(x) for x in L.rotate(q, k, t))

    def both(fn):
        out, vjp = jax.vjp(fn, q, k)
        return out, vjp((dq, dk))

    _same(jax.jit(lambda: both(pair))(), jax.jit(lambda: both(xla))())


@pytest.mark.parametrize("b,hq,hk,d,rot", SHAPES[::2])
def test_flash_attention_with_the_tables_is_rotate_then_attend(
        b, hq, hk, d, rot):
    """``rotary_attention`` hands the tables to ``flash_attention`` (a
    ``functools.partial`` of it too), whose output and gradients equal
    rotating first; the gauge counts two rotations built as the kernel."""
    s = 128
    t = _tables(s, d, rot)
    q, k, v = _qkv(b, s, hq, hk, d)
    attn = functools.partial(flash_attention, window=64)
    assert L.hands_rotary(attn, t) and L.hands_rotary(flash_attention, t)

    def loss(fn):
        def f(q, k, v):
            o = fn(q, k, v)
            return jnp.sum(o.astype(F32) ** 2), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    def first(q, k, v):
        q, k = L.rotate(q, k, t)
        return attn(q, k, v, causal=True)

    telemetry.configure()
    got = loss(lambda q, k, v: L.rotary_attention(
        attn, q, k, v, t, causal=True))(q, k, v)
    g = telemetry.get_registry().get("ds_rope_calls")
    labels = dict(head=str(d), rotated=str(rot))
    assert g.value(form="kernel", **labels) >= 2
    assert g.value(form="xla", **labels) == 0
    _same(got, loss(first)(q, k, v))
    assert g.value(form="xla", **labels) >= 2


def _plain(q, k, v, **kw):
    return L.dot_product_attention(q, k, v, **kw)


@pytest.mark.parametrize("why", ["head_64", "positions", "plain_attention",
                                 "unaligned_rows", "float16_tables"])
def test_what_must_not_engage_keeps_the_xla_form(why):
    """A head of 64 (LFM2), gathered positions (decode), an attention that
    does not say it rotates, a sequence the flash kernels refuse and tables
    that are not float32: ``apply_rotary`` runs, the gauge says ``xla`` and
    no kernel is built."""
    b, s, hq, hk, d = 1, 128, 4, 2, 128
    attn, positions, dtype = flash_attention, None, F32
    if why == "head_64":
        d = 64
    elif why == "positions":
        positions = jnp.arange(s)[None, :] + 3
    elif why == "plain_attention":
        attn = _plain
    elif why == "unaligned_rows":
        s = 192
    else:
        dtype = jnp.float16
    t = L.rotary_tables(*L.rotary_embedding(s + 8, d, dtype=dtype), d)
    q, k, v = _qkv(b, s, hq, hk, d)
    assert (t.wide is None) == (why in ("head_64", "float16_tables"))
    assert L.hands_rotary(attn, t, positions) == (why == "unaligned_rows")

    telemetry.configure()
    got = jax.jit(lambda q, k, v: L.rotary_attention(
        attn, q, k, v, t, positions=positions, causal=True))(q, k, v)
    g = telemetry.get_registry().get("ds_rope_calls")
    labels = dict(head=str(d), rotated=str(d))
    assert g.value(form="xla", **labels) == 2
    assert g.value(form="kernel", **labels) == 0
    rq = L.apply_rotary(q, t.cos, t.sin, positions)
    rk = L.apply_rotary(k, t.cos, t.sin, positions)
    want = jax.jit(functools.partial(attn, causal=True))(rq, rk, v)
    _same(got, want)


def test_per_shard_on_four_devices_is_the_one_device_result():
    """``sharded_flash_attention`` passes the tables to every shard whole:
    the batch over ``fsdp`` and the heads over ``tp`` of a 2 x 2 mesh, the
    output and the gradients equal the one-device call's."""
    b, s, hq, hk, d, rot = 2, 128, 4, 2, 128, 64
    t = _tables(s, d, rot)
    q, k, v = _qkv(b, s, hq, hk, d)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("fsdp", "tp"))
    sharded = sharded_flash_attention(mesh, ("fsdp",), window=64)
    assert L.hands_rotary(sharded, t)

    def grads(attn):
        def f(q, k, v):
            o = L.rotary_attention(attn, q, k, v, t, causal=True)
            return jnp.sum(o.astype(F32) ** 2), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    # (the loss is a sum over all devices' rows in another order)
    (_, o), g = grads(sharded)(q, k, v)
    (_, o1), g1 = grads(functools.partial(flash_attention, window=64))(
        q, k, v)
    _same((o, g), (o1, g1))


def test_geometry_fetches_a_row_tiles_tables_once_for_all_heads():
    """Rows, chunk and heads a grid step at the cells' shapes; the head
    groups are the grid's innermost axis, so the tables' block index does
    not move while a row tile's heads pass."""
    assert rope._geometry(8192, 72, 128) == (512, 64, 8)
    assert rope._geometry(8192, 48, 128) == (512, 64, 8)
    assert rope._geometry(16384, 4, 128) == (512, 64, 4)
    assert rope._geometry(16384, 16, 256) == (512, 64, 4)
    assert rope._geometry(16384, 2, 256) == (512, 64, 2)
    assert rope._geometry(96, 3, 16) == (96, 96, 3)      # interpret only
    x = jax.ShapeDtypeStruct((1, 1024, 16 * 128), BF)
    tab = jax.ShapeDtypeStruct((1024, 128), F32)
    jaxpr = jax.make_jaxpr(lambda x, c, s: rope._call(
        x, c, s, heads=16, rot=128, to_heads=True))(x, tab, tab)
    call = next(e for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "pallas_call")
    grid = call.params["grid_mapping"]
    assert tuple(grid.grid) == (1, 2, 2)
    for table in grid.block_mappings[1:3]:
        index = jax.extend.core.jaxpr_as_fun(table.index_map_jaxpr)
        assert [tuple(int(i) for i in index(0, r, h))
                for r in (0, 1) for h in (0, 1)] == [
                    (0, 0), (0, 0), (1, 0), (1, 0)]


@pytest.mark.parametrize("family,overrides", [
    ("mistral", dict(remat_policy="segments")),
    ("mistral", dict(remat_policy="nothing_saveable")),
    ("mellum", {}),
    ("ouro", {}),
])
def test_a_model_at_a_lane_aligned_head_hands_the_tables_over(
        family, overrides, monkeypatch):
    """The tiny preset at a head of 128 on the flash kernels: the loss and
    every gradient of the step that hands the tables to the kernels equal
    the step that rotates in XLA first (the same model with the wide
    tables taken away), and the gauge says which ran."""
    model = get_model_class(family)(
        size="tiny", attn_head_dim=128, attn_impl="flash", remat=True,
        param_dtype=BF, **overrides)
    params = model.init(jax.random.PRNGKey(0))
    tok = jnp.asarray(np.random.default_rng(0).integers(
        0, model.config.vocab_size, (1, 129)))
    batch = (tok[:, :-1], tok[:, 1:])
    telemetry.configure()
    got = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    g = telemetry.get_registry().get("ds_rope_calls")
    assert g is not None and not any(
        dict(key).get("form") == "xla" for key in g._values)

    narrow = lambda t: t._replace(wide=None)  # noqa: E731
    if hasattr(model, "_ropes"):
        monkeypatch.setattr(model, "_ropes", {
            kind: narrow(t) for kind, t in model._ropes.items()})
    else:
        monkeypatch.setattr(model, "_rotary", narrow(model._rotary))
    want = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    _same(got, want)

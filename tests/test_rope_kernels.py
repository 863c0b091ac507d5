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


# ------------------------------------------------- latent attention (ISSUE 65)
# the rotation of the shared key's and of a query head's ``rope`` channels:
# by halves (Xing4.0), a checkpoint's interleaved pairs (Kanana), none (Kimi)
FORMS = ["halves", "pairs", "none"]


def _latent(b, s, heads, nope, rope, dv, form, seed=0):
    """(q [B, S, H, nope + rope], kv [B, S, H, nope + dv], k_pe [B, S,
    rope]), cotangents of the three operands [B x H, S, .] and the
    tables."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    w = nope + rope
    args = tuple(jax.random.normal(k, dims, BF) for k, dims in zip(ks, (
        (b, s, heads, w), (b, s, heads, nope + dv), (b, s, rope))))
    cts = tuple(jax.random.normal(k, (b * heads, s, n), BF)
                for k, n in zip(ks[3:], (w, w, dv)))
    tables = None if form == "none" else L.latent_rotary_tables(
        *L.rotary_embedding(s + 8, rope, scaling=dict(
            rope_type="yarn", factor=4.0,
            original_max_position_embeddings=64)), pairs=form == "pairs")
    return args, cts, tables


def _built(q, kv, k_pe, tables, pairs, positions=None):
    """q, k, v [B x H, S, .] as ``latent_attention``'s XLA form hands them
    to an attention that does not take latent operands."""
    laid = []
    L.latent_attention(lambda q, k, v: laid.extend(
        _to_heads(x) for x in (q, k, v)) or v, q, kv, k_pe, tables,
        pairs=pairs, positions=positions)
    return tuple(laid)


def _pairs_as_halves(x, nope):
    """The kernels leave interleaved pairs where they lie: laid out as
    ``pairs_to_halves`` lays them."""
    return jnp.concatenate([x[..., :nope], L.pairs_to_halves(x[..., nope:])],
                           axis=-1)


def _halves_as_pairs(x, nope):
    r = x[..., nope:]
    return jnp.concatenate([x[..., :nope], jnp.stack(
        jnp.split(r, 2, axis=-1), axis=-1).reshape(r.shape)], axis=-1)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("heads,nope,rot,dv", [
    (4, 128, 64, 128),      # the three cells' widths: a head of 192
    (2, 256, 64, 128),      # a nope of two tiles
    (6, 128, 64, 256),      # a value of two tiles, three pairs of heads
])
def test_the_latent_pass_is_slice_rotate_concatenate_and_transpose(
        form, heads, nope, rot, dv):
    """q, k and v through ``latent_to_heads`` equal the XLA form's in every
    bit once the pairs' rotated channels are laid as halves (each channel
    today's value at a permuted place); the cotangents of q and kv too, and
    the shared key's, summed over the heads in float32 and rounded once
    where XLA rounds the sum and the rotation, to that rounding."""
    b, s = 2, 256
    args, cts, tables = _latent(b, s, heads, nope, rot, dv, form)
    pairs = form == "pairs"

    def pair(q, kv, k_pe):
        return rope.latent_to_heads(q, kv, k_pe, tables and tables.wide,
                                    pairs=pairs)

    def xla(q, kv, k_pe):
        return _built(q, kv, k_pe, tables, pairs)

    def both(fn, cts):
        out, vjp = jax.vjp(fn, *args)
        return out, vjp(cts)

    there = lambda x: (  # noqa: E731
        _halves_as_pairs(x, nope) if pairs and x.shape[-1] == nope + rot
        else x)
    back = lambda x: (  # noqa: E731
        _pairs_as_halves(x, nope) if pairs and x.shape[-1] == nope + rot
        else x)
    got, (dq, dkv, dk_pe) = jax.jit(lambda: both(
        pair, tuple(there(c) for c in cts)))()
    want, (wq, wkv, wk_pe) = jax.jit(lambda: both(xla, cts))()
    _same(tuple(back(x) for x in got), want)
    _same((dq, dkv), (wq, wkv))
    # the reference of dk_pe in float32: one rounding, as the kernel's
    f32 = lambda t: jax.tree.map(lambda x: x.astype(F32), t)  # noqa: E731
    ref = jax.jit(lambda: jax.vjp(xla, *f32(args))[1](f32(cts))[2])()
    np.testing.assert_allclose(np.asarray(dk_pe, np.float32),
                               np.asarray(ref), rtol=2 ** -7, atol=2 ** -6)
    assert np.abs(np.asarray(wk_pe, np.float32) - np.asarray(ref)).max() > 0


@pytest.mark.parametrize("form", FORMS)
def test_flash_attention_with_the_latent_operands_is_build_then_attend(form):
    """``latent_attention`` hands the three projections to
    ``flash_attention.latent``; output and gradients equal building q, k, v
    in XLA first (pairs: to the order of the scores' sums, q and k being
    permuted alike), and the gauge counts q and k built as the kernel at a
    head of 192."""
    b, s, heads, nope, rope, dv = 1, 128, 2, 128, 64, 128
    args, _, tables = _latent(b, s, heads, nope, rope, dv, form)
    pairs = form == "pairs"
    assert L.hands_latent(flash_attention, *args, tables)

    def loss(fn):
        def f(*args):
            o = fn(*args)
            return jnp.sum(o.astype(F32) ** 2), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    def first(q, kv, k_pe):
        q, k, v = (x.reshape(b, heads, s, -1).transpose(0, 2, 1, 3)
                   for x in _built(q, kv, k_pe, tables, pairs))
        return flash_attention(q, k, v, causal=True)

    telemetry.configure()
    got = loss(lambda *a: L.latent_attention(
        flash_attention, *a, tables, pairs=pairs, causal=True))(*args)
    g = telemetry.get_registry().get("ds_rope_calls")
    labels = dict(head="192", rotated="0" if form == "none" else "64")
    assert g.value(form="kernel", **labels) >= 2
    assert g.value(form="xla", **labels) == 0
    want = loss(first)(*args)
    if pairs:
        for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(w, np.float32),
                rtol=2 ** -6, atol=2 ** -6 * float(jnp.max(jnp.abs(w))))
    else:
        (_, o), (dq, dkv, _) = got
        (_, wo), (wq, wkv, _) = want
        _same((o, dq, dkv), (wo, wq, wkv))
    assert g.value(form="xla", **labels) >= 2


@pytest.mark.parametrize("why", [
    "nope_96", "value_64", "rope_128", "three_heads", "positions",
    "plain_attention",
    "a_partial_of_the_attention", "rows_192", "a_planted_rotation",
    "float16_tables"])
def test_what_must_not_engage_keeps_latent_attentions_xla_form(
        why, monkeypatch):
    """A nope or a value that is no whole lane tile, a rotated width other
    than the 64 that two heads fill a tile with, an odd head count,
    gathered positions, an attention that does not say it takes
    latent operands (or a ``functools.partial`` that hides it), rows the
    flash kernels refuse, a rotation planted in ``apply_rotary``'s place
    (the benchmark's controls) and tables that are not float32: the XLA
    form runs, the gauge says ``xla`` and no kernel of the pass is built."""
    b, s, heads, nope, rope, dv = 1, 128, 2, 128, 64, 128
    attn, positions, dtype = flash_attention, None, F32
    if why == "nope_96":
        nope = 96
    elif why == "value_64":
        dv = 64
    elif why == "rope_128":
        rope = 128
    elif why == "three_heads":
        heads = 3
    elif why == "positions":
        positions = jnp.arange(s)[None, :] + 3
    elif why == "plain_attention":
        attn = _plain
    elif why == "a_partial_of_the_attention":
        attn = functools.partial(flash_attention, window=None)
    elif why == "rows_192":
        s = 192
    elif why == "a_planted_rotation":
        rotary = L.apply_rotary
        monkeypatch.setattr(L, "apply_rotary", lambda x, cos, sin: (
            x if x.shape[2] == 1 else rotary(x, cos, sin)))
    else:
        dtype = jnp.float16
    args, _, _ = _latent(b, s, heads, nope, rope, dv, "none")
    tables = L.latent_rotary_tables(
        *L.rotary_embedding(s + 8, rope, dtype=dtype))
    assert (tables.wide is None) == (why in ("rope_128", "float16_tables"))
    assert not L.hands_latent(attn, *args, tables, positions)

    telemetry.configure()
    got = jax.jit(lambda *a: L.latent_attention(
        attn, *a, tables, positions=positions, causal=True))(*args)
    g = telemetry.get_registry().get("ds_rope_calls")
    labels = dict(head=str(nope + rope), rotated=str(rope))
    assert g.value(form="xla", **labels) == 2
    assert g.value(form="kernel", **labels) == 0
    q, k, v = (x.reshape(b, heads, s, -1).transpose(0, 2, 1, 3)
               for x in _built(*args, tables, False, positions))
    _same(got, jax.jit(functools.partial(attn, causal=True))(q, k, v))
    if why == "a_planted_rotation":     # and the planted fault is IN it
        monkeypatch.undo()
        right = _built(*args, tables, False)[1]
        assert not np.array_equal(np.asarray(right, np.float32),
                                  np.asarray(_to_heads(k), np.float32))


def test_latent_per_shard_on_four_devices_is_the_one_device_result():
    """``sharded_flash_attention(...).latent``: the batch over ``fsdp`` and
    the heads over ``tp`` of a 2 x 2 mesh, the shared key whole on every
    shard of the heads and its cotangent summed over them: the output and
    the gradients equal the one-device call's (``dk_pe``: two shards' float32
    sums rounded each, to that rounding)."""
    b, s, heads, nope, rope, dv = 2, 128, 4, 128, 64, 128
    args, _, tables = _latent(b, s, heads, nope, rope, dv, "pairs")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("fsdp", "tp"))
    sharded = sharded_flash_attention(mesh, ("fsdp",))
    assert L.hands_latent(sharded, *args, tables)

    def grads(attn):
        def f(*args):
            o = L.latent_attention(attn, *args, tables, pairs=True,
                                   causal=True)
            return jnp.sum(o.astype(F32) ** 2), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, o), (dq, dkv, dk_pe) = grads(sharded)(*args)
    (_, o1), (dq1, dkv1, dk_pe1) = grads(flash_attention)(*args)
    _same((o, dq, dkv), (o1, dq1, dkv1))
    np.testing.assert_allclose(np.asarray(dk_pe, np.float32),
                               np.asarray(dk_pe1, np.float32),
                               rtol=2 ** -6, atol=2 ** -6 * float(
                                   jnp.max(jnp.abs(dk_pe1))))


def test_latent_geometry_keeps_the_blocks_inside_the_vmem_xla_gets():
    """Rows, chunk and heads a grid step at the three cells' shapes: four
    heads of 128 + 64 / 128 by 512 rows, both buffers of the five head
    blocks 8.5 MiB; the heads are the grid's innermost axis, so the
    tables' and the shared key's block index does not move while a row
    tile's heads pass, and the backward's float32 scratch sums them."""
    for s in (32768, 16384, 8192):
        assert rope._latent_geometry(s, 32, 128, 128, 2) == (512, 64, 4)
    assert rope._latent_geometry(8192, 6, 256, 128, 2) == (512, 64, 2)
    heads, w = 8, 192
    flat = [jax.ShapeDtypeStruct((1, 1024, heads * n), BF)
            for n in (w, 256)] + [jax.ShapeDtypeStruct((1, 1024, 64), BF)]
    tab = jax.ShapeDtypeStruct((1024, 128), F32)
    jaxpr = jax.make_jaxpr(lambda *a: rope._latent_call(
        *a, heads=heads, m=1, to_heads=True))(*flat, tab, tab)
    call = next(e for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "pallas_call")
    grid = call.params["grid_mapping"]
    assert tuple(grid.grid) == (1, 2, 2)
    for shared in grid.block_mappings[2:5]:         # k_pe, cos_w, sin_w
        index = jax.extend.core.jaxpr_as_fun(shared.index_map_jaxpr)
        assert [tuple(int(i) for i in index(0, r, h))[-2:]
                for r in (0, 1) for h in (0, 1)] == [
                    (0, 0), (0, 0), (1, 0), (1, 0)]


@pytest.mark.parametrize("family,rotated,layers", [
    ("deepseek_v3", 64, {}), ("xing4_0", 64, {}),
    ("kimi_linear", 0, dict(kda_layers=(1,), full_attn_layers=(2,)))])
def test_a_latent_model_at_the_cells_widths_hands_its_projections_over(
        family, rotated, layers, monkeypatch):
    """The tiny preset at the cells' 128 + 64 / 128 on the flash kernels:
    the step's gauge says q and k were built by the kernel at a head of 192
    and never by XLA, and the loss and the gradients are those of the same
    model with the hand-off taken away, to the rounding of a bf16 step (the
    pairs' q and k are permuted alike; ``dk_pe`` is rounded once)."""
    model = get_model_class(family)(
        size="tiny", qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, attn_impl="flash", remat=True, param_dtype=BF,
        num_layers=2, **layers)
    params = model.init(jax.random.PRNGKey(0))
    tok = jnp.asarray(np.random.default_rng(0).integers(
        0, model.config.vocab_size, (1, 129)))
    batch = (tok[:, :-1], tok[:, 1:])
    telemetry.configure()
    got = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    g = telemetry.get_registry().get("ds_rope_calls")
    labels = dict(head="192", rotated=str(rotated))
    assert g.value(form="kernel", **labels) >= 2
    assert g.value(form="xla", **labels) == 0

    monkeypatch.delattr(flash_attention, "latent")
    want = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert g.value(form="xla", **labels) >= 2
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=2e-3)
    for a, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        assert np.linalg.norm(a - w) <= 0.03 * np.linalg.norm(w) + 1e-6

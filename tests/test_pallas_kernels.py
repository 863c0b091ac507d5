import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.layers import dot_product_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

# the package exports the function under the module's name
fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")


@pytest.mark.parametrize("s,hq,hkv,d", [(128, 4, 4, 32), (256, 4, 2, 64)])
def test_flash_forward_matches_reference(s, hq, hkv, d):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (2, s, hq, d))
    k = jax.random.normal(k2, (2, s, hkv, d))
    v = jax.random.normal(k3, (2, s, hkv, d))
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_non_causal():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (1, 128, 2, 32))
    k = jax.random.normal(k2, (1, 128, 2, 32))
    v = jax.random.normal(k3, (1, 128, 2, 32))
    ref = dot_product_attention(q, k, v, causal=False)
    out = flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("hq,hkv", [(2, 2), (8, 2), (4, 1)])
def test_flash_grads_match_reference(hq, hkv):
    """Gradients vs the exact reference, including GQA/MQA head ratios —
    the GQA-native backward emits per-q-head dk/dv and group-sums them
    (kernel indexes shared kv at q_head // rep; no repeated kv exists)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k1, (1, 256, hq, 32))
    k = jax.random.normal(k2, (1, 256, hkv, 32))
    v = jax.random.normal(k3, (1, 256, hkv, 32))

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    # jitted: eager interpret-mode Pallas dispatches op by op (~3x slower)
    gf = jax.jit(jax.grad(f_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(f_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_flash_bf16():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(k1, (1, 128, 2, 32), jnp.bfloat16)
    k = jax.random.normal(k2, (1, 128, 2, 32), jnp.bfloat16)
    v = jax.random.normal(k3, (1, 128, 2, 32), jnp.bfloat16)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_flash_unaligned_seq_falls_back_exact():
    """s=192 (not a multiple of 128) must not silently truncate the tail."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(k1, (1, 192, 2, 32))
    k = jax.random.normal(k2, (1, 192, 2, 32))
    v = jax.random.normal(k3, (1, 192, 2, 32))
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def _live_pairs(s, window, causal, rows=slice(None), cols=slice(None)):
    """The real mask, pair by pair: [s, s] bool (or its ``rows`` x ``cols``
    alone: the cells' lengths do not fit whole)."""
    rel = np.arange(s)[rows, None] - np.arange(s)[None, cols]
    live = rel >= 0 if causal else np.ones(rel.shape, bool)
    return live & (rel < window) if window is not None else live


@pytest.mark.parametrize("s,b,window,causal", [
    (640, 128, 256, True), (640, 128, 384, True),      # multiples of b
    (640, 128, 300, True), (640, 128, 129, True),      # two far-edge tiles
    (640, 128, 128, True), (640, 128, 127, True), (640, 128, 100, True),
    (640, 128, 2, True), (640, 128, 1, True),          # no live tile
    (640, 128, 640, True), (640, 128, 639, True), (640, 128, 5000, True),
    (640, 128, None, True), (640, 128, None, False),
    (1024, 512, 700, True), (1024, 256, 512, True), (128, 128, 64, True),
])
def test_flash_tile_classes_match_the_mask(s, b, window, causal):
    """A tile is skipped iff no pair of it is live, unmasked iff all are,
    masked otherwise; and the ranges the kernels sweep are exactly the
    tiles that are not skipped, each under the body of its class."""
    n = s // b
    edge, dead = fa.tile_bands(s, b, window)
    live = _live_pairs(s, window, causal).reshape(n, b, n, b)
    want = {}
    for i in range(n):
        for j in range(n):
            t = live[i, :, j, :]
            want[i, j] = ("skipped" if not t.any() else
                          "unmasked" if t.all() else "masked")
            assert fa.tile_kind(i - j, edge, dead, causal) == want[i, j], \
                (i, j)
    counts = fa.tile_counts(s, b, window, causal)
    swept = [k for (i, j), k in want.items() if not causal or j <= i]
    assert counts == {k: swept.count(k) for k in fa.TILE_KINDS}
    if not causal:
        return          # one unmasked loop over every tile
    for x in range(n):
        lo, a = (int(r) for r in fa._fwd_ranges(x, edge, dead))
        c, hi = (int(r) for r in fa._bwd_ranges(x, n, edge, dead))
        assert 0 <= lo <= a <= x and x + 1 <= c <= hi <= n
        fwd = ({j: "masked" for j in range(lo, a)}
               | {j: "unmasked" for j in range(a, x)} | {x: "masked"})
        bwd = ({x: "masked"} | {i: "unmasked" for i in range(x + 1, c)}
               | {i: "masked" for i in range(c, hi)})
        assert fwd == {j: want[x, j] for j in range(n)
                       if want[x, j] != "skipped"}
        assert bwd == {i: want[i, x] for i in range(n)
                       if want[i, x] != "skipped"}
        # the loops the kernels leave out at trace time are empty
        if edge >= n:
            assert lo == a and c == hi


@pytest.mark.parametrize("s,b,window", [
    (8192, 512, 512), (16384, 512, 1024),       # the Laguna and Mellum cells
    (1024, 512, 512), (1024, 512, 1024), (1024, 512, 700), (1024, 256, 512),
    (640, 128, 256), (640, 128, 100), (640, 128, 129), (640, 128, 1),
    (128, 128, 64),
    (8192, 512, 4096), (8192, 512, 1025), (640, 128, 300), (640, 128, 257),
    (8192, 512, None), (640, 128, None),        # the loops
])
def test_flash_band_covers_the_mask(s, b, window):
    """Where the window is at most two blocks wide each group of ``t`` rows
    meets ONE span: every live pair of the group lies in it, the span is in
    bounds and ``t``-aligned, the mask the body cuts it by (``off`` from
    the two group indices) is the real one, and the pairs the gauge counts
    are a direct count; forward (groups of queries, spans of keys) and
    backward (groups of keys, spans of queries). A wider window, or none,
    takes the loops."""
    live_all = sum(int(_live_pairs(s, window, True, slice(r, r + b)).sum())
                   for r in range(0, s, b))
    t = fa.band_rows(b, window, True)
    counts = fa.pair_counts(s, b, window, True)
    assert counts["live"] == live_all
    assert fa.band_rows(b, window, False) is None
    if window is None or window > 2 * b:
        assert t is None
        tiles = fa.tile_counts(s, b, window, True)
        assert counts["swept"] == (tiles["masked"] + tiles["unmasked"]) * b * b
        return
    assert b % t == 0 and (t % 128 == 0 or t == b == s)
    span = fa.band_span(s, t, window)
    assert span % t == 0 and t <= span <= s
    for kernel in ("fwd", "bwd"):
        swept = 0
        for g in range(s // t):
            own = slice(g * t, (g + 1) * t)
            if kernel == "fwd":         # [t queries, s keys]
                first = int(fa._fwd_span(g, t, span))
                live = _live_pairs(s, window, True, rows=own)
            else:                       # [t keys, s queries]
                first = int(fa._bwd_span(g, s, t, span))
                live = _live_pairs(s, window, True, cols=own).T
            met = slice(first * t, first * t + span)
            assert 0 <= met.start and met.stop <= s, (kernel, g)
            assert live[:, met].sum() == live.sum(), (kernel, g)
            # the body's mask: ``rel`` is query - key, from the local
            # indices and the two groups' distance
            own_less_met = (np.arange(t)[:, None] - np.arange(span)[None, :]
                            + (g - first) * t)
            rel = own_less_met if kernel == "fwd" else -own_less_met
            assert np.array_equal((rel >= 0) & (rel < window),
                                  live[:, met]), (kernel, g)
            swept += t * span
        assert counts["swept"] == swept


@pytest.mark.parametrize("s,window,hq,hkv,dtype", [
    (640, 300, 4, 4, jnp.float32), (640, 300, 4, 1, jnp.float32),
    (640, 256, 4, 4, jnp.float32), (640, 256, 4, 1, jnp.float32),
    (640, 100, 4, 4, jnp.float32), (640, 100, 4, 1, jnp.float32),
    (640, 640, 4, 4, jnp.float32), (640, 640, 4, 1, jnp.float32),
    (640, None, 4, 4, jnp.float32), (640, None, 4, 1, jnp.float32),
    (640, 300, 4, 1, jnp.bfloat16),
    (1024, 512, 4, 4, jnp.float32), (1024, 512, 4, 1, jnp.float32),
    (1024, 1024, 4, 4, jnp.float32), (1024, 1024, 4, 1, jnp.float32),
    (1024, 512, 4, 1, jnp.bfloat16),
])
def test_flash_all_tile_classes_match_reference(s, window, hq, hkv, dtype):
    """Forward and all three gradients against the exact masked form. At
    five 128-blocks: window 300 reaches masked (diagonal and far edge),
    unmasked and skipped tiles in one call of the LOOPS, 640 and None no
    far edge; windows 100 and 256 are at most two blocks wide and run the
    BAND, at one group a block. At two 512-blocks, windows 512 and 1024
    run the band at several groups a block (``t < b``), the first groups'
    spans held at the row's start and the last key groups' at its end."""
    from deepspeed_tpu.ops.layers import window_bias
    d = 32
    b = fa._block(s)
    t = fa.band_rows(b, window, True)
    assert (t is None) == (window in (300, 640, None))
    assert t is None or (t < b) == (s == 1024)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (1, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (1, s, hkv, d), dtype)
    bias = window_bias(s, window) if window is not None else None

    def both(attn):
        def f(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) ** 2), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))(q, k, v)

    (_, o1), g1 = both(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window))
    (_, o2), g2 = both(lambda q, k, v: dot_product_attention(
        q, k, v, causal=True, bias=bias))
    fwd_tol, bwd_tol = ((2e-5, 2e-4) if dtype == jnp.float32
                        else (3e-2, 1.5e-1))
    assert o1.dtype == dtype
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               atol=fwd_tol, rtol=fwd_tol)
    for a, b in zip(g1, g2):
        assert a.shape == b.shape and a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=bwd_tol, rtol=bwd_tol)


@pytest.mark.parametrize("window,want,swept,live", [
    (4096, {"masked": 24, "unmasked": 84, "skipped": 28},
     108 * 512 * 512, 25_167_872),
    (None, {"masked": 16, "unmasked": 120, "skipped": 0},
     136 * 512 * 512, 33_558_528),
    # the Laguna cell's window layers: the band's groups count as masked,
    # and 0.78 of the swept pairs are live where the loops' 31 tiles of
    # 512 x 512 had 0.50
    (512, {"masked": 64, "unmasked": 0, "skipped": 0},
     8192 * 640, 4_063_488),
])
def test_flash_tiles_gauge(window, want, swept, live):
    """``ds_flash_tiles`` and ``ds_flash_pairs`` are set where the kernels
    are built (trace time, so an abstract evaluation is enough), at the
    cells' shape."""
    from deepspeed_tpu import telemetry
    if window is None or window > 1024:
        assert fa.tile_counts(8192, 512, window, True) == want
    x = jax.ShapeDtypeStruct((1, 8192, 1, 128), jnp.bfloat16)

    def grad(q, k, v):
        return jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32)))(q)

    telemetry.shutdown()
    telemetry.configure()
    try:
        reg = telemetry.get_registry()
        assert reg.get("ds_flash_tiles") is None
        assert reg.get("ds_flash_pairs") is None
        jax.eval_shape(grad, x, x, x)
        g, pairs = reg.get("ds_flash_tiles"), reg.get("ds_flash_pairs")
        for kernel in ("fwd", "bwd"):
            assert {k: g.value(kernel=kernel, kind=k)
                    for k in fa.TILE_KINDS} == want
            assert pairs.value(kernel=kernel, kind="swept") == swept
            assert pairs.value(kernel=kernel, kind="live") == live
    finally:
        telemetry.shutdown()


def test_fused_adam_with_schedule_matches_optax():
    """lr schedule must be evaluated at the same step index as optax
    (first update uses lr(0))."""
    import optax
    from deepspeed_tpu.ops.pallas.fused_optimizers import fused_adam
    sched = optax.linear_schedule(0.0, 1e-2, 5)
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (13, 7))}
    grads = {"w": jax.random.normal(jax.random.PRNGKey(1), (13, 7))}
    tx_ref = optax.adamw(sched, weight_decay=0.01)
    tx_f = fused_adam(sched, weight_decay=0.01)
    s_ref, s_f = tx_ref.init(params), tx_f.init(params)
    p_ref, p_f = params, params
    for _ in range(3):
        u_ref, s_ref = tx_ref.update(grads, s_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, u_ref)
        u_f, s_f = tx_f.update(grads, s_f, p_f)
        p_f = optax.apply_updates(p_f, u_f)
    np.testing.assert_allclose(np.asarray(p_f["w"]), np.asarray(p_ref["w"]),
                               atol=1e-6, rtol=1e-5)


def test_fused_adam_l2_mode_matches_optax():
    """adam_w_mode=False must reproduce optax.adam + add_decayed_weights."""
    import optax
    from deepspeed_tpu.ops.pallas.fused_optimizers import fused_adam
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (11, 9))}
    grads = {"w": jax.random.normal(jax.random.PRNGKey(1), (11, 9))}
    tx_ref = optax.chain(optax.add_decayed_weights(0.05),
                         optax.adam(1e-2))
    tx_f = fused_adam(1e-2, weight_decay=0.05, adamw_mode=False)
    s_ref, s_f = tx_ref.init(params), tx_f.init(params)
    p_ref, p_f = params, params
    for _ in range(3):
        u_ref, s_ref = tx_ref.update(grads, s_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, u_ref)
        u_f, s_f = tx_f.update(grads, s_f, p_f)
        p_f = optax.apply_updates(p_f, u_f)
    np.testing.assert_allclose(np.asarray(p_f["w"]), np.asarray(p_ref["w"]),
                               atol=1e-6, rtol=1e-5)


def test_fused_adam_matches_optax():
    import optax
    from deepspeed_tpu.ops.pallas.fused_optimizers import fused_adam
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (70, 33)),
              "b": jnp.zeros((5,))}
    grads = {"w": jax.random.normal(jax.random.PRNGKey(1), (70, 33)),
             "b": jnp.ones((5,))}
    tx_ref = optax.adamw(1e-2, weight_decay=0.01)
    tx_fused = fused_adam(1e-2, weight_decay=0.01)
    s_ref = tx_ref.init(params)
    s_f = tx_fused.init(params)
    p_ref, p_f = params, params
    for _ in range(3):
        u_ref, s_ref = tx_ref.update(grads, s_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, u_ref)
        u_f, s_f = tx_fused.update(grads, s_f, p_f)
        p_f = optax.apply_updates(p_f, u_f)
    for kk in ("w", "b"):
        np.testing.assert_allclose(np.asarray(p_f[kk]), np.asarray(p_ref[kk]),
                                   atol=1e-6, rtol=1e-5)


def test_fused_lion_matches_optax():
    import optax
    from deepspeed_tpu.ops.pallas.fused_optimizers import fused_lion
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (40, 17))}
    grads = {"w": jax.random.normal(jax.random.PRNGKey(1), (40, 17))}
    tx_ref = optax.lion(1e-2, weight_decay=0.05)
    tx_fused = fused_lion(1e-2, weight_decay=0.05)
    s_ref, s_f = tx_ref.init(params), tx_fused.init(params)
    p_ref, p_f = params, params
    for _ in range(3):
        u_ref, s_ref = tx_ref.update(grads, s_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, u_ref)
        u_f, s_f = tx_fused.update(grads, s_f, p_f)
        p_f = optax.apply_updates(p_f, u_f)
    np.testing.assert_allclose(np.asarray(p_f["w"]), np.asarray(p_ref["w"]),
                               atol=1e-6, rtol=1e-5)


def test_int8_quant_roundtrip():
    from deepspeed_tpu.ops.pallas.quantization import (dequantize_int8,
                                                       quantize_int8)
    x = jax.random.normal(jax.random.PRNGKey(0), (300, 70)) * 3.0
    q, s, meta = quantize_int8(x)
    back = dequantize_int8(q, s, meta)
    assert back.shape == x.shape
    err = np.abs(np.asarray(back) - np.asarray(x))
    amax = float(jnp.max(jnp.abs(x)))
    assert err.max() <= amax / 127.0 + 1e-6


def test_pallas_norms_match_reference():
    from deepspeed_tpu.ops import layers as L
    from deepspeed_tpu.ops.pallas import norms
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 128))
    s = jax.random.normal(jax.random.PRNGKey(1), (128,)) + 1.0
    b = jax.random.normal(jax.random.PRNGKey(2), (128,))
    np.testing.assert_allclose(np.asarray(norms.rms_norm(x, s)),
                               np.asarray(L.rms_norm(x, s)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(norms.layer_norm(x, s, b)),
                               np.asarray(L.layer_norm(x, s, b)), atol=1e-6)
    # grads flow through the custom vjp
    g = jax.grad(lambda x: jnp.sum(norms.rms_norm(x, s) ** 2))(x)
    g_ref = jax.grad(lambda x: jnp.sum(L.rms_norm(x, s) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-5)


def test_flash_attention_sliding_window():
    """Windowed flash (Mistral SWA; reference masks via layout) matches
    the exact masked form, forward and gradients — the kernel skips
    blocks fully outside the band instead of masking O(S^2)."""
    from deepspeed_tpu.ops.layers import dot_product_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    key = jax.random.PRNGKey(0)
    for s, w in [(256, 64), (256, 16), (384, 100), (128, 200)]:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (2, s, 4, 64), jnp.float32)
        k = jax.random.normal(ks[1], (2, s, 4, 64), jnp.float32)
        v = jax.random.normal(ks[2], (2, s, 4, 64), jnp.float32)
        qi = jnp.arange(s)[:, None]
        ki = jnp.arange(s)[None, :]
        bias = jnp.where(qi - ki < w, 0.0, -1e30)[None, None]
        ref = dot_product_attention(q, k, v, causal=True, bias=bias)
        out = flash_attention(q, k, v, causal=True, window=w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        g1 = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, window=w) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda q, k, v: jnp.sum(dot_product_attention(
            q, k, v, causal=True, bias=bias) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)


def test_mistral_sliding_window_uses_flash():
    """Mistral's sliding_window rides the flash kernel (no O(S^2) masked
    fallback) and matches the reference attention implementation."""
    from deepspeed_tpu.models import Mistral

    m_flash = Mistral(size="tiny", sliding_window=16, attn_impl="flash",
                      max_seq_len=128)
    m_ref = Mistral(size="tiny", sliding_window=16,
                    attn_impl="reference", max_seq_len=128)
    p = m_flash.init(jax.random.PRNGKey(0))
    t = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                           m_flash.config.vocab_size)
    np.testing.assert_allclose(np.asarray(m_flash.apply(p, t)),
                               np.asarray(m_ref.apply(p, t)),
                               atol=2e-5, rtol=2e-5)

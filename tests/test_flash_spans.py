"""The flash kernels past their residency cap (ISSUE 64): a (batch x head)
row too long to hold runs as equal spans through the SAME forward and
backward kernels (``ops/pallas/flash_attention.py`` ``_spans_fwd``,
``_spans_bwd``), here in interpret mode with the span handed to the inner
function directly (2 and 3 spans, a value narrower than the key, 1 and 4
query heads a key head, causal and not, and with ``rotary`` through the
public function) against ``dot_product_attention`` for ``o``, ``dq``,
``dk``, ``dv``; what the cap admits; the calls, the scope and the gauges
of a row in spans; and that a call under the cap is built as before. Every
call is jitted, one program a case. A CPU run shows results and counts,
never a time."""

import collections
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.models.transformer import _remat_policy
from deepspeed_tpu.ops import layers as L

from helpers.families import _telemetry_isolation  # noqa: F401
from helpers.families import _err, kernel_calls

F = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")


def _qkv(s, hq, rep, d, dv, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape), dtype)
    return (draw(1, s, hq, d), draw(1, s, hq // rep, d),
            draw(1, s, hq // rep, dv), draw(1, s, hq, dv))


def _in_spans(span, causal, rep):
    """``_flash`` with the span handed over, in the model's layout."""
    def attn(q, k, v):
        b, s, hq, _ = q.shape
        to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(  # noqa: E731
            -1, s, x.shape[-1])
        o = F._flash(to_bh(q), to_bh(k), to_bh(v), causal, None, rep, span)
        return o.reshape(b, hq, s, -1).transpose(0, 2, 1, 3)
    return attn


def _value_and_grads(attn, q, k, v, w):
    return jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w),
        argnums=(0, 1, 2)))(q, k, v)


# ---- the spans against plain attention -------------------------------------
@pytest.mark.parametrize("n, d, dv, rep, causal", [
    (2, 48, 32, 1, True), (3, 48, 32, 4, True), (2, 48, 32, 4, False),
    (3, 32, 32, 1, False), (2, 24, 16, 2, True)],
    ids=["2_spans", "3_spans_gqa4", "2_spans_gqa4_full", "3_spans_full",
         "2_spans_gqa2_narrow"])
def test_a_row_in_spans_is_plain_attention_forward_and_backward(
        n, d, dv, rep, causal):
    """``o`` and the three cotangents of a row of ``n`` spans of 256 (two
    blocks of 128 a span, so a diagonal pair holds a masked and an
    unmasked tile) to 1e-5 of ``dot_product_attention``'s in float32:
    the merge by log-sum-exp is exact, and a pair's backward against the
    row's own statistics adds up."""
    s = 256 * n
    q, k, v, w = _qkv(s, 4, rep, d, dv)
    got = _value_and_grads(_in_spans(256, causal, rep), q, k, v, w)
    want = _value_and_grads(
        lambda q, k, v: L.dot_product_attention(q, k, v, causal=causal),
        q, k, v, w)
    for name, a, b in zip(("o", "dq", "dk", "dv"), jax.tree.leaves(got),
                          jax.tree.leaves(want)):
        assert a.shape == b.shape and _err(a, b) < 1e-5, name


def test_the_public_call_past_the_cap_rotates_and_runs_in_spans(monkeypatch):
    """``flash_attention`` with ``rotary`` tables at a lane-aligned head,
    its dispatch told that a row of 512 is two spans: the rotation rides
    the relayout (``ops/pallas/rope.py``) and the rotated q and k run in
    spans, bf16, against the XLA rotation and plain attention; the gauge
    says 2."""
    telemetry.configure()
    monkeypatch.setattr(F, "segments", lambda s, d, dv=None, itemsize=2: 2)
    q, k, v, w = _qkv(512, 4, 2, 128, 128, dtype=jnp.bfloat16)
    tables = L.rotary_tables(*L.rotary_embedding(512, 128, 1e4), 128)
    assert tables.wide is not None
    got = _value_and_grads(
        lambda q, k, v: F.flash_attention(q, k, v, causal=True,
                                          rotary=tables), q, k, v, w)

    def plain(q, k, v):
        q, k = L.rotate(q, k, tables)
        return L.dot_product_attention(q, k, v, causal=True)

    want = _value_and_grads(plain, q, k, v, w)
    for name, a, b in zip(("o", "dq", "dk", "dv"), jax.tree.leaves(got),
                          jax.tree.leaves(want)):
        assert _err(a.astype(jnp.float32), b.astype(jnp.float32)) < 2e-2, name
    reg = telemetry.get_registry()
    assert reg.get("ds_flash_segments").value(s="512", d="128") == 2


def test_a_window_past_the_cap_keeps_the_exact_form(monkeypatch):
    """The one call left to ``dot_product_attention``: no configuration has
    a window layer past the cap, and the docstring says so."""
    monkeypatch.setattr(F, "segments", lambda s, d, dv=None, itemsize=2: 2)
    q, k, v, _ = _qkv(512, 2, 1, 32, 32)
    calls = kernel_calls(lambda q, k, v: F.flash_attention(
        q, k, v, causal=True, window=200), q, k, v)
    assert not calls
    got = jax.jit(lambda q, k, v: F.flash_attention(
        q, k, v, causal=True, window=200))(q, k, v)
    want = L.dot_product_attention(q, k, v, causal=True,
                                   bias=L.window_bias(512, 200))
    assert _err(got, want) < 1e-6
    assert "WINDOW past the cap" in F.flash_attention.__doc__


# ---- what a row in spans is built of ---------------------------------------
@pytest.mark.parametrize("n, causal, pairs", [(1, True, 1), (2, True, 3),
                                              (3, True, 6), (2, False, 4)])
def test_a_row_in_spans_calls_the_two_kernels_a_pair_and_keeps_its_output(
        n, causal, pairs):
    """One forward and one backward kernel a pair of spans the mask leaves
    (3 of 4 at two causal spans), nothing else that is a kernel; under a
    whole-layer checkpoint of the cells' policy the forward is NOT rerun
    (the ``custom_vjp`` keeps ``o`` and the row's statistics); one span is
    the row held whole: the parent's one call each way."""
    s = 256 * n
    q, k, v, w = _qkv(s, 2, 1, 32, 32)
    attn = _in_spans(None if n == 1 else 256, causal, 1)
    layer = jax.checkpoint(lambda q, k, v: jnp.sum(attn(q, k, v) * w),
                           policy=_remat_policy("nothing_saveable"))
    calls = kernel_calls(jax.grad(layer, argnums=(0, 1, 2)), q, k, v)
    assert calls == collections.Counter(ds_flash_fwd=pairs,
                                        ds_flash_bwd=pairs)


def test_the_merge_lies_under_its_scope_and_a_held_row_has_none():
    """Everything outside the kernels that spans cost carries
    ``ds.flash_merge`` in the lowered step; a row under the cap lowers
    with no such scope, to one forward and one backward call."""
    q, k, v, w = _qkv(512, 2, 1, 32, 32)

    def lowered(span):
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            _in_spans(span, True, 1)(q, k, v) * w), argnums=(0, 1, 2))
        ).lower(q, k, v).as_text(debug_info=True)

    spans, held = lowered(256), lowered(None)
    assert "ds.flash_merge" in spans and "ds.flash_merge" not in held
    for text in (spans, held):
        assert "ds.flash_fwd" in text and "ds.flash_bwd" in text
    # the public call under the cap asks for no spans
    assert F.segments(512, 32, 32, 4) == 1
    public = jax.jit(jax.grad(lambda q, k, v: jnp.sum(F.flash_attention(
        q, k, v, causal=True) * w), argnums=(0, 1, 2))).lower(
            q, k, v).as_text(debug_info=True)
    assert "ds.flash_merge" not in public


def test_the_gauges_count_what_the_pairs_of_spans_sweep():
    """``ds_flash_tiles`` / ``ds_flash_pairs`` of a row in spans are the
    sums over its pairs, and at equal blocks they are what ONE long row's
    sweep would count: the spans sweep nothing more."""
    telemetry.configure()
    q, k, v, w = _qkv(1024, 2, 1, 32, 32)
    _value_and_grads(_in_spans(512, True, 1), q, k, v, w)
    reg = telemetry.get_registry()
    tiles, pairs = reg.get("ds_flash_tiles"), reg.get("ds_flash_pairs")
    whole = F.tile_counts(1024, 512, None, True)
    for kernel in ("fwd", "bwd"):
        assert {kind: tiles.value(kernel=kernel, kind=kind)
                for kind in F.TILE_KINDS} == whole == {
                    "masked": 2, "unmasked": 1, "skipped": 0}
        assert pairs.value(kernel=kernel, kind="live") == 1024 * 1025 // 2
        assert pairs.value(kernel=kernel, kind="swept") == 3 * 512 * 512
    assert F.span_counts(lambda n, c: F.pair_counts(n, 512, None, c),
                         32768, 16384, True) == F.pair_counts(
                             32768, 512, None, True)
    assert F.span_counts(lambda n, c: F.tile_counts(n, 512, None, c),
                         1536, 512, False) == F.tile_counts(
                             1536, 512, None, False)


# ---- the cap ---------------------------------------------------------------
def test_the_cap_is_the_bytes_the_backward_holds_and_the_spans_are_equal():
    """``_resident_max_seq`` from the lanes a head is PADDED to (a head of
    64 costs a head of 128's VMEM: the old rule admitted 65536 x 64, which
    asks 132 of 128 MiB), under what compiles for a described v5e
    (``tests/test_zero_layout.py`` compiles AT these caps) and over every
    accepted cell's rows; the spans are the fewest equal ones of whole
    128-row blocks."""
    cap = F._resident_max_seq
    assert (cap(64), cap(128), cap(192, 128), cap(256)) == (
        47662, 47662, 27594, 24197)
    assert cap(64) < 57344 and cap(192, 128) < 31744 and cap(256) < 28672
    assert cap(128, itemsize=4) < cap(128)
    # the accepted cells' longest rows: one span, as before
    for s, d, dv in ((8192, 128, 128), (16384, 128, 128), (16384, 192, 128),
                     (16384, 256, 256), (8192, 64, 64), (8192, 192, 128)):
        assert F.segments(s, d, dv) == 1
    assert F.segments(32768, 192, 128) == 2
    assert F.segments(65536, 64) == 2 and F.segments(65536, 192, 128) == 4
    assert F.segments(131072, 192, 128) == 8    # 5 would not divide
    assert F.segments(128 * 431, 128) == 431    # a prime count of blocks

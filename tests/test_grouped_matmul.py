"""The held experts' grouped-matmul kernel pair and the add to tokens
(``ops/pallas/grouped_matmul.py``, PRs 41 and 48) under
``moe.sharded_moe.held_experts_ffn`` against the ``jax.numpy`` block loop
they replaced (``tests/helpers/held_reference.py``): interpret mode,
jitted, tiny widths that keep the lane rule. The row tile is a quarter of
the block here and a chunk four blocks' rows (a block an expert held, what ``held_experts_ffn`` takes
where it is told none; one case runs other chunks), so that a run ends
in a part-empty tile and crosses chunks as at the cells' sizes; the add
takes tiles of 32 tokens and 32 rows, so that a chunk's rows meet several
tiles of the carry and a tile of it several row tiles.
``tests/test_kimi_linear.py`` and ``tests/test_mellum.py`` hold the same function to a dense sum over
experts; its compile for the chip is in ``tests/test_zero_layout.py``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.sharded_moe import held_chunk, held_experts_ffn
from deepspeed_tpu.ops.pallas import grouped_matmul

from helpers import held_reference  # noqa: E402  (tests/helpers)

TOKENS, TOP_K, EXPERTS, HELD, BLOCK, TILE = 320, 2, 8, 4, 64, 16


@pytest.fixture(autouse=True)
def _small_tiles(monkeypatch):
    monkeypatch.setattr(grouped_matmul, "row_tile", lambda block: TILE)
    monkeypatch.setattr(grouped_matmul, "ADD_TILE", 32)


def _routing(load: str, rng):
    """idx [TOKENS, TOP_K] over EXPERTS, of which the first HELD are held."""
    away = lambda n: HELD + np.stack([  # noqa: E731
        rng.permutation(EXPERTS - HELD)[:TOP_K] for _ in range(n)])
    idx = np.stack([rng.permutation(EXPERTS)[:TOP_K]
                    for _ in range(TOKENS)])
    if load == "one_expert":        # 320 rows: five blocks, two chunks
        idx = away(TOKENS)
        idx[:, 0] = 1
    elif load == "an_expert_with_none":
        idx = np.where(idx == 2, EXPERTS - 1 - (idx[:, ::-1] == EXPERTS - 1),
                       idx)
    elif load in ("one_over_a_tile", "one_under_a_tile"):
        idx = away(TOKENS)
        idx[:TILE + (1 if load == "one_over_a_tile" else -1), 1] = 3
        idx[BLOCK:2 * BLOCK + 2 * TILE, 0] = 0      # a block and two tiles
    elif load == "absent_only":
        idx = away(TOKENS)
    assert all(len(set(row)) == TOP_K for row in idx), load
    return idx.astype(np.int32)


def _inputs(load, d, f, dtype, seed=0):
    rng = np.random.default_rng(seed)
    normal = lambda *s: jnp.asarray(  # noqa: E731
        rng.standard_normal(s, dtype=np.float32), dtype)
    experts = {"w_gate": normal(HELD, d, f) / d ** 0.5,
               "w_up": normal(HELD, d, f) / d ** 0.5,
               "w_down": normal(HELD, f, d) / f ** 0.5}
    return (normal(TOKENS, d), jnp.asarray(_routing(load, rng)),
            jnp.asarray(rng.uniform(0.1, 1.0, (TOKENS, TOP_K)), jnp.float32),
            experts, normal(TOKENS, d))


def _all(fn, *static):
    """(out, rows computed, dx, d weights, the three dW) in one program;
    behind them the sweep's own count, where ``fn`` returns one."""
    def run(x, idx, w, experts, ct):
        (out, counts), back = jax.vjp(
            lambda x, w, e: fn(x, idx, w, e, 0, BLOCK, *static),
            x, w, experts)
        dx, dw, de = back((ct, jax.tree.map(
            lambda c: np.zeros(c.shape, jax.dtypes.float0), counts)))
        swept = dict(counts) if isinstance(counts, dict) else {"done": counts}
        return (out, swept.pop("done"), dx, dw, de["w_gate"], de["w_up"],
                de["w_down"], swept)
    return jax.jit(run)


@functools.cache
def _loop(load, width):
    """The block loop's results on ``_inputs(load, 128, width, float32)``:
    one program a (load, width) a file, whoever asks."""
    return _all(held_reference.held_experts_ffn)(
        *_inputs(load, 128, width, jnp.float32))


def _err(got, want):
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


NAMES = ("out", "done", "dx", "dweights", "dw_gate", "dw_up", "dw_down")
LOADS = ("balanced", "one_expert", "an_expert_with_none",
         "one_over_a_tile", "one_under_a_tile", "absent_only")


@pytest.mark.parametrize("width", [384, 256], ids=["f384", "f256"])
@pytest.mark.parametrize("load", LOADS)
def test_kernel_pair_matches_the_block_loop(load, width):
    """Forward and every gradient (x, the three weights, the routing
    weights) at float32, where both forms are exact to rounding; ``done``
    is the rows routed to the held experts, whatever the skew."""
    args = _inputs(load, 128, width, jnp.float32)
    got = _all(held_experts_ffn, True)(*args)
    want = _loop(load, width)
    rows = int(np.sum(np.asarray(args[1]) < HELD))
    assert int(got[1]) == int(want[1]) == rows
    for name, g, w in zip(NAMES, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert _err(g, w) < 2e-5, (name, _err(g, w))
    if load == "absent_only":
        assert not any(np.asarray(g).any() for g in got[:7]), load


@pytest.mark.parametrize("width", [384, 256], ids=["f384", "f256"])
@pytest.mark.parametrize("load", LOADS)
def test_add_rows_matches_a_scatter_add(load, width):
    """``ds_moe_add_rows`` alone, float32 against ``.at[tokens].add``: the
    load's rows for the held experts (a token holds 0 to ``TOP_K`` of
    them) in token order, cut into two chunks whose last tiles are empty
    (the second wholly, under ``absent_only`` both): the first adds to a
    carry it is told is zeros (and does not read: it holds NaNs here
    where a row of that chunk will land), the second to what the first
    left."""
    rng = np.random.default_rng(1)
    held = np.flatnonzero(_routing(load, rng).reshape(-1) < HELD)
    tokens = (held // TOP_K).astype(np.int32)
    if load == "balanced":      # a token with every choice held, one with none
        held_of = np.bincount(tokens, minlength=TOKENS)
        assert held_of.max() == TOP_K and held_of.min() == 0
    cut, c = len(held) * 2 // 3, -(-len(held) // 32) * 32 + 64
    rows = rng.standard_normal((len(held), width), dtype=np.float32)
    want = np.zeros((TOKENS, width), np.float32)
    np.add.at(want, tokens, rows)
    pad = lambda v, fill: np.concatenate(  # noqa: E731
        [v, np.full((c - len(v), *v.shape[1:]), fill, v.dtype)])
    add = jax.jit(grouped_matmul.add_rows)
    acc = jnp.zeros(want.shape).at[tokens[:cut]].set(jnp.nan)
    for fresh, part in ((True, slice(0, cut)), (False, slice(cut, None))):
        acc = add(acc, pad(rows[part], 7.0), pad(tokens[part], TOKENS),
                  fresh)
    assert acc.shape == want.shape and acc.dtype == jnp.float32
    assert _err(acc, want) < 1e-6, _err(acc, want)


@pytest.mark.parametrize("chunk", [TILE, 3 * TILE + 1, 24 * TILE])
@pytest.mark.parametrize("load", ["balanced", "one_expert"])
def test_any_chunk_gives_what_the_loop_gives(load, chunk):
    """The rows a chunk are the sweep's own business: one tile a chunk
    (every run crosses chunks), four (a chunk opens inside a run and
    holds several experts; the rows asked for round up to whole tiles),
    24 (one chunk, part empty, whatever the load)."""
    args = _inputs(load, 128, 256, jnp.float32)
    got = _all(held_experts_ffn, True, chunk)(*args)
    want = _loop(load, 256)
    assert int(got[1]) == int(want[1])
    for name, g, w in zip(NAMES, got, want):
        assert _err(g, w) < 2e-5, (name, _err(g, w))


# ---- the sweep counts itself (ISSUE 68) ------------------------------------
# rows a chunk: the shape's own (what a balanced router sends the four held
# experts and a tile each: 24 tiles), and half the one expert's 20 tiles
CHUNK = 24 * TILE
SWEEPS = {"balanced": (CHUNK, 1), "an_expert_with_none": (CHUNK, 1),
          "one_expert": (12 * TILE, 2)}


def _forward(load, chunk):
    """(out, the sweep's counts as ints, the routing) of the forward."""
    x, idx, w, experts, _ = _inputs(load, 128, 128, jnp.float32)
    out, counts = jax.jit(lambda x, w, e: held_experts_ffn(
        x, idx, w, e, 0, BLOCK, True, chunk))(x, w, experts)
    return (np.asarray(out), {k: int(v) for k, v in counts.items()},
            np.asarray(idx))


@pytest.mark.parametrize("load", sorted(SWEEPS))
def test_the_sweep_counts_its_trips_and_its_tiles(load):
    """``trips``, ``tiles`` and ``swept`` of the forward sweep: a balanced
    load and one with an expert that is sent nothing fit the shape's chunk
    (one trip, and the chunk's tiles past the live ones are swept and not
    live: no tile of the absent expert among the live); a share sent more
    than a chunk holds takes a second trip and gives what one trip of a
    chunk that holds it all gives."""
    assert held_chunk(TOKENS, TOP_K, EXPERTS, HELD, BLOCK) == CHUNK
    chunk, trips = SWEEPS[load]
    out, got, idx = _forward(load, chunk)
    sent = np.bincount(idx.ravel(), minlength=EXPERTS)[:HELD]
    live = int(np.sum(-(-sent // TILE)))
    assert got == {"done": int(sent.sum()), "trips": trips, "tiles": live,
                   "swept": trips * chunk // TILE, "tile": TILE}
    assert got["tiles"] <= got["swept"]
    if load == "an_expert_with_none":
        assert sent[2] == 0 and got["tiles"] < got["swept"]
    if trips == 2:
        assert chunk // TILE < live <= 2 * chunk // TILE
        whole, once, _ = _forward(load, CHUNK)
        assert once["trips"] == 1 and once["tiles"] == live
        assert _err(out, whole) < 1e-6


def test_the_trips_returned_are_the_bound_the_loop_is_given(monkeypatch):
    """One expression, not two: the ``trips`` the forward hands back IS
    the value ``lax.fori_loop`` got as its upper bound."""
    x, idx, w, experts, _ = _inputs("balanced", 128, 128, jnp.float32)
    bounds, loop = [], sharded_moe.lax.fori_loop
    monkeypatch.setattr(sharded_moe.lax, "fori_loop", lambda lo, hi, *a: (
        bounds.append(hi), loop(lo, hi, *a))[1])

    def probe(x, w, e):
        out, counts = sharded_moe._held_forward(x, idx, w, e, 0, BLOCK,
                                                CHUNK, "swiglu")
        assert any(hi is counts["trips"] for hi in bounds)
        assert counts["swept"].dtype == counts["trips"].dtype == jnp.int32
        return out

    jax.eval_shape(probe, x, w, experts)
    assert bounds


def test_the_chunk_rule_by_shape(monkeypatch):
    """A chunk is what a balanced router sends the held experts and a
    row tile an expert: any split of that total fits."""
    monkeypatch.undo()      # the kernels' own row tile: 256 in both cells
    assert held_chunk(16384, 8, 64, 16, 768) == 32768 + 16 * 256 == 36864
    assert held_chunk(16384, 8, 256, 8, 1024) == 4096 + 8 * 256 == 6144
    assert held_chunk(256, 8, 256, 8, 128) == 64 + 8 * 128
    rng = np.random.default_rng(0)
    for _ in range(50):     # any split of the even total, in whole tiles
        cuts = np.sort(rng.integers(0, 32769, 15))
        loads = np.diff(np.concatenate([[0], cuts, [32768]]))
        assert np.sum(-(-loads // 256)) * 256 <= 36864


def test_bf16_rounds_no_worse_than_the_loop():
    """At bf16 the kernels keep ``gate``, ``up`` and the sums in float32
    where the loop rounds each matmul's result: against the float32 loop
    the kernels' error is within the bf16 loop's own (times 1.5 of room)."""
    args32 = _inputs("balanced", 128, 384, jnp.float32)
    cast = lambda t: jax.tree.map(  # noqa: E731
        lambda v: v.astype(jnp.bfloat16) if v.dtype == jnp.float32
        and v.ndim > 1 and v.shape != (TOKENS, TOP_K) else v, t)
    args16 = cast(args32)
    exact = _all(held_reference.held_experts_ffn)(*args32)
    loop = _all(held_reference.held_experts_ffn)(*args16)
    got = _all(held_experts_ffn, True)(*args16)
    for name, e, l, g in zip(NAMES, exact, loop, got):
        if name != "done":
            assert g.dtype == l.dtype, name
            assert _err(g, e) < max(1.5 * _err(l, e), 1e-3), (
                name, _err(g, e), _err(l, e))


def test_without_router_grad_the_weights_get_zeros_and_the_rest_stays():
    args = _inputs("balanced", 128, 256, jnp.float32)
    on = _all(held_experts_ffn, True)(*args)
    off = _all(held_experts_ffn, False)(*args)
    for name, a, b in zip(NAMES, on, off):
        if name == "dweights":
            assert not np.asarray(b).any()
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)


def test_tile_tables_name_one_expert_a_tile_and_skip_the_dead():
    """Chunks of four tiles of 16 rows: expert 2 with 81 rows (six tiles,
    the last of one row), expert 3 with 5, then nothing: a dead tile
    names the last live tile of ITS chunk; ``init`` zeroes an expert's dW
    at its first tile and takes it from the carry where a chunk opens
    inside a run."""
    expert = jnp.asarray([2] * 6 + [3] + [0] * 5, jnp.int32)
    live = jnp.asarray([16] * 5 + [1, 5] + [0] * 5, jnp.int32)
    begun = jnp.asarray([False] + [True] * 5 + [False] * 6)
    of, src, rows, init = (np.asarray(t) for t in grouped_matmul.tile_tables(
        expert, live, begun, 4))
    assert rows.tolist() == [16] * 5 + [1, 5] + [0] * 5
    assert src.tolist() == [0, 1, 2, 3] + [0, 1, 2, 2] + [0] * 4
    assert of.tolist() == [2] * 6 + [3, 3] + [0] * 4
    assert init.tolist() == [1, 0, 0, 0] + [2, 0, 1, 0] + [0] * 4


@pytest.mark.parametrize("block, tile", [(768, 256), (1024, 256), (128, 128),
                                         (256, 256), (16, 16), (640, 128)])
def test_row_tile_divides_the_block(block, tile, monkeypatch):
    monkeypatch.undo()
    assert grouped_matmul.row_tile(block) == tile and block % tile == 0


# ---- the backward's hold of one expert (ISSUE 56) --------------------------
@pytest.mark.parametrize("d, f, tile, want", [
    (2304, 1024, 256, (1, 2, 84934656)),    # Kimi: whole, two buffers
    (2304, 896, 256, (1, 2, 74317824)),     # Mellum
    (2048, 512, 128, (1, 2, 37748736)),     # Qwen3-Next
    (2048, 1536, 128, (1, 1, 94371840)),    # LFM2: the weights take one
    (3584, 1024, 256, (2, 2, 66060288)),    # Xing4: two column runs
], ids=["kimi", "mellum", "qnext", "lfm", "xing"])
def test_backward_geometry_by_shape(d, f, tile, want):
    """(column runs, the weights' buffers, bytes resident) of
    ``grouped_matmul.backward`` from the shapes alone: the four shapes the
    accepted cells run hold an expert whole, exactly as before the column
    cut was written (one grid axis, the same blocks and buffering: the
    rows of ``tests/test_step_pins.py`` hold their lowered steps); hidden
    3584 by 1024 is cut in two, each half at two buffers."""
    assert grouped_matmul.backward_geometry(d, f, tile, 2) == want


@pytest.mark.parametrize("load", ["balanced", "one_expert",
                                  "an_expert_with_none", "absent_only"])
@pytest.mark.parametrize("router_grad", [True, False], ids=["rg", "norg"])
def test_the_column_cut_gives_what_the_whole_expert_gives(
        load, router_grad, monkeypatch):
    """With ``_VMEM_MAX`` lowered until an expert of 128 by 512 is cut
    into four column runs (a second grid axis in front of the row tiles,
    ``dW`` blocks by columns, ``dx`` and the row's ``dy . y`` summed over
    the runs by the caller), every gradient is the block loop's; several
    chunks, so that a carried ``dW`` is copied in by columns too."""
    from deepspeed_tpu.ops.pallas import _common
    args = _inputs(load, 128, 512, jnp.float32)
    want = _loop(load, 512)
    monkeypatch.setattr(grouped_matmul, "_VMEM_MAX", 17 << 20)
    assert grouped_matmul.backward_geometry(128, 512, TILE, 4)[0] == 4
    _common._TRACED.clear()
    try:
        got = _all(held_experts_ffn, router_grad, 3 * TILE + 1)(*args)
    finally:
        _common._TRACED.clear()
    for name, g, w in zip(NAMES, got, want):
        if name == "dweights" and not router_grad:
            assert not np.asarray(g).any()
            continue
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert _err(g, w) < 2e-5, (name, _err(g, w))

"""Every stored hash of a lowered program (ISSUE 50): the train steps of the
six families the benchmark's configurations run, at the switches their cells
run them with, and the chunked head every cell's loss goes through. A PR
that edits code on another family's path shows here that the other
families' steps are still the parents' programs; a PR that changes a
program BY DESIGN re-takes its pins here and nowhere else, and says in the
table's comment which and why. A pin is a row of ``_PINS``, never a test of
a family's own file. A CPU run shows results and counts, never a time."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import _chunked_cross_entropy
from deepspeed_tpu.ops.pallas import _common

from helpers.families import _telemetry_isolation  # noqa: F401
from helpers.families import program

# row -> (the sha256 of the lowered train step with its symbols renumbered,
# the sum of the seeded master weights' magnitudes). The class and the
# switches over the tiny preset are the row's of ``tests/helpers/families.py``
# (cell's switches, step's switches, ``two_layers`` cut: here until PR 58).
# Two layers of a stack where two hold every kind of
# layer the family has (a routed layer behind a KDA and an MLA mixer; a Mamba
# and an attention layer; a window and a full attention layer; a Gated
# DeltaNet and a gated attention layer); ``mellum`` also at the preset's own
# four, ``mistral`` at both remat policies (``segments`` is what the Mistral
# cells run: attention outside every ``jax.checkpoint``). Rows and switches
# are those that ``tests/test_mellum.py``, ``tests/test_ouro.py`` and
# ``tests/test_qwen3_next_engine.py`` held until PR 50, each once, and
# ``qwen3_next``, which no file held. Every number was taken on PR 50's
# parent (commit d65b177, this file's ``_step_pin`` run on that checkout),
# where each was first shown equal under the form its old file kept
# (``CHANGES.md``, PR 50). The sums are the CPU's arithmetic as
# ``tests/conftest.py`` has it compiled (LLVM's optimizer off since PR 50:
# the init's last bits differ, a part in 1e10 of a sum, and the texts do
# not). The programs are PR 48's for the routed families
# (``kimi_linear``, ``mellum``, ``qwen3_next``: the held sweep's add to tokens
# is the kernel ``ds_moe_add_rows``) and PR 47's for the others (a rematted
# layer keeps the flash kernel's ``o`` and ``lse``). PR 51 re-took
# ``kimi_linear`` by design (its KDA layers run in two head groups here, four
# in its cell: a rematted layer keeps the delta-rule scan's ``o``,
# ``ops/kda.py`` ``chunk_kda``, and the layer's rerun holds no kernel of the
# scan); the seven other rows stood: ``qwen3_next`` calls the op with one
# head group, where nothing is kept, and the five other families never call
# it. PR 53 re-took ``qwen3_next`` by design (its Gated DeltaNet layer hands
# q and k to the scan at their 2 key heads where it repeated them to the 4
# value heads: ``models/qwen3_next.py`` ``_gdn`` holds no ``jnp.repeat``, the
# preparation's kernels read a key head for the value heads it serves and
# sum those heads' ``dq`` and ``dk`` before their one store,
# ``ops/pallas/kda.py``; the seeded weights are the parent's); the seven
# other rows stand: ``kimi_linear`` calls the op with as many key heads as
# value heads, where every slice, block and store is the parent's. PR 54
# added ``lfm2_moe`` (a routed attention layer and a routed conv layer, 8 of
# 64 experts held; taken on its own tree, the first that has the family) and
# moved ``kimi_linear``'s ``after_step`` body into
# ``RoutedStackOfKinds._balanced`` word for word: the eight rows before it
# stand. PR 55 re-took ``kimi_linear`` and ``qwen3_next`` by design (the
# gated norm between the delta-rule scan and the output projection is the
# kernel pair of ``ops/layers.py`` ``gated_norm`` where ``_kda`` and ``_gdn``
# held ``jax.numpy`` expressions; on one device the scan hands ``o`` over as
# the heads' stack, ``chunk_kda(by_head=True)``, on this mesh per shard as
# [B, S, H, d]; ``_kda`` ties the cotangents round the norm, ``_together``;
# the seeded weights are the parent's); the seven other rows stand: no other
# family calls the op, and ``models/stack.py`` hands their ``_mixers`` on as
# they were. PR 56 added ``xing4_0`` (a leading dense layer and a routed one,
# 8 of 64 experts held, both under four hyper-connected streams, at the
# seeded spreads of its cell; taken on its own tree, the first that has the
# family) and cut ``grouped_matmul.backward`` by columns for an expert too
# wide to hold: the nine rows before it stand, their experts are held whole
# and lower to the text they lowered to. PR 59 re-took ``kimi_linear`` and
# ``qwen3_next`` by design (the delta-rule scan's head groups are an offset
# in its kernels' index maps and ONE ``custom_vjp`` spans the grouped scan,
# ``ops/kda.py`` ``_scan``: the four calls take the group's index as a
# scalar-prefetch operand and the whole arrays, at one group too, where the
# offset is 0 of the same calls; Kimi's two groups here are one rolled loop
# over buffers that are not initialised; the ``lax.map``, the per-group
# ``jax.checkpoint`` and ``_kept`` are gone; the seeded weights are the
# parent's); the eight other rows stand: no other family calls the op.
# PR 60 added ``laguna`` (a dense window layer of 6 query heads and a routed
# full layer of 4, over 2 key heads, 8 of 256 experts held beside the shared
# one, the gate a head, half the full layer's head rotated; taken on its own
# tree, the first that has the family), gave ``ops/layers.py``
# ``apply_rotary`` / ``rotary_embedding`` a rotated width narrower than the
# head and moved what ``models/mellum.py`` shares with it (the mixers, the
# tables a kind, the partition rules) into ``models/stack.py``
# ``WindowAndFullAttention``, the blocks' count into
# ``RoutedStackOfKinds._held_blocks``: the eleven rows before it
# stand, ``mellum``'s two among them (the same equations in the same order;
# a whole-head table lowers to the text it lowered to,
# ``tests/test_laguna.py``).
# PR 61 re-took ``mellum``, ``mellum_two_layers``, ``mistral``,
# ``mistral_segments`` and ``laguna`` by design: their tiny presets have a
# window of at most two of the flash kernels' blocks (64 at one block of
# 128 here; 512 and 1024 at blocks of 512 in the Laguna and Mellum cells),
# where a grid step now runs its rows by groups against ONE span under one
# mask and no loop (``ops/pallas/flash_attention.py`` ``band_rows``; the
# seeded weights are the parent's). The seven other rows stand, their
# layers pass no window, and the Mistral CELLS' window of 4096 is eight
# blocks: ``test_the_flash_loops_are_the_parents_program`` below holds the
# loops to the parent's text.
# PR 62 re-took ``qwen3_next`` by design: its attention layer hands the
# WHOLE head to ``ops/layers.py`` ``rotary_attention`` with its table of
# ``rotary_dim`` channels (``apply_rotary`` rotates the leading channels of
# a head wider than its table, PR 60) where ``_attention`` sliced the head,
# rotated the slice and concatenated the rest back: one cast to float32 and
# one rounding a head for two, the same products (the seeded weights are the
# parent's). The eleven other rows stand: every tiny preset's head is
# narrower than 128 lanes, so every family keeps the XLA form, the same
# equations in the same order (``models/laguna.py``, ``models/mellum.py``
# and ``models/lfm2_moe.py`` through ``rotary_attention`` -> ``rotate``,
# ``models/transformer.py`` ``_qkv(rotate=True)`` as it was), and
# ``sharded_flash_attention`` maps the same body with no tables to pass.
# PR 64 added ``deepseek_v3`` (a leading dense layer and a routed one, 16 of
# 128 experts held beside two shared ones as one SwiGLU, both under rotated
# direct-query latent attention; taken on its own tree, the first that has
# the family), moved the latent attention of ``kimi_linear`` and ``xing4_0``
# into ``models/stack.py`` ``LatentAttention`` (the same equations in the
# same order a family: Kimi's unrotated key is cut from the latent where it
# was, Xing4.0's inside ``ds.rope``; the weights are drawn in the order they
# were) and split the flash kernels' backward into the call and what stands
# round it, for a row past the residency cap to run in spans
# (``ops/pallas/flash_attention.py`` ``_spans_fwd`` / ``_spans_bwd``): the
# twelve rows before it stand, every tiny row is held whole and lowers to
# the text it lowered to, and
# ``test_the_flash_loops_are_the_parents_program`` holds the loops.
# PR 66 added ``nemotron_h`` (three layers of ONE sublayer each: a Mamba-2
# mixer at two groups with the gated norm a group, 8 of 512 ``relu2``
# experts held in a latent of 16 beside a shared one, an attention layer
# without positions; taken on its own tree, the first that has the family),
# gave the held experts' kernel pair a static ``body`` (``ops/pallas/
# grouped_matmul.py`` ``BODIES``: ``swiglu`` walks the refs it walked, in
# the order it walked them), ``moe_ffn_held`` a ``latent`` and moved
# ``granite_hybrid``'s Mamba-2 mixer into ``models/stack.py`` ``Mamba2``
# word for word (the norm's group count its argument; the weights are
# drawn in the order they were): the thirteen rows before it stand.
# PR 67 re-took the nine routed rows by design (``kimi_linear``, ``mellum``,
# ``mellum_two_layers``, ``qwen3_next``, ``lfm2_moe``, ``xing4_0``,
# ``laguna``, ``deepseek_v3``, ``nemotron_h``): ``sigmoid_top_k`` and
# ``softmax_top_k`` take experts, scores and load from ONE helper
# (``moe/sharded_moe.py`` ``top_k_of``), so the ``bincount`` that stood at
# the end of ``moe_ffn_held``'s router now stands before the renormalising
# divide. At these rows' 1024 tokens the helper is XLA's form
# (``_top_k_xla``: the same ``top_k``, gather and ``bincount`` equations; the
# kernel pair of ``ops/pallas/router.py`` takes 8192 tokens or more), and
# the seeded weights are the parent's. The four unrouted rows stand.
# PR 68 re-took the same nine routed rows by design: the forward sweep of
# ``held_experts_ffn`` hands back what it counted of itself beside ``done``
# (``_held_sweep``: the trips it gives ``fori_loop``, the tiles with a live
# row, the tiles its trips held, the tile's rows: four int32 scalars a
# routed layer among the layer scan's outputs) and ``_held_metrics`` sums
# them into five more scalars of the step's metrics; the loop, its body and
# the kernels' calls are the parent's, and so are the seeded weights. The
# four unrouted rows stand.
_PINS = {
    "kimi_linear": (
        "027fb5dcc2c76300526f6cecf8fbc67937d83909238d3248b0233a066b5416f6",
        7191.956370612894),
    "granite_hybrid": (
        "784e9ecf5ebfccdeb1b817732e5cef85f8f7725e508030251d429a08f63e80c6",
        2422.8129150247487),
    "mellum": (
        "87c0c543efd670ae616bb342b33997fbbbaf769dcb179651fb55275421294eb2",
        36510.69587289919),
    "mellum_two_layers": (
        "9da51e2e7123428a1f1bed10eafe111d3b414cc499c3590d008d12d452b423fd",
        31755.548628388842),
    "ouro": (
        "9eec4588f2e615a8871cef610ca85b5dc9984269762d3ecda677f84ad4d2f3d0",
        2733.7353564571135),
    "mistral": (
        "a17a78d1f0971400b6af99bf037488344b6902deeb9bc5b8facf00a36cc9d7e4",
        2339.9930016614694),
    "mistral_segments": (
        "ff01f5cdc985b238b6f862877aa11232503e0a0743274f22dddd837917e3a975",
        2339.9930016614694),
    "qwen3_next": (
        "7fa16cedf7b9394482d187c110831d8d660436abce1da0575fb87ddfb332e752",
        39458.17879846059),
    "lfm2_moe": (
        "1ebb4b34e14f36505a25dac5b16a1814b3647500c2b835fc17796cbc0046e334",
        3449.799246064109),
    "xing4_0": (
        "f539519677d3fcd80bd427adc75881dfb317deeb2cb9cd957b6ade13c248d4ba",
        4668.748035160373),
    "laguna": (
        "1be16a64a0138869291ad88377923588b612f3764de9433f4acf3343204bd9db",
        31325.334374967497),
    "deepseek_v3": (
        "6b9d785b58258203200552260e80de1172018f807588ac80210519be82793290",
        4508.550148079469),
    "nemotron_h": (
        "14811766d77433bc05a744f0f38e205292c65395f885d9a30147cbf765e9e9d3",
        5834.368574828769),
}
# the rows that are not a family's two-layer cut under the family's name: (family, cut of its
# layers, further switches)
_OTHER_CUTS = {
    "mellum": ("mellum", "cell", {}),
    "mellum_two_layers": ("mellum", "two_layers", {}),
    "mistral": ("mistral", "cell", {}),
    "mistral_segments": ("mistral", "cell", dict(remat_policy="segments")),
}


def _pin(text: str) -> str:
    """The sha256 of a lowered program's ``text`` with every symbol
    (``@name``) renamed by the order of its first appearance: what is left is
    the program, whatever numbers MLIR's symbol table gave the private
    functions' names (JAX lowers every distinct equation as a private
    function named after its primitive, and one more such function numbers
    every LATER collision higher: PR 47 found eleven names of 3501 lines
    moved and no other character)."""
    assert "loc(" not in text       # no source locations in it
    table = {}
    return hashlib.sha256(re.sub(
        r"@[\w.]+", lambda m: table.setdefault(m.group(0), f"@f{len(table)}"),
        text).encode()).hexdigest()


def _step_pin(row: str):
    # a kernel is traced once a shape and bound from that trace ever after
    # (``ops/pallas/_common.py`` ``_bind``): one that an earlier row traced
    # under its own model would be bound here
    _common._TRACED.clear()
    family, cut, switches = _OTHER_CUTS.get(row, (row, "two_layers", {}))
    step = program(family, cut, **switches)
    tok = np.zeros((8, step.model.config.max_seq_len), np.int32)
    text = step.lower((tok, tok)).as_text()
    leaves = jax.device_get(jax.tree.leaves(step.engine.state["master"]))
    return _pin(text), float(sum(np.abs(x.astype(np.float64)).sum()
                                 for x in leaves))


@pytest.mark.parametrize("row", list(_PINS))
def test_the_families_steps_are_the_parents_programs(row):
    """The lowered train step under ZeRO-3 bf16 on the 8-device mesh is the
    parent's text and the seeded weights the parent's numbers."""
    assert _step_pin(row) == _PINS[row]


def test_the_unweighted_head_is_the_parents_program():
    """``_chunked_cross_entropy`` is on the path of every cell: with no
    weights its lowered text (value and gradient) is the parent's (commit
    9909adf, ISSUE 42, which gave the head its weighted form; the hash of
    the text as it is, as ``tests/test_ouro.py`` kept it until PR 50: one
    jitted function has no private functions to number)."""
    x = jax.ShapeDtypeStruct((2, 128, 64), jnp.bfloat16)
    W = jax.ShapeDtypeStruct((64, 512), jnp.bfloat16)
    t = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = jax.jit(jax.value_and_grad(
        lambda x, W, t: _chunked_cross_entropy(x, W, None, t, 32),
        argnums=(0, 1))).lower(x, W, t).as_text()
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "e4b425dd87402f9216cae4e4bd9f1e349f6e3fb5515488abb2badeecb6e8e10f")


@pytest.mark.parametrize("window,want", [
    (300, "9bd80c841e4c1315ea14a3d4e3763726ae750e9977aacb1e59aff8d5bb7f74a2"),
    (None, "4a81f6ab21af3578b533b5407f1b30b3026c80af3bc35eb927f0c56b16632890"),
])
def test_the_flash_loops_are_the_parents_program(window, want):
    """Past a window of two blocks, and with none, the flash kernels sweep
    by the loops, and the program they trace to (value and the three
    gradients at five 128-blocks, 4 query heads over 2; window 300 meets
    all three ranges) is the one PR 61's parent traced (commit c369513,
    this function run on that checkout): the band of ISSUE 61 is another
    way to call the one body, chosen at trace time, and the cells whose
    window is wider (Mistral's 4096 at block 512) or absent run what they
    ran. The tiny ``mistral`` rows above have a window of 64 at one block
    of 128 and took the band."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    q = jax.ShapeDtypeStruct((1, 640, 4, 32), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 640, 2, 32), jnp.bfloat16)
    text = jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))).lower(q, k, k).as_text()
    assert _pin(text) == want

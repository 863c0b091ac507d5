"""Qwen3-Next (ISSUE 46), the parts under the layers: the delta rule's
chunked form and its kernels with a gate a HEAD against the recurrence
token by token, and the held share of 512 small experts beside the gated
shared expert against the uncut layer (``tests/test_qwen3_next.py`` holds
the model to its reference). A CPU run shows results and counts, never a
time."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.sharded_moe import held_block, moe_ffn_held
from deepspeed_tpu.ops import kda

from helpers.family_cases import _close, _err
from architectures import qwen3_next as arch  # noqa: E402


# ---- the scan with a gate a head -------------------------------------------
def _gdn_inputs(b=2, s=192, hk=2, hv=4, dk=32, dv=16, seed=0, fast=False):
    """q and k at ``hk`` key heads repeated to ``hv`` value heads, as the
    layer hands them in; ``g`` [B, S, hv] from A = U(0, 16) (``fast``:
    every head at A = 16 and a softplus of 5: -80 a token, -5120 a chunk)."""
    rng = np.random.default_rng(seed)
    l2 = lambda x: x / np.sqrt((x ** 2).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    rep = lambda x: np.repeat(x, hv // hk, axis=2)  # noqa: E731
    q = rep(l2(rng.normal(size=(b, s, hk, dk))) / np.sqrt(dk))
    k = rep(l2(rng.normal(size=(b, s, hk, dk))))
    v = rng.normal(size=(b, s, hv, dv))
    a = np.full(hv, 16.0) if fast else rng.uniform(0.01, 16, size=hv)
    soft = np.log1p(np.exp(rng.normal(size=(b, s, hv)) + (5 if fast else 1)))
    g = -a * soft
    if not fast:
        g[..., 0] *= 0.01           # a head that remembers
    beta = 1 / (1 + np.exp(-rng.normal(size=(b, s, hv))))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


@pytest.mark.parametrize("case", ["drawn_decay", "fastest_decay"])
def test_a_gate_a_head_through_the_kernels_is_the_recurrence(case):
    """``chunk_kda`` with ``g`` [B, S, H] against ``recurrent_kda`` token by
    token, forward and the five gradients, float32, at 2 key heads serving
    4 value heads, in one head group and in two. The gate widened by hand
    to the channels is the same recurrence, but NOT the same chunked form:
    its row blocks' factoring loses a near-diagonal ``exp(g_i)`` where the
    rows before it in the block have decayed past the clamp, which a
    drawn decay of this family does (1e-3 of the output where the mask
    gives 1e-7); at one rate for every token both agree."""
    args = _gdn_inputs(fast=case == "fastest_decay")
    q, k, v, g, beta = args
    assert g.shape == beta.shape and float(jnp.min(g)) < (
        -80 if case == "fastest_decay" else -10)
    # jitted: eager, every line round the kernels compiles alone
    recurrent = jax.jit(kda.recurrent_kda)
    chunked = jax.jit(kda.chunk_kda, static_argnames="head_groups")
    want = recurrent(*args)
    wide = jnp.broadcast_to(g[..., None], q.shape)
    _close(recurrent(q, k, v, wide, beta), want, 0, "recurrent")
    got = chunked(*args)
    _close(got, want, 2e-5, "forward")
    np.testing.assert_array_equal(got, chunked(*args, head_groups=2))
    widened = _err(chunked(q, k, v, wide, beta), want)
    assert widened < 2e-5 if case == "fastest_decay" else widened > 1e-3
    cot = jnp.asarray(np.random.default_rng(9).normal(size=want.shape),
                      jnp.float32)
    grad = lambda f: jax.jit(jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * cot), argnums=range(5)))(*args)
    want_g, got_g = grad(kda.recurrent_kda), grad(kda.chunk_kda)
    for name, a, b in zip("q k v g beta".split(), got_g, want_g):
        assert a.shape == b.shape, name
        # at -80 a token nothing is remembered and dg is 1e-16: held to
        # an absolute 1e-9 there, to 2e-4 of its largest otherwise
        scale = max(float(jnp.max(jnp.abs(b))), 5e-6)
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-4 * scale, name


# ---- the held share and the gated shared expert ----------------------------
def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Experts 32 c to 32 c + 31 of one layer of 512, c = 0..15, each share
    run as its chip runs it (the held part through the dispatch), and the
    gated shared expert counted ONCE, sum to the reference's layer with
    every expert held; ``shared_gate=None`` is the ungated shared expert,
    and without a shared expert the gate is not read."""
    E, K, D, F = 512, 10, 16, 8
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 12))
    w = lambda *shape: 0.5 * jax.random.normal(next(ks), shape)  # noqa: E731
    experts = {"w_gate": w(E, D, F), "w_up": w(E, D, F),
               "w_down": w(E, F, D)}
    shared = {"w_gate": w(D, F), "w_up": w(D, F), "w_down": w(F, D)}
    layer = {"router": w(D, E), "experts": experts, "shared": shared,
             "shared_gate": 3.0 * w(D, 1)}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, D))
    xt = x.reshape(-1, D)

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def share(chip, gated, x, layer):
        mine = {n: v[32 * chip:32 * chip + 32]
                for n, v in layer["experts"].items()}
        return moe_ffn_held(
            x, layer["router"], None, mine,
            layer["shared"] if gated is not None else None, k=K,
            first_expert=32 * chip, router="softmax", block=16,
            shared_gate=layer["shared_gate"] if gated else None)

    with jax.default_matmul_precision("highest"):
        whole, _ = arch.experts(layer, xt, top_k=K, renormalise=True)
        routed_only, _ = arch.experts(
            {k: v for k, v in layer.items() if "shared" not in k}, xt,
            top_k=K, renormalise=True)
        total, loads = 0, []
        for chip in range(16):
            out, counts = share(chip, None, x, layer)
            sent = counts["load"][32 * chip:32 * chip + 32]
            assert int(counts["done"]) == int(jnp.sum(sent))
            loads.append(np.asarray(counts["load"]))
            total = total + out.reshape(-1, D)
        assert _err(total, routed_only) < 1e-5
        gated, _ = share(0, True, x, layer)
        plain, _ = share(0, False, x, layer)
        alone, _ = share(0, None, x, layer)
        once = (gated - alone).reshape(-1, D)
        assert _err(total + once, whole) < 1e-5
        assert _err(once, jax.nn.sigmoid(xt @ layer["shared_gate"])
                    * arch._swiglu(shared, xt)) < 1e-5
        assert _err((plain - alone).reshape(-1, D),
                    arch._swiglu(shared, xt)) < 1e-5
    assert all(np.array_equal(loads[0], one) for one in loads)
    assert int(loads[0].sum()) == xt.shape[0] * K


def test_the_block_rule_at_many_small_experts():
    """512 experts, top 10, 16384 tokens: an even load of 320 rows is
    under a block, so the block is a padded capacity of two, 640 rows; the
    Kimi and Mellum cells keep theirs."""
    assert held_block(16384, 10, 512) == 640
    assert held_block(16384, 8, 256) == 1024 and held_block(
        16384, 8, 64) == 768
    assert held_block(8 * 128, 10, 512) == 128      # the tiny engine's step

"""Qwen3-Next (ISSUE 46), the parts under the layers: the delta rule's
chunked form and its kernels with a gate a HEAD against the recurrence
token by token, q and k at their KEY heads (ISSUE 53: the kernels read a
key head where each of its value heads needs it, at either gate), and the
held share of 512 small experts beside the gated shared expert against the
uncut layer (``tests/test_qwen3_next.py`` holds the model to its
reference). A CPU run shows results and counts, never a time."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.moe.sharded_moe import held_block, moe_ffn_held
from deepspeed_tpu.ops import kda

from helpers.families import (_batch, _close, _err,  # noqa: F401
                               _kda_inputs, _telemetry_isolation,
                               _walk_eqns)
from helpers.families import FAMILIES, kernel_calls, tiny
from architectures import qwen3_next as arch  # noqa: E402


# ---- the scan with a gate a head -------------------------------------------
def _gdn_inputs(b=2, s=192, hk=2, hv=4, dk=32, dv=16, seed=0, fast=False):
    """q and k at ``hk`` key heads beside v, g, beta at ``hv`` value heads,
    as the layer hands them in; ``g`` [B, S, hv] from A = U(0, 16)
    (``fast``: every head at A = 16 and a softplus of 5: -80 a token,
    -5120 a chunk)."""
    rng = np.random.default_rng(seed)
    l2 = lambda x: x / np.sqrt((x ** 2).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = l2(rng.normal(size=(b, s, hk, dk))) / np.sqrt(dk)
    k = l2(rng.normal(size=(b, s, hk, dk)))
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
    wide = jnp.broadcast_to(g[..., None], (*g.shape, q.shape[-1]))
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


# ---- q and k at the key heads ----------------------------------------------
def _key_head_inputs(rep, gate, hk=2):
    """q, k at ``hk`` key heads; v, beta and the gate (a head's [B, S, H],
    a channel's [B, S, H, dk]) at ``H = rep hk`` value heads, float32."""
    if gate == "a_head":
        return _gdn_inputs(b=1, s=128, hk=hk, hv=rep * hk, seed=rep)
    args = _kda_inputs(b=1, s=128, h=rep * hk, seed=rep)
    args[:2] = [x[:, :, ::rep] for x in args[:2]]
    return args


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("gate", ["a_head", "a_channel"])
@pytest.mark.parametrize("rep", [2, 4])
def test_a_key_head_is_read_where_its_value_heads_need_it(rep, gate, groups):
    """``chunk_kda`` with q and k at ``Hk`` heads and v, g, beta at ``rep
    Hk`` (``rep`` is read from the shapes): against ``recurrent_kda`` on
    the repeated heads, forward and the five gradients, ``dq`` and ``dk``
    in the KEY heads' shape; the forward bit-equal to the parent's form
    (``jnp.repeat``, then the op at equal head counts). Two key heads in
    one head group and in two: a group holds whole key heads."""
    args = _key_head_inputs(rep, gate)
    cot = jnp.asarray(np.random.default_rng(9).normal(
        size=args[2].shape), jnp.float32)

    def both(f):
        total = lambda *a: (lambda o: (jnp.sum(o * cot), o))(f(*a))  # noqa: E731
        return jax.jit(jax.value_and_grad(
            total, argnums=range(5), has_aux=True))(*args)

    scan = functools.partial(kda.chunk_kda, head_groups=groups)
    repeated = lambda q, k, *rest: scan(  # noqa: E731
        *(jnp.repeat(x, rep, axis=2) for x in (q, k)), *rest)
    (_, want), want_g = both(kda.recurrent_kda)
    (_, got), got_g = both(scan)
    np.testing.assert_array_equal(got, jax.jit(repeated)(*args))
    _close(got, want, 2e-5, "forward")
    for name, x, a, y in zip("q k v g beta".split(), got_g, args, want_g):
        assert x.shape == a.shape == y.shape, name
        _close(x, y, 2e-4, f"d{name}")


@pytest.mark.parametrize("case, hk, h, groups, says", [
    ("a_group_cuts_a_key_head", 2, 4, 4, "2 of q and k"),
    ("no_head_block_holds_the_heads_of_a_key_head", 1, 16, 1,
     "serves 16 value heads"),
    ("value_heads_no_multiple_of_key_heads", 3, 4, 1, "no multiple")])
def test_head_counts_the_scan_cannot_run_are_refused(case, hk, h, groups,
                                                     says):
    """Nothing is repeated in silence: head groups that would cut a key
    head, more value heads to a key head than a grid step of the
    preparation holds, and value heads that are no multiple of the key
    heads are each a ``ValueError`` that says so."""
    sd = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    args = (sd(1, 64, hk, 32), sd(1, 64, hk, 32), sd(1, 64, h, 16),
            sd(1, 64, h), sd(1, 64, h))
    with pytest.raises(ValueError, match=says):
        jax.eval_shape(functools.partial(kda.chunk_kda, head_groups=groups),
                       *args)


def _loss_gradient(family):
    """(the tiny model, the gradient of its loss as a function of seeded
    shapes): what the engine's train step differentiates. A NEW model a
    call: ``jax.checkpoint`` keeps a function's trace."""
    model = tiny(family, **FAMILIES[family].step)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = _batch(model)

    def grad(p):
        out = model.loss(p, batch)
        return out[0] if isinstance(out, tuple) else out

    return model, jax.grad(grad), params


def test_the_step_holds_no_repeated_q_or_k_and_the_parents_kernel_counts():
    """The tiny Qwen3-Next step's gradient as it is traced: nothing under
    ``ds.mix_pre`` (forward, remat's rerun or transpose) is larger than
    the [B, S, 2 Hv] projection beta and the gate are cut from, where the
    parent repeated q and k there ([B, S, Hv, dk]) and summed the pairs
    back; and the scan's kernels are called as often as at the parent,
    3 / 1 / 3 / 1 (one head group keeps nothing, PR 51; the period's three
    Gated DeltaNet layers are one traced body); the gated norm behind the
    scan is its kernel pair, 2 / 1 (ISSUE 55)."""
    model, grad, params = _loss_gradient("qwen3_next")
    c = model.config
    calls = kernel_calls(grad, params)
    assert [calls[k] for k in ("ds_kda_prep_fwd", "ds_kda_prep_bwd",
                               "ds_kda_fwd", "ds_kda_bwd")] == [3, 1, 3, 1]
    assert [calls[k] for k in ("ds_gated_norm_fwd",
                               "ds_gated_norm_bwd")] == [2, 1]
    pre = [v.aval for e in _walk_eqns(jax.make_jaxpr(grad)(params).jaxpr)
           if "ds.mix_pre" in str(e.source_info.name_stack)
           for v in e.outvars]
    tokens = 2 * c.max_seq_len
    assert pre and max(a.size for a in pre) <= (
        tokens * 2 * c.linear_num_value_heads), max(
            pre, key=lambda a: a.size)


@pytest.mark.parametrize("family, key, value, groups", [
    ("qwen3_next", 2, 4, 1), ("kimi_linear", 4, 4, 2)])
def test_the_gauge_says_the_heads_the_preparation_was_built_for(
        family, key, value, groups):
    """``ds_kda_heads{kind="key"|"value"|"groups"}`` is set where the
    preparation's kernel is built (trace time): 2 key heads to 4 value
    heads in one head group at the tiny Qwen3-Next, as many of each in two
    groups at the tiny Kimi-Linear (the WHOLE arrays' heads since ISSUE
    59: a group is an offset in them, not a slice). With telemetry off
    nothing is touched."""
    _, grad, params = _loss_gradient(family)
    jax.eval_shape(grad, params)
    telemetry.configure()
    assert telemetry.get_registry().get("ds_kda_heads") is None
    # a new model: jax.checkpoint keeps a function's trace
    _, grad, params = _loss_gradient(family)
    jax.eval_shape(grad, params)
    gauge = telemetry.get_registry().get("ds_kda_heads")
    assert (gauge.value(kind="key"), gauge.value(kind="value"),
            gauge.value(kind="groups")) == (key, value, groups)


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

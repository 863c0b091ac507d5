"""Ouro's looped stack (ISSUE 42): the same layers run ``total_ut_steps``
times on shared weights, an exit gate after every pass, the expected loss
over the exits through ONE weighted chunked head; checked on the CPU at the
tiny preset against the plain float32 reference the benchmark keeps
(``benchmark/architectures/ouro.py``, which imports nothing from the
program). A CPU run shows results and counts, never a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import Ouro, get_model_class
from deepspeed_tpu.models.transformer import (_chunk_logits,
                                              _chunked_weighted_cross_entropy)
from deepspeed_tpu.parallel.partition import match_rules

from helpers.families import DS_CONFIG as _DS_CONFIG
from helpers.families import _batch, _telemetry_isolation  # noqa: F401
from helpers.families import arch_of, tail_loss_grads
from lib import modelspec  # noqa: E402  (benchmark/, by helpers.families)

arch = arch_of("ouro")
TAIL = 32


def _tiny(**kw):
    return Ouro(size="tiny", **kw)


def _weights(model, seed=3):
    """Seeded weights under which every part the check has to see carries
    weight in the loss: sharper scores and larger values (at the init's own
    scale a softmax over 128 keys is near uniform), norm scales that are
    not all one, a gate with a bias."""
    boost = {"wq": 4.0, "wk": 4.0, "wv": 4.0, "wo": 4.0}
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w * boost.get(path[-1].key, 1.0),
        model.init(jax.random.PRNGKey(seed)))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
    for name in ("ln1_scale", "ln1_out_scale", "ln2_scale", "ln2_out_scale"):
        w = params["layers"][name]
        params["layers"][name] = w * (
            1 + 0.3 * jax.random.normal(next(keys), w.shape))
    params["final_norm"]["scale"] = 1 + 0.3 * jax.random.normal(
        next(keys), params["final_norm"]["scale"].shape)
    params["exit_gate"]["b"] = jnp.float32(0.3)
    return params


def _err(got, want):
    got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
    return float(jnp.max(jnp.abs(got - want))) / (
        float(jnp.max(jnp.abs(want))) + 1e-30)


@pytest.fixture(scope="module")
def right():
    """The reference's loss, pass-T tail logits and gradients on the
    boosted float32 weights."""
    model = _tiny()
    params, (tokens, targets) = _weights(model), _batch(model)
    m = modelspec.reference_model(arch, model)
    with jax.default_matmul_precision("highest"):
        loss, tail = arch.reference(params, tokens, targets, m, TAIL)
        # jitted: eager, every line of the reference's gradient compiles alone
        grads = jax.jit(jax.grad(
            lambda *a: arch.expected_loss(*a, m)))(params, tokens, targets)
    return params, tokens, targets, m, loss, tail, grads


# what is compared of the gradient: a layer's shared weights (each the sum
# of four passes' cotangents), the head (made in the weighted head's forward
# scan) and the gate (which only the weights' cotangent and the entropy
# reach)
_GRADS = [("layers", "wq"), ("layers", "w_down"), ("layers", "ln1_out_scale"),
          ("layers", "ln2_out_scale"), ("final_norm", "scale"),
          ("lm_head",), ("exit_gate", "w"), ("exit_gate", "b"),
          ("embed", "tokens")]


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


@pytest.mark.parametrize("variant", ["plain", "flash_chunked_head"])
def test_float32_agrees_with_the_reference(right, variant):
    """Loss, pass-T logits and gradients in float32: the program's scans,
    remat, flash kernels (interpreted) and the weighted chunked head
    against Python loops. 2e-5: float32 roundings through 8 layer
    applications in another order of summation."""
    params, tokens, targets, m, loss, tail, grads = right
    kw = (dict(attn_impl="flash", loss_chunk=32)
          if variant == "flash_chunked_head" else {})
    model = _tiny(**kw)
    with jax.default_matmul_precision("highest"):
        got_tail, got_loss, got = tail_loss_grads(model, params, tokens,
                                                  targets, tail=TAIL)
    assert abs(float(got_loss) - loss) / loss < 2e-6
    assert _err(got_tail, tail) < 2e-5
    for path in _GRADS:
        assert _err(_leaf(got, path), _leaf(grads, path)) < 2e-5, path


def test_bf16_agrees_with_the_reference_and_the_passes_sum_in_bf16(right):
    """The program on bf16 weights as the engine runs it (flash, chunked
    head) against the float32 reference on the SAME rounded weights: what
    is left is bf16 arithmetic. Logits 3e-2 (two layers x four passes of
    bf16 roundings, eps 3.9e-3 each, measured 1.1e-2); the loss 2e-3 of
    itself (a mean over 256 positions; measured 3e-4). The gradients of
    the shared layer weights are the sum of four passes' cotangents, which
    autodiff adds in the weights' dtype: bf16. Measured against the
    float32 gradient, as a share of its largest element (PERF.md section
    6): wq 1.1e-2, w_down 4.2e-3, the output norms 7.2e-3 and 7.9e-3,
    beside the head's 6.8e-3 and the gate's 4.3e-3, whose sums are made in
    float32, and the embedding's 1.5e-2: the bf16 sum over four passes is
    not what bounds the error. Limit 4e-2."""
    params, tokens, targets, m, *_ = right
    rounded = jax.tree.map(lambda w: w.astype(jnp.bfloat16), params)
    with jax.default_matmul_precision("highest"):
        as_f32 = jax.tree.map(lambda w: w.astype(jnp.float32), rounded)
        loss, tail = arch.reference(as_f32, tokens, targets, m, TAIL)
        grads = jax.jit(jax.grad(lambda *a: arch.expected_loss(*a, m)))(
            as_f32, tokens, targets)
    model = _tiny(attn_impl="flash", loss_chunk=32)
    got_tail, got_loss, got = tail_loss_grads(model, rounded, tokens,
                                              targets, tail=TAIL)
    assert got_loss.dtype == jnp.float32
    assert abs(float(got_loss) - loss) / loss < 2e-3
    assert _err(got_tail, tail) < 3e-2
    errs = {path: _err(_leaf(got, path), _leaf(grads, path))
            for path in _GRADS}
    print("bf16 gradient errors:", errs)
    assert all(_leaf(got, p).dtype == jnp.bfloat16 for p in _GRADS)
    assert max(errs.values()) < 4e-2, errs


def test_one_pass_is_a_plain_stack_and_each_pass_counts(right):
    """``T`` = 1 with ``beta`` = 0 is the same weights through a plain
    sandwich-norm stack written out here (the loop adds nothing of its
    own); ``T`` = 4 differs from ``T`` = 3; ``beta`` moves the loss by
    beta x the entropy."""
    params, tokens, targets, *_ = right
    one = _tiny(total_ut_steps=1, exit_entropy_beta=0.0)
    x = one.embed(params, tokens)
    from deepspeed_tpu.ops import layers as L
    for i in range(one.config.num_layers):
        p = jax.tree.map(lambda w: w[i], params["layers"])
        x = one._layer(p, x, L.dot_product_attention, None)
    x = one._norm(x, params["final_norm"]["scale"])
    logits = x @ params["lm_head"]
    want = L.cross_entropy_loss(logits, targets)
    assert abs(float(one.loss(params, (tokens, targets))) - float(want)) \
        < 1e-5
    assert _err(one.apply(params, tokens), logits) < 1e-5

    four, three = _tiny(), _tiny(total_ut_steps=3)
    assert _err(three.apply(params, tokens), four.apply(params, tokens)) > 0.1
    l4, stats = four.loss(params, (tokens, targets), with_stats=True)
    l3 = three.loss(params, (tokens, targets))
    assert abs(float(l4) - float(l3)) / float(l4) > 1e-3
    l0 = _tiny(exit_entropy_beta=0.0).loss(params, (tokens, targets))
    assert abs(float(l0) - float(l4) - 0.1 * float(stats["exit_entropy"])) \
        < 1e-5
    assert float(stats["exit_entropy"]) > 0.5


def test_exit_distribution_sums_to_one_and_the_statistics_are_its_means(
        right):
    params, tokens, targets, m, *_ = right
    model = _tiny()
    exits = model._exit_states(params, tokens)
    log_p = model._exit_log_probs(params, exits)
    p = jnp.exp(log_p)
    assert p.shape == (4, 2, 128)
    np.testing.assert_allclose(np.asarray(p.sum(0)), 1.0, atol=1e-6)
    assert float(p.min()) > 0 and float(jnp.std(p[0])) > 0.01
    want = jnp.exp(arch.exit_log_probs(
        params, arch.exit_states(params, tokens, m)))
    np.testing.assert_allclose(np.asarray(p), np.asarray(want), atol=2e-5)
    # a saturated gate loses nothing: p is exact where lambda rounds to 1
    hot = dict(params, exit_gate={"w": params["exit_gate"]["w"],
                                  "b": jnp.float32(40.0)})
    log_hot = model._exit_log_probs(hot, exits)
    assert bool(jnp.all(jnp.isfinite(log_hot)))
    assert float(jnp.max(log_hot[1:])) < -30
    _, stats = model.loss(params, (tokens, targets), with_stats=True)
    np.testing.assert_allclose(np.asarray(stats["exit_prob"]),
                               np.asarray(p.mean((1, 2))), rtol=1e-5)
    assert abs(float(stats["exit_prob"].sum()) - 1) < 1e-5
    assert stats["exit_nll"].shape == (4,)
    assert float(stats["micro_batches"]) == 1.0


# ---- the weighted chunked head ---------------------------------------------
def _plain_weighted(x, W, targets, weights):
    nll = _chunk_logits(x, targets, W, None, rows=True)[-1]
    return jnp.sum(weights * nll) / jnp.sum(targets != -100)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_weighted_chunked_head_is_the_plain_forms_gradient(dtype):
    """Value, per-row NLL and the gradients for ``x``, ``W`` AND the
    weights against ``jax.grad`` of the plain form, with a masked target;
    float32 to 1e-5 (another order of summation), bf16 to 2e-2 of the
    largest element (dlogits are rounded to bf16 once, as autodiff does)."""
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (6, 64, 32)).astype(dtype)
    W = (0.3 * jax.random.normal(k[1], (32, 96))).astype(dtype)
    targets = jax.random.randint(k[2], (6, 64), 0, 96).at[1, 5].set(-100)
    weights = jax.nn.softmax(jax.random.normal(k[3], (6, 64)), axis=0)

    def chunked(x, W, weights):
        loss, nll = _chunked_weighted_cross_entropy(x, W, None, targets,
                                                    weights, 16)
        return loss, nll

    (got, nll), grads = jax.value_and_grad(
        chunked, argnums=(0, 1, 2), has_aux=True)(x, W, weights)
    want, want_grads = jax.value_and_grad(
        _plain_weighted, argnums=(0, 1, 3))(x, W, targets, weights)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert abs(float(got) - float(want)) < tol * float(want)
    assert nll.shape == (6, 64) and float(nll[1, 5]) == 0.0
    # without differentiation: the loss-only scan
    assert abs(float(chunked(x, W, weights)[0]) - float(got)) < 1e-6
    for g, w in zip(grads, want_grads):
        assert g.dtype == w.dtype and _err(g, w) < tol


# ---- the family, its refusals ----------------------------------------------
def test_registry_presets_and_partition_rules():
    assert get_model_class("ouro") is Ouro
    c = Ouro(size="2.6b").config
    assert (c.hidden_size, c.num_layers, c.num_heads, c.num_kv_heads,
            c.head_dim, c.intermediate_size, c.vocab_size,
            c.total_ut_steps) == (2048, 48, 16, 16, 128, 5632, 49152, 4)
    assert c.rope_theta == 1e6 and c.norm_eps == 1e-6
    assert not c.tie_embeddings and c.sliding_window is None
    # a layer, eight, embedding and head, final norm and gate (ISSUE 42)
    eight = Ouro(size="2.6b", num_layers=8).config.num_params()
    assert eight == 8 * 51388416 + 201326592 + 4097 == 612438017
    model = _tiny()
    params = model.init(jax.random.PRNGKey(0))
    assert model.config.num_params() == sum(
        int(np.prod(w.shape)) for w in jax.tree.leaves(params))
    match_rules(model.partition_rules(), params, default=None)
    assert {"ln1_out_scale", "ln2_out_scale"} <= set(params["layers"])
    assert set(params["exit_gate"]) == {"w", "b"}
    # T x the layers', the head's and the gate's FLOPs; the embedding once
    one = Ouro(size="tiny", total_ut_steps=1).config
    per_pass = one.num_params() - 512 * 64
    assert model.config.flops_per_token(128) == pytest.approx(
        one.flops_per_token(128) + 3 * (
            6 * per_pass + 12 * 2 * 64 * 64.5))


def test_what_runs_a_layer_at_a_time_refuses():
    """A cache would hold T x L slots and a pipeline would send the state
    round its ring once a pass: serving, the pipeline and every engine
    that calls ``block`` refuse, by name of the mechanism."""
    model = _tiny()
    for entry in (model.block, model.block_decode, model.decode,
                  model.init_cache):
        with pytest.raises(NotImplementedError, match="looped stack"):
            entry()
    from deepspeed_tpu.runtime.pipe.pipelined_model import PipelinedDecoderLM
    with pytest.raises(NotImplementedError, match="looped stack.*ring"):
        PipelinedDecoderLM(model, None, num_stages=2, num_microbatches=2)
    with pytest.raises(NotImplementedError, match="rematted whole"):
        _tiny(remat_policy="segments")
    with pytest.raises(NotImplementedError, match="sandwich-norm"):
        _tiny(sandwich_norm=False)
    with pytest.raises(ValueError, match="total_ut_steps"):
        _tiny(total_ut_steps=0)


# ---- through the engine (the shared cases: tests/test_ouro_engine.py) ------
def test_four_devices_agree_with_one(devices8, monkeypatch):
    """On a forced four-device mesh (``fsdp`` = 4) the loss and the
    gradient the engine makes agree with one device's: the loop gathers a
    layer's weights once a pass (nothing measured, only right). bf16
    matmuls over shards sum in another order: 2e-2 of the largest element
    of a gradient, 1e-3 of the loss."""
    model = _tiny(attn_impl="flash", loss_chunk=64)
    batch = _batch(model, b=4)
    got = {}
    for n in (1, 4):
        devices = devices8[:n]
        monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)
        engine, *_ = ds.initialize(model=model, config=dict(
            _DS_CONFIG, train_batch_size=4, mesh={"fsdp": n}))
        assert engine.mesh.size == engine.topology.sizes["fsdp"] == n
        # jitted: eager, every line and interpreted kernel compiles alone
        loss, grads = jax.jit(jax.value_and_grad(engine._loss_fn))(
            engine.state["params"], engine._put_batch(batch))
        got[n] = float(loss), jax.device_get(grads)
    assert got[4][0] == pytest.approx(got[1][0], rel=1e-3)
    for path in _GRADS:
        assert _err(_leaf(got[4][1], path), _leaf(got[1][1], path)) < 2e-2, \
            path

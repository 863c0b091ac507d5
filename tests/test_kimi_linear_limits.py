"""Kimi-Linear (ISSUE 31), the part of ``tests/test_kimi_linear.py`` that
is not the model's loss and gradients against its reference (PR 45: a
file is one worker's under ``--dist loadfile``): the reference's tail
logits and the cell's loss limit against planted faults,
the sigmoid router and a held share of its experts by hand, and the other
architectures' steps held to their parents' programs. A CPU run shows
results and counts, never a time."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import Mistral
from deepspeed_tpu.moe.sharded_moe import (balance_bias, held_experts_ffn,
                                           moe_ffn_held, sigmoid_top_k)
from deepspeed_tpu.ops import kda as kda_ops
from deepspeed_tpu.ops.pallas import kda as kda_kernels

from helpers.families import config_of, tiny
from helpers.families import (BENCH, _batch, _close,  # noqa: F401
                               _telemetry_isolation)
from architectures import kimi_linear as arch  # noqa: E402  (benchmark/,
#                                           on sys.path by families)
from helpers.families import kimi_ref_loss as _ref_loss
from lib import modelspec  # noqa: E402  (benchmark/, by families)

_tiny = functools.partial(tiny, "kimi_linear")


# ---- the reference's tail logits, the cell's loss limit ---------------------
def test_reference_logits_match_apply_and_every_position_counts():
    model = _tiny()
    params = model.init(jax.random.PRNGKey(4))
    tokens, targets = _batch(model, b=1)
    m = modelspec.reference_model(arch, model, {"routing_margin": 0.0,
                                                "excluded_share_max": 1.0})
    with jax.default_matmul_precision("highest"):
        loss, tail, counted = arch.reference(params, tokens, targets, m, 32)
        got = jax.jit(model.apply)(params, tokens)[:, -32:]
    assert bool(jnp.all(counted)) and counted.shape == (1, 32)
    _close(got, tail, 1e-4, "tail logits")
    assert abs(loss - float(jax.jit(model.loss)(
        params, (tokens, targets)))) < 1e-4
    # a margin leaves out the positions whose held experts sit near the
    # boundary, and only those
    m["routing_margin"] = 0.05
    with jax.default_matmul_precision("highest"):
        _, _, some = arch.reference(params, tokens, targets, m, 32)
    assert 0 < int(jnp.sum(some)) < 32


@pytest.mark.parametrize("fault", [None, "targets_off_by_one",
                                   "a_chunk_left_out_of_the_count"])
def test_the_cells_loss_limit_catches_a_planted_fault(fault):
    """``check.loss_err`` of the cell's configuration guards the loss
    arithmetic: the program's chunked loss passes it, a loss whose targets
    are shifted once more, or whose mean leaves one chunk's positions out
    of the count, does not (the decision is the benchmark's own)."""
    from kinds import train_job
    check = config_of("kimi_linear")["check"]
    model = _tiny(loss_chunk=64)
    params = model.init(jax.random.PRNGKey(3))
    tokens, targets = _batch(model)
    m = modelspec.reference_model(arch, model, check)
    with jax.default_matmul_precision("highest"):
        # jitted: eager, every line of the reference compiles alone
        want = float(jax.jit(lambda *a: _ref_loss(*a, m))(
            params, tokens, targets))
        if fault == "targets_off_by_one":
            targets = jnp.roll(targets, 1, axis=1)
        got = float(jax.jit(model.loss)(params, (tokens, targets)))
    if fault == "a_chunk_left_out_of_the_count":
        got *= targets.size / (targets.size - model.config.loss_chunk)
    numbers = {}
    assert train_job.decide(numbers, want, got, check) == (fault is None)
    assert (numbers["loss_err"] <= check["loss_err"]) == (fault is None)
    assert check["loss_err"] <= 1e-4


def test_sigmoid_router_by_hand():
    """Bias in the selection only, renormalised over the chosen, x 2.446."""
    logits = jnp.log(jnp.asarray([[0.8, 0.6, 0.5, 0.2]])
                     / (1 - jnp.asarray([[0.8, 0.6, 0.5, 0.2]])))
    bias = jnp.asarray([0.0, -0.5, 0.0, 0.35])
    idx, w, select, _ = sigmoid_top_k(logits, bias, 2, scaling=2.446)
    # scores + bias = .8, .1, .5, .55: experts 0 and 3, not 0 and 1
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 3]
    np.testing.assert_allclose(np.asarray(select[0]), [.8, .1, .5, .55],
                               rtol=1e-6)
    by_expert = dict(zip(np.asarray(idx[0]).tolist(),
                         np.asarray(w[0]).tolist()))
    # weights from the SCORES .8 and .2, not from scores + bias
    assert by_expert[0] == pytest.approx(2.446 * 0.8 / 1.0, rel=1e-6)
    assert by_expert[3] == pytest.approx(2.446 * 0.2 / 1.0, rel=1e-6)
    _, raw, _, _ = sigmoid_top_k(logits, bias, 2, renormalise=False)
    assert sorted(np.asarray(raw[0]).tolist()) == pytest.approx([0.2, 0.8])
    # and no gradient reaches the bias
    g = jax.grad(lambda b: jnp.sum(sigmoid_top_k(logits, b, 2)[1]))(bias)
    assert not np.any(np.asarray(g))


# ---- a held share ----------------------------------------------------------
E, K, D, F = 256, 8, 16, 8


def _full_layer():
    """An uncut layer's weights (every one of the E experts) and tokens."""
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    w = lambda *shape: 0.5 * jax.random.normal(next(ks), shape)  # noqa: E731
    params = {"router": w(D, E), "router_bias": jnp.linspace(-0.05, 0.05, E),
              "experts": {"w_gate": w(E, D, F), "w_up": w(E, D, F),
                          "w_down": w(E, F, D)},
              "shared": {"w_gate": w(D, F), "w_up": w(D, F),
                         "w_down": w(F, D)}}
    return params, jax.random.normal(jax.random.PRNGKey(1), (2, 48, D))


def _share(params, x, chip, held=8):
    """``moe_ffn_held`` as chip ``chip`` of E / held runs it."""
    mine = {n: w[held * chip:held * (chip + 1)]
            for n, w in params["experts"].items()}
    return moe_ffn_held(x, params["router"], params["router_bias"], mine,
                        params["shared"], k=K, first_expert=held * chip,
                        scaling=2.446, block=16)


def test_the_shares_add_up_to_the_uncut_layer():
    """32 shares of 8 experts, the shared expert counted once, sum to the
    whole layer, which is the reference's with every expert held."""
    params, x = _full_layer()
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda w: w.astype(jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        whole, _, _ = arch.routed(f32(params), x.reshape(-1, D), top_k=K,
                                  first=0, renormalise=True, scaling=2.446)
        shared = arch._swiglu(params["shared"], x.reshape(-1, D))
        total, load = 0, 0
        for chip in range(E // 8):
            mine = dict(params, experts={n: w[8 * chip:8 * chip + 8]
                                         for n, w in
                                         params["experts"].items()})
            out, counts = _share(params, x, chip)
            out = out.reshape(-1, D)
            # every share counts the same load over ALL the experts, and
            # computes the rows of its own slice of it
            assert int(counts["done"]) == int(
                jnp.sum(counts["load"][8 * chip:8 * chip + 8]))
            load = counts["load"]
            # the program's share is the reference's share
            ref, _, _ = arch.routed(f32(mine), x.reshape(-1, D), top_k=K,
                                    first=8 * chip, renormalise=True,
                                    scaling=2.446)
            _close(out, ref, 1e-5, f"share {chip}")
            total = total + out
    _close(total - (E // 8 - 1) * shared, whole, 1e-5, "sum of shares")
    assert int(jnp.sum(load)) == x.shape[0] * x.shape[1] * K


@pytest.mark.parametrize("skew", ["balanced", "all_to_one_held_expert",
                                  "none_held"])
def test_no_token_is_dropped_under_a_skewed_router(skew):
    params, x = _full_layer()
    held = {n: w[:8] for n, w in params["experts"].items()}
    bias = {"balanced": params["router_bias"],
            # every token's top-8 holds experts 0..7: 8 rows a token here
            "all_to_one_held_expert": jnp.where(jnp.arange(E) < 8, 5.0, 0.0),
            "none_held": jnp.where(jnp.arange(E) < 8, -5.0, 0.0)}[skew]
    xt = x.reshape(-1, D)
    idx, w, _, _ = sigmoid_top_k(xt @ params["router"], bias, K,
                                 scaling=2.446)
    out, swept = held_experts_ffn(xt, idx, w, held, 0, 16)
    want_rows = int(jnp.sum(idx < 8))
    assert int(swept["done"]) == want_rows
    assert want_rows == {"all_to_one_held_expert": xt.shape[0] * 8,
                         "none_held": 0}.get(skew, want_rows)
    dense = sum(jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
                * arch._swiglu({n: v[e] for n, v in held.items()}, xt)
                for e in range(8))
    _close(out, dense, 1e-5) if want_rows else None
    if not want_rows:
        assert not np.any(np.asarray(out))
    # a token routed only to absent experts gets the shared expert alone
    full, counts = moe_ffn_held(x, params["router"], bias, held,
                                params["shared"], k=K, scaling=2.446,
                                block=16)
    _close(full.reshape(-1, D), dense + arch._swiglu(params["shared"], xt),
           1e-5)
    assert int(counts["done"]) == int(jnp.sum(counts["load"][:8])) \
        == want_rows


def test_balance_bias_by_hand_and_it_holds_a_drifting_router():
    """Over the mean load: bias down by the rate; under it: up; at it:
    left. And where the held experts' scores drift down step by step (as
    they do in the cut model, whose absent experts get no gradient), the
    update keeps their load near the mean if its rate is over the
    drift's (0.02 a step in the logit is 0.0023 in the score at the
    top-k boundary), where without it the load collapses."""
    got = balance_bias(jnp.zeros(4), jnp.asarray([9, 1, 5, 5]), 0.001)
    np.testing.assert_allclose(np.asarray(got), [-.001, .001, 0, 0])
    logits = jax.random.normal(jax.random.PRNGKey(2), (4096, E))
    drift = jnp.where(jnp.arange(E) < 8, -0.02, 0.0)    # a step, held only

    def held_load(rate, steps=60):
        bias = jnp.zeros(E)
        for t in range(steps):
            idx, _, _, _ = sigmoid_top_k(logits + t * drift, bias, K)
            load = jnp.bincount(idx.reshape(-1), length=E)
            bias = balance_bias(bias, load, rate)
        return float(jnp.mean(load[:8])) / (4096 * K / E)

    assert held_load(0.0) < 0.2
    assert held_load(0.001) < 0.6       # a rate under the drift lags it
    assert 0.85 < held_load(0.004) < 1.15


# ---- the one-kind scan is the parent's program -----------------------------
def _parent_final_hidden(self, params, tokens, *, attn_fn=None,
                         positions=None, act_sharding=None):
    """``DecoderLM._final_hidden`` as it stood before the stack of kinds
    was split off into ``_layer_stack`` (commit d200a6f)."""
    import functools

    from deepspeed_tpu.models.transformer import _remat_policy
    from deepspeed_tpu.parallel.mesh import constrain_free
    c = self.config
    pin = (functools.partial(constrain_free, sharding=act_sharding)
           if act_sharding is not None else lambda x: x)
    with jax.named_scope("ds.embed"):
        x = self.embed(params, tokens, positions)
    x = pin(x)

    def body(carry, layer_params):
        x, aux = carry
        x, layer_aux = self.block(layer_params, x, attn_fn=attn_fn,
                                  positions=positions)
        return (pin(x), aux + layer_aux), None

    if c.remat and c.remat_policy != "segments":
        body = jax.checkpoint(body, prevent_cse=False,
                              policy=_remat_policy(c.remat_policy))
    with jax.named_scope("ds.layers"):
        (x, aux), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    with jax.named_scope("ds.loss_head"):
        x = self._norm(x, params["final_norm"]["scale"],
                       params["final_norm"].get("bias"))
    return x, aux


def _mistral_step_text(monkeypatch, parent: bool, **model_kw):
    if parent:
        monkeypatch.setattr(Mistral, "_final_hidden", _parent_final_hidden)
    model = Mistral(size="tiny", **model_kw)
    engine, *_ = ds.initialize(model=model, config={
        "train_batch_size": 8, "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "gradient_clipping": 1.0, "mesh": {"fsdp": -1},
        "steps_per_print": 10 ** 9})
    tok = np.zeros((8, model.config.max_seq_len), np.int32)
    lowered = engine._train_step.lower(engine.state,
                                       engine._put_batch((tok, tok)))
    monkeypatch.undo()
    # no source locations in either (debug_info off); the compiled text
    # with what only says where the code stood taken out
    hlo = lowered.compile().as_text()
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    hlo = "\n".join(l for l in hlo.splitlines()
                    if not re.match(r"^(FileNames|FunctionNames|"
                                    r"FileLocations|StackFrames)\b|^\d+ ",
                                    l.strip()))
    return lowered.as_text(), hlo


@pytest.mark.parametrize("model_kw", [
    dict(), dict(remat_policy="segments", loss_chunk=64, attn_impl="flash")],
    ids=["default", "the_cells_switches"])
def test_mistral_step_is_the_parents_program(monkeypatch, model_kw):
    """With and without this PR's stack code on its path, the compiled
    train step of the mistral tiny preset is one program."""
    mlir_now, hlo_now = _mistral_step_text(monkeypatch, False, **model_kw)
    mlir_parent, hlo_parent = _mistral_step_text(monkeypatch, True,
                                                 **model_kw)
    assert mlir_now == mlir_parent
    assert hlo_now == hlo_parent


@pytest.mark.parametrize("family", ["mistral", "granite_hybrid"])
def test_the_other_architectures_steps_run_nothing_of_kda(monkeypatch,
                                                          family):
    """PR 35 changed ``ops/kda.py`` and ``ops/pallas/kda.py`` alone (and a
    list in ``telemetry/scopes.py``): Mistral's and Granite's lowered train
    steps are the same text with every entry point of the two files made
    to raise, so they are the parent's."""
    from deepspeed_tpu.models.base import get_model_class

    def step_text():
        model = get_model_class(family)(size="tiny")
        engine, *_ = ds.initialize(model=model, config={
            "train_batch_size": 8, "bf16": {"enabled": True},
            "zero_optimization": {"stage": 3},
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "gradient_clipping": 1.0, "mesh": {"fsdp": -1},
            "steps_per_print": 10 ** 9})
        tok = np.zeros((8, model.config.max_seq_len), np.int32)
        return engine._train_step.lower(
            engine.state, engine._put_batch((tok, tok))).as_text()

    def refuse(*a, **kw):
        raise AssertionError("KDA code on another architecture's path")

    now = step_text()
    for module, names in ((kda_ops, ("chunk_kda", "sharded_chunk_kda",
                                     "_scan", "recurrent_kda")),
                          (kda_kernels, ("kda_prepare", "kda_recurrence",
                                         "_Chunk", "_forward", "_backward"))):
        for name in names:
            monkeypatch.setattr(module, name, refuse)
    assert "loc(" not in now and step_text() == now

"""The agreement check on a ROUTED architecture (the rehearsal's
``mixtral`` module at the tiny widths but with 64 experts, top-8 without
renormalisation): what the mask lets pass, what it does not, and that the
dense cells read what they read without it. By hand, with the others:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The stand-ins need no engine: the weights are drawn here, normal(0,
0.02 x sqrt(2048 / 64)), so that a router logit has the rms it has at
OLMoE's hidden size (0.9) and an expert's output weighs in the residual
stream as it does there. (With the engine's 0.02 at hidden 64 the experts
move the stream by a hundredth of it, no flip shows, and the test would
pass with the mask taken out.)
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH)
REHEARSAL = os.path.join(HERE, "rehearsal")
# ``architectures`` has no __init__.py: with both roots on the path it is
# one namespace package over benchmark/ and the rehearsal's added files
sys.path[:0] = [BENCH, REHEARSAL, HERE]

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402

import control                                      # noqa: E402
from architectures import mixtral                   # noqa: E402
from kinds import train_job                         # noqa: E402

with open(os.path.join(REHEARSAL, "configs",
                       "moe-64x8-zero3-1chip.json")) as f:
    CHECK = json.load(f)["check"]      # the limits the chip readings set

D, F, V, HEADS, KV, HD, E, K, S = 64, 128, 512, 4, 2, 16, 64, 8, 128
STD = 0.02 * (2048 / D) ** 0.5
M = dict(hidden_size=D, intermediate_size=F, num_attention_heads=HEADS,
         num_key_value_heads=KV, head_dim=HD, vocab_size=V,
         sliding_window=None, rope_theta=1e4, rms_norm_eps=1e-5,
         num_local_experts=E, num_experts_per_tok=K, norm_topk_prob=False,
         router_aux_loss_coef=0.01, num_hidden_layers=1,
         routing_margin=CHECK["routing_margin"])


def _drawn(seed):
    """(params in the program's layout, tokens, targets) from the seed."""
    rng = np.random.default_rng(seed)

    def n(*shape, std=STD):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32)
                           * std)
    out_std = STD / 2 ** 0.5
    params = {
        "embed": {"tokens": n(V, D)},
        "layers": {"ln1_scale": jnp.ones((1, D)),
                   "ln2_scale": jnp.ones((1, D)),
                   "wq": n(1, D, HEADS * HD), "wk": n(1, D, KV * HD),
                   "wv": n(1, D, KV * HD),
                   "wo": n(1, HEADS * HD, D, std=out_std),
                   "router": n(1, D, E),
                   "experts": {"w_gate": n(1, E, D, F), "w_up": n(1, E, D, F),
                               "w_down": n(1, E, F, D, std=out_std)}},
        "final_norm": {"scale": jnp.ones((D,))}, "lm_head": n(D, V)}
    batch = rng.integers(0, V, size=(2, S + 1), dtype=np.int32)
    return params, jnp.asarray(batch[:, :-1]), jnp.asarray(batch[:, 1:])


@pytest.fixture(scope="module", params=[11, 12, 13])
def drawn(request):
    params, toks, tgts = _drawn(request.param)
    with jax.default_matmul_precision("highest"):
        ref = train_job.reference_of(mixtral, params, toks, tgts, M)
    return params, toks, tgts, ref


def _verdict(ref, got, counted, check=CHECK):
    """The kind's own decision on a stand-in's (loss, tail)."""
    numbers = train_job.tail_numbers(got[1], ref[1], counted)
    return train_job.decide(numbers, ref[0], got[0], check), numbers


def _stand_in(drawn, dtype=None, **m):
    params, toks, tgts, _ = drawn
    with jax.default_matmul_precision("highest"):
        if dtype is None:
            return train_job.reference_of(mixtral, params, toks, tgts,
                                          {**M, **m})
        with control.weight_matmuls_in(dtype) as traced:
            got = train_job.reference_of(mixtral, params, toks, tgts, M)
        assert traced
        return got


def test_bf16_stand_in_fails_without_the_mask_and_passes_with_it(drawn):
    ref, got = drawn[3], _stand_in(drawn, "bfloat16")
    ok, numbers = _verdict(ref, got, None)
    assert not ok and numbers["logits_err_max"] > 3 * CHECK["logits_err_max"]
    assert "excluded_share" not in numbers
    ok, numbers = _verdict(ref, got, ref[2])
    assert ok, numbers
    assert 0.0 < numbers["excluded_share"] <= CHECK["excluded_share_max"]
    assert numbers["positions_counted"] == int(np.sum(np.asarray(ref[2])))


def test_fp8_stand_in_fails_with_the_mask(drawn):
    ok, numbers = _verdict(drawn[3], _stand_in(drawn, "float8_e4m3fn"),
                           drawn[3][2])
    assert not ok
    assert numbers["logits_err_rms"] > 3 * CHECK["logits_err_rms"]
    assert numbers["logits_err_max"] > 3 * CHECK["logits_err_max"]


@pytest.mark.parametrize("wrong", [{"num_experts_per_tok": K - 1},
                                   {"norm_topk_prob": True}],
                         ids=["k-1_experts", "renormalised_weights"])
def test_a_wrong_router_fails_with_the_mask(drawn, wrong):
    """In float32, so nothing but the routing is wrong; the mask is the
    reference's, and the positions a wrong router changes are counted."""
    params, toks, _, ref = drawn
    got = _stand_in(drawn, **wrong)
    ok, numbers = _verdict(ref, got, ref[2])
    assert not ok and numbers["logits_err_max"] > CHECK["logits_err_max"]
    with jax.default_matmul_precision("highest"):
        _, sets, _ = mixtral.routes(params, toks, M, train_job.TAIL)
        _, got_sets, _ = mixtral.routes(params, toks, {**M, **wrong},
                                        train_job.TAIL)
    if "num_experts_per_tok" in wrong:      # every position chose otherwise
        assert got_sets.shape[-1] == K - 1
    else:       # the same experts, other weights: no mask can hide that
        assert bool(jnp.all(sets == got_sets))
    # the error is at counted positions: over them alone it is as large
    worst = np.asarray(jnp.max(jnp.abs(got[1] - ref[1]), axis=-1))
    assert worst[np.asarray(ref[2])].max() == pytest.approx(worst.max())


def test_the_excluded_share_alone_makes_a_run_not_correct(drawn):
    """A margin that leaves out most positions: the errors over the rest
    are a right program's, and the run is NOT correct."""
    params, toks, tgts, ref = drawn
    got = _stand_in(drawn, "bfloat16")
    with jax.default_matmul_precision("highest"):
        wide = train_job.reference_of(mixtral, params, toks, tgts,
                                      {**M, "routing_margin": 0.2})
    ok, numbers = _verdict(wide, got, wide[2])
    assert numbers["excluded_share"] > CHECK["excluded_share_max"]
    assert all(numbers[k] <= CHECK[k] for k in (
        "logits_err_max", "logits_err_rms", "loss_err"))
    assert not ok
    # and a mask that counts nothing is infinitely wrong
    none = jnp.zeros_like(ref[2])
    assert train_job.errors(got[1], ref[1], none) == (float("inf"),) * 2


def test_errors_over_counted_positions_are_errors_of_those_positions():
    rng = np.random.default_rng(0)
    ref = rng.standard_normal((2, 5, 7)).astype(np.float32)
    got = ref + 0.01 * rng.standard_normal(ref.shape).astype(np.float32)
    got[1, 3] += 5.0                         # one position far off
    counted = np.ones((2, 5), bool)
    counted[1, 3] = False
    masked = train_job.errors(got, ref, jnp.asarray(counted))
    kept = train_job.errors(got[counted], ref[counted])
    assert masked == pytest.approx(kept, rel=1e-6)
    assert train_job.errors(got, ref)[0] > 50 * masked[0]
    every = train_job.errors(got, ref, jnp.ones((2, 5), bool))
    assert every == pytest.approx(train_job.errors(got, ref), rel=1e-6)
    got[0, 0, 0] = np.nan                    # non-finite, though left out
    counted[0, 0] = False
    assert train_job.errors(got, ref, jnp.asarray(counted))[0] == float("inf")


# ---- the dense cells read what the parent reads ---------------------------
# ``agreement:`` lines of the parent (PR 26, 420729f) on this CPU rig, seed
# 3000000019: five numbers, no mask's keys
PARENT = {
    "train-s8k-1chip": {
        "ref_loss": 6.24191951751709,
        "logits_err_max": 0.0049835373647511005,
        "logits_err_rms": 0.004742510616779327,
        "first_step_loss": 6.241942405700684,
        "loss_err": 3.666850161960188e-06},
    "train-s8k-4chip": {
        "ref_loss": 6.25101375579834,
        "logits_err_max": 0.007569995243102312,
        "logits_err_rms": 0.006595073267817497,
        "first_step_loss": 6.25097131729126,
        "loss_err": 6.789059940991627e-06}}


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_dense_cells_print_the_parents_agreement_line(cell):
    """Equal digit for digit on the machine the lines were recorded on;
    another CPU may round the last digits otherwise, so the values are
    held to 1e-4 and the keys, their order and the verdict exactly."""
    chips = 4 if cell.endswith("4chip") else 1
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   CHECKOUT, ".bench_trace", "test_jax_cache"))
    p = subprocess.run([sys.executable, os.path.join(HERE, "cpu_rig.py"),
                        cell, "0", "1"], cwd=CHECKOUT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line, = [ln for ln in p.stdout.splitlines()
             if ln.startswith("agreement: ")]
    text = line[len("agreement: "):line.index(" tolerances ")]
    got = json.loads(text.replace("'", '"'))
    assert list(got) == list(PARENT[cell])
    assert got == pytest.approx(PARENT[cell], rel=1e-4)
    assert line.endswith("ok=True")

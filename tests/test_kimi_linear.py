"""Kimi-Linear (ISSUE 31): a stack of KDA linear attention, NoPE latent
attention and a held share of sigmoid-routed experts, checked on the CPU
at tiny sizes against the plain float32 reference the benchmark keeps
(``benchmark/architectures/kimi_linear.py``, which imports nothing from
the program). A CPU run shows results and counts, never a time. A file is
one worker's under ``--dist loadfile`` (this one was 1381 s of the gate's
1470 at PR 41, 906 at PR 44), so the family's cases lie in six: the cases
that train the engine are ``tests/test_kimi_linear_engine.py`` (PR 41),
the KDA kernels' ``tests/test_kda_kernels.py`` and
``tests/test_kda_prep_kernels.py``, the cell's limits, the router and the
parents' programs ``tests/test_kimi_linear_limits.py`` (PR 45), the whole
model against the float32 reference
``tests/test_kimi_linear_reference.py`` (PR 50); what they share is
``tests/helpers/families.py``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import KimiLinear
from deepspeed_tpu.models.stack import stack_plan
from deepspeed_tpu.ops.kda import chunk_kda, recurrent_kda
from deepspeed_tpu.ops.pallas import kda as kda_kernels
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

from helpers.families import tiny
from helpers.families import (_as_bf16, _close, _kda_inputs,  # noqa: F401
                              _telemetry_isolation)
from architectures import kimi_linear as arch  # noqa: E402  (benchmark/,
#                                           on sys.path by families)

_tiny = functools.partial(tiny, "kimi_linear")


# ---- MLA: the flash path (key 24, value 16) against plain softmax ----------
def test_flash_attention_with_a_narrower_value_matches_plain_softmax():
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(size=(2, 256, 4, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, 256, 4, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 256, 4, 16)), jnp.float32)
    want = arch.causal_attention(q, k, v)
    # jitted: eager, every line round the kernels compiles alone
    got = jax.jit(lambda *a: flash_attention(*a, causal=True))(q, k, v)
    assert got.shape == (2, 256, 4, 16)
    _close(got, want, 1e-5, "forward")
    grad = lambda f: jax.jit(jax.grad(  # noqa: E731
        lambda q, k, v: jnp.sum(f(q, k, v) * w), argnums=(0, 1, 2)))(q, k, v)
    for name, g, r in zip("qkv", grad(flash_attention),
                          grad(arch.causal_attention)):
        _close(g, r, 1e-4, f"d{name}")


def test_mla_layer_through_flash_matches_the_plain_layer():
    params = _tiny().init(jax.random.PRNGKey(5))
    p = params["layers"]["tail"]["0"]["mla"]
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 128, 64))
    c = _tiny().config
    want = arch.mla_mixer(p, h, heads=c.num_heads, nope=c.qk_nope_head_dim,
                          rope=c.qk_rope_head_dim, dv=c.v_head_dim,
                          lora=c.kv_lora_rank, eps=c.norm_eps)
    got = jax.jit(lambda p, h: _tiny(attn_impl="flash")._mla(
        p, h, flash_attention))(p, h)
    _close(got, want, 1e-5)


# ---- the stack, the counts, the engine -------------------------------------
@pytest.mark.parametrize("mixers,lead,want", [
    ("KKKM" * 6 + "KKM", 1, (4, 6, 2)),     # the published 27 layers
    ("KKKMK", 1, (1, 2, 2)),                # the cell's cut: layers 1 to 5
    ("KM", 0, (0, 0, 2)),                   # nothing repeats: unrolled
    ("KKKK", 0, (1, 4, 0)),
])
def test_stack_plan(mixers, lead, want):
    assert stack_plan(list(mixers), lead) == want


def test_published_preset_counts():
    """``ModelConfig`` counts a stack of kinds and a held share: the
    issue's 602 M parameters, of which a token computes with the dense
    parts and a quarter of an expert a routed layer."""
    c = KimiLinear(size="48b-a3b", num_layers=5, vocab_size=20480,
                   kda_layers=[1, 2, 3, 5], full_attn_layers=[4],
                   moe_held_experts=8).config
    assert c.num_params() == 602450816
    per = c._kind_params()
    assert (per["kda"], per["mla"], per["dense"], per["expert"]) == (
        39518368, 29114880, 63700992, 7077888)
    assert c.num_params() - c.num_active_params() == int(
        4 * (8 - 8 * 8 / 256) * per["expert"])
    m = {k: getattr(c, a) for k, a in arch.WIDTHS.items()}
    # the program's estimate and the benchmark's count agree to 1%: they
    # differ in norms, biases and the convolutions
    assert c.flops_per_token(16384) == pytest.approx(
        arch.train_flops_per_token(m, 16384), rel=0.01)
    tiny = _tiny()
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(tiny.init, jax.random.PRNGKey(0))))
    assert tiny.config.num_params() == n
    whole = KimiLinear(size="48b-a3b").config
    assert 47e9 < whole.num_params() < 50e9     # "48B"
    assert 2.5e9 < whole.num_active_params() < 3.6e9    # "A3B"


# ---- the decays at which the jax.numpy preparation overflowed ---------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("per_token", [6.0, 12.0, 20.0])
def test_chunked_kda_stays_finite_where_a_channel_forgets_in_one_token(
        per_token, dtype):
    """Past a log-decay of -5.5 a token a 16-row block's own columns
    overflowed float32 and a training run on the chip went NaN (PR 31):
    8 rows, a clamped exponent and an exact diagonal hold any decay, and
    the kernels (PR 32) get their operands from those; in bfloat16 as the
    cell runs them, too. Two heads are one grid step of the preparation:
    their inverses run side by side in one product (PR 44)."""
    args = _kda_inputs(b=1, s=128, h=2)
    assert kda_kernels._prep_geometry(args[0], args[2], 64)[-1] == 2
    g = args[3].at[..., 0].set(-per_token).at[..., 1].set(-per_token / 2)
    args[3] = g
    tol_o, tol_g = 1e-5, 2e-5
    if dtype == "bfloat16":
        args, tol_o, tol_g = _as_bf16(args), 2e-2, 4e-2

    def both(f):
        """``f``'s float32 output and the gradients of its sum, one
        program (eager, every line round the kernels compiles alone)."""
        def total(*a):
            out = f(*a).astype(jnp.float32)
            return jnp.sum(out), out
        return jax.jit(jax.value_and_grad(
            total, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)

    (_, want), want_g = both(recurrent_kda)
    (_, got), grads = both(chunk_kda)
    assert bool(jnp.all(jnp.isfinite(got)))
    _close(got, want, tol_o, "forward")
    for name, a, b in zip("qkvgb", grads, want_g):
        assert bool(jnp.all(jnp.isfinite(a))), name
        _close(a.astype(jnp.float32), b.astype(jnp.float32), tol_g,
               f"d{name}")

"""What the two files on kept residuals share (``tests/test_kept_residuals.py``:
the engine's train steps by kernel, the flash kernels, whole families bit
for bit; ``tests/test_kept_scan.py``: the delta rule's scan alone and the
family that cannot be held bit for bit): the rematted families at their
``tiny`` presets, a traced program's kernel calls, ``policy=None`` in the
models' place, and a family's loss and gradients. A file is one worker's
under ``--dist loadfile``, so the cases lie in two."""

import collections

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import (GraniteHybrid, KimiLinear, Mellum, Ouro,
                                  Qwen3Next, ouro, stack, transformer)
from deepspeed_tpu.ops.pallas import _common

from helpers.family_cases import _batch, _walk_eqns

# family -> (class, the `tiny` preset's switches, attention layer
# applications in the TRACED program: a scan's body is traced once, so
# Ouro's 2 layers x 4 passes are one application, and Mellum's two kinds
# of attention layer are two). Kimi-Linear's KDA heads run in two groups, as
# its cell's run in four: with one, the scan keeps nothing.
REMATTED = {
    "kimi_linear": (KimiLinear, dict(moe_held_experts=8,
                                     kda_head_groups=2), 1),
    "granite_hybrid": (GraniteHybrid, {}, 1),
    "mellum": (Mellum, dict(moe_held_experts=16), 2),
    "ouro": (Ouro, {}, 1),
    "qwen3_next": (Qwen3Next, dict(moe_held_experts=32), 1),
}


def tiny(family):
    cls, model_kw, _ = REMATTED[family]
    return cls(size="tiny", attn_impl="flash", loss_chunk=64, **model_kw)


def kernel_calls(fn, *args):
    """How often each Pallas kernel is called in ``fn``'s traced program,
    by the kernel's name. Interpreted kernels lower to plain HLO, so the
    lowered text of a CPU step holds no kernel's name: the jaxpr that is
    lowered does. Traced through a function of its own, so that no trace
    made under another policy is found again."""
    _common._TRACED.clear()
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a))(*args)
    return collections.Counter(
        eqn.params["name"] for eqn in _walk_eqns(jaxpr.jaxpr)
        if eqn.primitive.name == "pallas_call")


def keep_nothing(monkeypatch):
    """``jax.checkpoint(policy=None)`` wherever a model asks
    ``_remat_policy``: what every policy name meant before PR 47."""
    for module in (transformer, stack, ouro):
        monkeypatch.setattr(module, "_remat_policy", lambda name: None)


def value_and_grads(family):
    """(loss, {path: gradient as float32}) of ``family``'s tiny model at
    seeded bf16 weights."""
    model = tiny(family)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                          model.init(jax.random.PRNGKey(1)))
    batch = _batch(model, b=2)

    def loss(p):
        out = model.loss(p, batch)
        return out[0] if isinstance(out, tuple) else out

    value, grads = jax.device_get(jax.jit(jax.value_and_grad(loss))(params))
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert np.isfinite(value)
    assert any(np.any(np.asarray(g, np.float32) != 0) for _, g in flat)
    return float(value), {jax.tree_util.keystr(path): np.asarray(
        g, np.float32) for path, g in flat}

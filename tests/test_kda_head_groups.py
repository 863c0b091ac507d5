"""The delta-rule scan's head groups (ISSUE 59): a group is an offset in the
four kernels' index maps (``ops/kda.py`` ``_scan``, ``ops/pallas/kda.py``
``_prep_specs`` / ``_specs``), so one, two and four groups give ONE
group's ``o`` and gradients bit for bit at either gate, at fewer key heads
than value heads and in either layout of ``o``, and the recurrence's token
by token to the kernels' tolerances. Interpret mode, tiny shapes, jitted;
a file of its own (a file is one worker's under ``--dist loadfile``). The
kept ``o`` under a rematted layer is ``tests/test_kept_scan.py``, the
compiled calls and copies ``tests/test_zero_layout.py``. A CPU run shows
results and counts, never a time."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.kda import chunk_kda, recurrent_kda

from helpers.families import _close, _kda_inputs


def _grouped_inputs(gate, rep):
    """8 value heads (``rep`` to a key head), so that one, two and four
    groups each hold whole pairs of heads, as the cells' groups do: a pair
    takes the inverse's float32 products together, and the CPU's sums of
    a pair are not a lone head's to the last bit."""
    args = _kda_inputs(b=1, s=128, h=8, seed=3)
    if gate == "a_head":
        args[3] = args[3][..., 5]
    args[:2] = [x[:, :, ::rep] for x in args[:2]]
    return args


@functools.lru_cache(maxsize=None)
def _grouped_scan(gate, rep, groups, by_head):
    """(o as [B, S, H, dv], the five gradients) of ``chunk_kda`` in
    ``groups`` head groups, jitted; ``by_head``: through the heads' stack,
    whose layout is undone here; ``groups`` None: of ``recurrent_kda``.
    One run a form: the one-group form and the recurrence are every
    case's other side."""
    args = _grouped_inputs(gate, rep)
    b, s, h, dv = args[2].shape
    cot = jnp.asarray(np.random.default_rng(7).normal(size=(b, s, h, dv)),
                      jnp.float32)

    def total(*a):
        if groups is None:
            o = recurrent_kda(*a)
        else:
            o = chunk_kda(*a, head_groups=groups, by_head=by_head)
        if by_head:
            assert o.shape == (groups, b, h // groups, s, dv)
            o = o.transpose(1, 3, 0, 2, 4).reshape(b, s, h, dv)
        return jnp.sum(jnp.tanh(o) * cot), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        total, argnums=range(5), has_aux=True))(*args)
    return (o, *grads)


@pytest.mark.parametrize("by_head", [False, True], ids=["tokens", "by_head"])
@pytest.mark.parametrize("gate, rep", [("a_channel", 1), ("a_head", 1),
                                       ("a_head", 2)])
@pytest.mark.parametrize("groups", [1, 2, 4])
def test_a_head_group_is_an_offset_not_another_result(groups, gate, rep,
                                                      by_head):
    """ISSUE 59: a head group is an offset in the kernels' index maps. The
    heads are independent, so ``o`` and all five gradients in one, two and
    four groups are ONE group's bit for bit (``rep`` 2: four key heads
    serve the eight value heads, a group holds whole key heads and sums
    their value heads' ``dq`` and ``dk`` before its one store into the
    whole gradient), whichever layout ``o`` leaves in, and the
    recurrence's token by token to the tolerances the kernels' cases
    hold."""
    args = _grouped_inputs(gate, rep)
    got = _grouped_scan(gate, rep, groups, by_head)
    one = _grouped_scan(gate, rep, 1, False)
    want = _grouped_scan(gate, rep, None, False)
    for name, x, y, z, a in zip("o dq dk dv dg dbeta".split(), got, one,
                                want, [args[2], *args]):
        assert x.shape == a.shape and x.dtype == a.dtype, name
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)
        _close(x, z, 1e-5 if name == "o" else 2e-4, name)

"""The delta rule's scan under a rematted layer (ISSUE 51): ``ops/kda.py``
``chunk_kda`` declares its ``o`` kept (``ops/pallas/_common.py`` ``_keep``,
in the forward rule of the ONE ``custom_vjp`` over the grouped scan since
ISSUE 59), so a checkpoint whose policy saves the name runs the scan's
forward kernels twice a backward (the forward; the backward rule's own
preparation and checkpoint form) and not three times where the head groups
are more than one (one group keeps nothing),
alone and per shard of the batch on a mesh, with the bits the rerun makes;
and the one family whose whole step cannot be held bit for bit on the CPU.
The engine's steps by kernel and the other families bit for bit:
``tests/test_kept_residuals.py``. The kernels are interpreted here: a
CPU run shows counts and bits, never a time."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu import telemetry
from deepspeed_tpu.models import transformer
from deepspeed_tpu.ops import kda
from deepspeed_tpu.parallel.mesh import MeshTopology, TopologyConfig

from helpers.families import _telemetry_isolation  # noqa: F401
from helpers.families import flat_grads, kernel_calls, program


def _scan_inputs(b, s, h, d, gate):
    bf = jnp.bfloat16
    sd = jax.ShapeDtypeStruct
    g = sd((b, s, h, d) if gate == "channel" else (b, s, h), jnp.float32)
    return (sd((b, s, h, d), bf), sd((b, s, h, d), bf), sd((b, s, h, d), bf),
            g, sd((b, s, h), jnp.float32))


def _scan_calls(fn, *args):
    """(preparation forward, its backward, recurrence forward in either
    form, its backward): the scan's kernel calls in ``fn``'s program."""
    calls = kernel_calls(fn, *args)
    return tuple(calls[k] for k in ("ds_kda_prep_fwd", "ds_kda_prep_bwd",
                                    "ds_kda_fwd", "ds_kda_bwd"))


@pytest.mark.parametrize("form", ["alone", "per_shard"])
@pytest.mark.parametrize("gate", ["channel", "head"])
def test_a_rematted_scan_keeps_its_output(gate, form):
    """``chunk_kda`` under a checkpoint with a policy of ``_remat_policy``,
    alone and per shard of the batch on a mesh (``sharded_chunk_kda``: the
    name lies inside the ``shard_map``, where the policy still sees it):
    two forwards a backward in two head groups, three under
    ``policy=None``. The traced program holds every call ONCE whatever
    the count: the body of the one rolled loop over the groups
    (``ops/kda.py`` ``_each_group``)."""
    scan = functools.partial(kda.chunk_kda, head_groups=2)
    if form == "per_shard":
        topo = MeshTopology(TopologyConfig(fsdp=4, tp=2))
        scan = functools.partial(kda.sharded_chunk_kda(NamedSharding(
            topo.mesh, PartitionSpec(topo.batch_axes(), None, None))),
            head_groups=2)
    args = _scan_inputs(4, 128, 4, 32, gate)

    def grad(policy):
        layer = jax.checkpoint(
            lambda *a: jnp.sum(scan(*a).astype(jnp.float32) ** 2),
            policy=policy)
        return jax.grad(layer, argnums=(0, 1, 2, 3, 4))

    assert _scan_calls(grad(transformer._remat_policy("nothing_saveable")),
                       *args) == (2, 1, 2, 1)
    assert _scan_calls(grad(None), *args) == (3, 1, 3, 1)


@pytest.mark.parametrize("groups", [1, 2])
def test_the_heads_stack_is_the_scans_output_where_the_kernel_wrote_it(
        groups):
    """``chunk_kda(by_head=True)`` (ISSUE 55): ``o`` as [G, B, H / G, S,
    dv], the bits of the [B, S, H, dv] form moved, for a consumer that
    reads a head where it lies (``ops.layers.gated_norm``); the five
    gradients are the same bits, and under a layer's policy the kernels
    run as often (the name is the stack's own: nothing is moved for it)."""
    b, s, h, d = 2, 128, 4, 32
    rng = np.random.default_rng(1)
    unit = lambda x: x / np.sqrt((x ** 2).sum(-1, keepdims=True))  # noqa: E731
    bf, f32 = jnp.bfloat16, jnp.float32
    shape = (b, s, h, d)
    args = (jnp.asarray(unit(rng.normal(size=shape)) * d ** -0.5, bf),
            jnp.asarray(unit(rng.normal(size=shape)), bf),
            jnp.asarray(rng.normal(size=shape), bf),
            jnp.asarray(-np.exp(rng.uniform(-7, 0.5, shape)), f32),
            jnp.asarray(1 / (1 + np.exp(-rng.normal(size=shape[:3]))), f32))
    weight = jnp.asarray(rng.normal(size=shape), f32)
    stack = lambda x: x.reshape(  # noqa: E731
        b, s, groups, h // groups, d).transpose(2, 0, 3, 1, 4)

    def run(by_head):
        def layer(*a):
            o = kda.chunk_kda(*a, head_groups=groups, by_head=by_head)
            w = stack(weight) if by_head else weight
            return jnp.sum(jnp.tanh(o.astype(f32)) * w), o
        fn = jax.checkpoint(
            layer, policy=transformer._remat_policy("nothing_saveable"))
        grad = jax.grad(fn, argnums=(0, 1, 2, 3, 4), has_aux=True)
        return jax.device_get(jax.jit(grad)(*args)), _scan_calls(
            lambda *a: grad(*a)[0], *args)

    (want, o), calls = run(False)
    (got, o_stack), calls_stack = run(True)
    assert o_stack.shape == (groups, b, h // groups, s, d)
    np.testing.assert_array_equal(np.asarray(o_stack, np.float32),
                                  np.asarray(stack(o), np.float32))
    for name, g, w_ in zip("q k v g beta".split(), got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w_, np.float32),
                                      err_msg=name)
    assert calls_stack == calls == ((2, 1, 2, 1) if groups > 1
                                    else (3, 1, 3, 1))


def test_one_head_group_keeps_nothing():
    """With one group there is no loop and ``chunk_kda`` names nothing
    (XLA merges the layer's rerun of the preparation with the group's
    there, and a kept ``o`` cost the Qwen3-Next cell's step more than its
    one ``ds_kda_fwd``: ``ops/kda.py``): three forwards a backward under
    the models' policy too, and the gauge stays unset."""
    telemetry.configure()
    grad = jax.grad(jax.checkpoint(
        lambda *a: jnp.sum(kda.chunk_kda(*a).astype(jnp.float32) ** 2),
        policy=transformer._remat_policy("nothing_saveable")),
        argnums=(0, 1, 2, 3, 4))
    assert _scan_calls(grad, *_scan_inputs(2, 128, 4, 32, "head")) == \
        (3, 1, 3, 1)
    assert telemetry.get_registry().get("ds_kernel_kept_bytes") is None


def test_the_gauge_reads_what_the_scan_declares():
    """``ds_kernel_kept_bytes{kernel="kda"}`` is the bytes of ``o``
    [B, S, H, dv] in ``v``'s dtype, set where a differentiated
    ``chunk_kda`` is traced; a call that is not differentiated declares
    nothing."""
    b, s, h, d = 2, 128, 4, 32
    args = _scan_inputs(b, s, h, d, "channel")
    layer = jax.checkpoint(
        lambda *a: jnp.sum(kda.chunk_kda(*a, head_groups=2).astype(
            jnp.float32)),
        policy=transformer._remat_policy("nothing_saveable"))
    telemetry.configure()
    reg = telemetry.get_registry()
    jax.eval_shape(functools.partial(kda.chunk_kda, head_groups=2), *args)
    assert reg.get("ds_kernel_kept_bytes") is None
    jax.eval_shape(jax.grad(layer), *args)
    assert reg.get("ds_kernel_kept_bytes").value(kernel="kda") == \
        2 * b * s * h * d


@pytest.mark.parametrize("gate", ["channel", "head"])
def test_the_kept_scan_output_is_the_reruns_bit_for_bit(gate):
    """A rematted function of ``chunk_kda`` alone, at either gate: the five
    gradients under a policy that keeps ``o`` are those of
    ``policy=None``, bit for bit."""
    b, s, h, d = 2, 128, 4, 32
    rng = np.random.default_rng(0)
    unit = lambda x: x / np.sqrt((x ** 2).sum(-1, keepdims=True))  # noqa: E731
    bf, f32 = jnp.bfloat16, jnp.float32
    shape = (b, s, h, d)
    args = (jnp.asarray(unit(rng.normal(size=shape)) * d ** -0.5, bf),
            jnp.asarray(unit(rng.normal(size=shape)), bf),
            jnp.asarray(rng.normal(size=shape), bf),
            jnp.asarray(-np.exp(rng.uniform(
                -7, 0.5, shape if gate == "channel" else shape[:3])), f32),
            jnp.asarray(1 / (1 + np.exp(-rng.normal(size=shape[:3]))), f32))
    weight = jnp.asarray(rng.normal(size=shape), f32)

    def layer(*a):
        o = kda.chunk_kda(*a, head_groups=2)
        return jnp.sum(jnp.tanh(o.astype(f32)) * weight)

    grads = lambda policy: jax.device_get(jax.jit(jax.grad(  # noqa: E731
        jax.checkpoint(layer, policy=policy), argnums=(0, 1, 2, 3, 4)))(*args))
    kept = grads(transformer._remat_policy("nothing_saveable"))
    rerun = grads(None)
    assert all(np.any(np.asarray(g, np.float32) != 0) for g in kept)
    for name, got, want in zip("q k v g beta".split(), kept, rerun):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32),
                                      err_msg=name)


def test_kimi_linears_gradients_are_policy_nones_to_bf16_rounding():
    """Kimi-Linear cannot be held bit for bit on the CPU: XLA compiles the
    rerun of its last KDA layer's projections to other bits than the
    forward's (at PR 51's parent the ``q`` that reaches the scan already
    differs between the two, by a hash of both), so the kept ``o`` (made
    from the forward's ``q``) and the rerun's are two roundings of one
    number. The loss is the same float, and every gradient agrees to what
    bf16 resolves (2^-8 a rounding): 0.022 read at the worst leaf of the
    preset's five layers, a router's, 0.014 or less at the others; at the
    ONE KDA layer that holds the scan (the row's ``scan`` cut, ISSUE 58)
    every leaf read 0: it is the stack's LAST of several KDA layers whose
    rerun XLA rounds apart, and the limit stays the five layers'."""
    kept, rerun = (program("kimi_linear", "scan", patch=patch
                           ).loss_and_grads(1) for patch in
                   (None, "keep_nothing"))
    assert np.isfinite(kept[0]) and kept[0] == rerun[0]
    want = flat_grads(rerun[1])
    for path, got in flat_grads(kept[1]).items():
        assert np.linalg.norm(got - want[path]) <= 0.06 * np.linalg.norm(
            want[path]), path

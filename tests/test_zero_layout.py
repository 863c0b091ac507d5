"""The layer scan stays on the ZeRO plan's layout (ISSUE 28): on every
mesh of more than one device the engine pins the scan's ``[B, S, D]``
carry to the batch axes, in the forward and in its transpose, so GSPMD
gathers weights to activations and never re-partitions the MLP backward
as tensor-parallel. Host-only: jaxprs on CPU meshes, and one AOT compile
for ``v5e:2x2`` that needs no chip."""

import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.models import Llama
from deepspeed_tpu.parallel.mesh import (MeshTopology, TopologyConfig,
                                         constrain_free)
from deepspeed_tpu.utils.jax_compat import shard_map

SEQ = 16


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _pinned(jaxpr, shape, inside_scan=False):
    """The sharding constraints on a value of ``shape`` anywhere under
    ``jaxpr``, as ``(inside a scan's body, spec)``."""
    out = []
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "sharding_constraint"
                and eqn.outvars[0].aval.shape == shape):
            out.append((inside_scan, eqn.params["sharding"].spec))
        for sub in _subjaxprs(eqn):
            out += _pinned(sub, shape,
                           inside_scan or eqn.primitive.name == "scan")
    return out


def _scans_pinning(jaxpr, shape):
    """How many scans under ``jaxpr`` hold such a constraint in their
    body: the layer scan and, under ``grad``, its transpose."""
    n = 0
    for eqn in jaxpr.eqns:
        subs = list(_subjaxprs(eqn))
        if eqn.primitive.name == "scan":
            n += any(_pinned(s, shape) for s in subs)
        else:
            n += sum(_scans_pinning(s, shape) for s in subs)
    return n


def _grad_jaxpr(monkeypatch, n_devices, mesh, batch):
    """jaxpr of ``grad(engine._loss_fn)`` for a tiny Llama on the first
    ``n_devices`` CPU devices."""
    devices = jax.devices()[:n_devices]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)
    model = Llama(size="tiny", num_layers=2)
    engine, *_ = ds.initialize(model=model, config={
        "train_batch_size": 4, "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "mesh": mesh})
    assert engine.mesh.size == n_devices
    tok = jnp.zeros((batch, SEQ), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(engine._loss_fn))(
        engine.state["params"], (tok, tok)).jaxpr
    return jaxpr, (batch, SEQ, model.config.hidden_size)


def _case_mesh(monkeypatch, n_devices, mesh, batch, want):
    jaxpr, carry = _grad_jaxpr(monkeypatch, n_devices, mesh, batch)
    pins = _pinned(jaxpr, carry)
    if want is None:
        assert pins == []       # no constraint, and no error
        return
    assert all(spec == want for _, spec in pins), pins
    # the forward scan and its transpose each hold the constraint, and
    # the embedding's output is pinned before the scan
    assert _scans_pinning(jaxpr, carry) >= 2
    assert any(not inside for inside, _ in pins)


def _case_manual_region(monkeypatch):
    """Inside an enclosing manual region the rule drops what a nested
    spec may not name: under the pipeline's ``pp`` map the batch axes
    stay, under a fully manual map (ZeRO++'s) nothing is left to pin."""
    topo = MeshTopology(TopologyConfig(pp=2, fsdp=2, tp=2))
    act = topo.sharding(("pp", "fsdp"), "sp")
    x = jnp.ones((8, SEQ, 4))

    def body(x):
        return constrain_free(x, act) * 2

    part = jax.make_jaxpr(shard_map(
        body, mesh=topo.mesh, in_specs=P("pp"), out_specs=P("pp"),
        axis_names={"pp"}))(x).jaxpr
    assert [s for _, s in _pinned(part, (4, SEQ, 4))] == [P("fsdp", None)]
    full = jax.make_jaxpr(shard_map(
        body, mesh=topo.mesh, in_specs=P(("pp", "fsdp", "tp")),
        out_specs=P(("pp", "fsdp", "tp"))))(x).jaxpr
    assert _pinned(full, (1, SEQ, 4)) == []
    # and it runs: the value is unchanged by the pin
    got = jax.jit(shard_map(body, mesh=topo.mesh, in_specs=P("pp"),
                            out_specs=P("pp"), axis_names={"pp"}))(x)
    assert float(got.sum()) == 2 * x.size


def _case_aot_v5e(monkeypatch):
    """The four-chip cell's configuration at depth 2, sequence 8192,
    compiled for ``v5e:2x2`` with the loss bound as the engine binds it:
    no all-to-all on ``fsdp`` (the parent held five of 224 MiB in the MLP's
    backward), and the backward still re-gathers the layer's weights."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology description: {e}")
    from deepspeed_tpu.analysis.meshsan import (MeshSanitizer,
                                                seed_training_contract)
    from deepspeed_tpu.models import Mistral
    from deepspeed_tpu.parallel.partition import constrain, named_shardings
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.runtime.zero import ZeroShardingPlan
    from deepspeed_tpu.telemetry.collectives import analyze_hlo

    # the kernel gates read the backend: compile the Mosaic kernels
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mt = MeshTopology(TopologyConfig(fsdp=4), devices=topo.devices)
    model = Mistral(size="7b", num_layers=2, max_seq_len=8192,
                    attn_impl="flash", remat_policy="segments",
                    loss_chunk=1024)
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    plan = ZeroShardingPlan(3, mt.mesh, model.partition_rules(), abstract)
    loss = DeepSpeedEngine._configure_sequence_parallel(
        types.SimpleNamespace(topology=mt, mesh=mt.mesh, module=model,
                              model_config=model.config))

    def step(params, batch):
        l, g = jax.value_and_grad(loss)(params, batch)
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        return l, constrain(g, mt.mesh, plan.grad_specs)

    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=s),
        abstract, named_shardings(mt.mesh, plan.param_specs))
    tok = jax.ShapeDtypeStruct(
        (4, 8192), jnp.int32,
        sharding=NamedSharding(mt.mesh, P(mt.batch_axes())))
    hlo = jax.jit(step).lower(params, (tok, tok)).compile().as_text()
    records = analyze_hlo(hlo, mt.mesh)
    assert [r for r in records if r["op"] == "all_to_all"] == []
    # ZeRO-3's own traffic is still there: the transposed scan gathers
    # the FFN weights (as all-gathers, or as the ring steps of a
    # collective matmul), a quarter of [4096, 14336] bf16 a chip
    regathers = [r for r in records
                 if "transpose(jvp(ds.layers))" in r["op_name"]
                 and "ds.mlp" in r["op_name"]
                 and (r["op"] == "all_gather"
                      or r.get("implements") == "collective_matmul")]
    assert regathers and all(r["axis"] == "fsdp" for r in regathers)
    assert max(r["bytes"] for r in regathers) >= 4096 * 14336 * 2 // 4
    san = MeshSanitizer(mode="raise")
    san.declare("compiled_step", seed_training_contract(mt.sizes))
    assert san.check_records("compiled_step", records) == []


_BATCH = P("fsdp", None)    # [B(batch axes), S(sp), D]; an extent of 1 dropped

CASES = {
    "fsdp4": lambda mp: _case_mesh(mp, 4, {"fsdp": 4}, 4, _BATCH),
    "one_device": lambda mp: _case_mesh(mp, 1, {"fsdp": 1}, 4, None),
    "tp2_fsdp2": lambda mp: _case_mesh(mp, 4, {"fsdp": 2, "tp": 2}, 4,
                                       _BATCH),
    "dp2_fsdp2": lambda mp: _case_mesh(mp, 4, {"dp": 2, "fsdp": 2}, 4,
                                       P(("dp", "fsdp"), None)),
    "uneven_batch": lambda mp: _case_mesh(mp, 4, {"fsdp": 4}, 3, None),
    "manual_region": _case_manual_region,
    "aot_v5e_2x2": _case_aot_v5e,
}


@pytest.mark.parametrize("case", list(CASES))
def test_layer_scan_carry_is_pinned_to_the_batch_axes(case, monkeypatch):
    CASES[case](monkeypatch)


def test_kda_kernels_compile_for_v5e_alone_and_per_shard(monkeypatch):
    """The KDA kernel pair (PR 32) at the cell's widths, compiled by Mosaic
    for one described v5e chip, and ``chunk_kda``'s gradient on ``v5e:2x2``
    with the batch over ``fsdp``: GSPMD cannot partition a Mosaic call, so
    on a mesh the model runs the kernels per shard
    (``ops.kda.sharded_chunk_kda``). Here, and not beside the other KDA
    tests, because one process a run may describe a topology."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology description: {e}")
    from deepspeed_tpu.ops import kda
    from deepspeed_tpu.ops.pallas import kda as kernels
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf, f32 = jnp.bfloat16, jnp.float32

    # the kernels alone: a head group of the cell (8 heads x 256 chunks)
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    bh, n, c, d = 8, 256, 64, 128
    ops = (sd((bh, n, c, d), f32), sd((bh, n, c, d), bf),
           sd((bh, n, c, d), bf), sd((bh, n, c, c), bf),
           sd((bh, n, c, d), bf), sd((bh, n, d), f32))
    ck = sd((bh, n // kernels.SEG, d, d), f32)
    for name, fn, args in (
            ("ds_kda_fwd", lambda *o: kernels._forward(o, bf, states=False),
             ops),
            ("ds_kda_fwd", lambda *o: kernels._forward(o, bf, states=True),
             ops),
            ("ds_kda_bwd", lambda *a: kernels._backward(a[:6], a[6], a[7]),
             (*ops, ck, sd((bh, n, c, d), bf)))):
        hlo = jax.jit(fn).lower(*args).compile().as_text()
        assert re.search(rf"%{name}[.\w]* = .*custom-call", hlo), name
    with pytest.raises(ValueError, match="multiples of 128"):
        jax.jit(lambda *o: kernels._forward(o, bf, states=False)).lower(
            *(sd(x.shape[:-1] + (64,), x.dtype) for x in ops))

    # the whole chunk_kda, forward and backward, a sequence a chip
    mt = MeshTopology(TopologyConfig(fsdp=4), devices=topo.devices)
    act = mt.sharding(mt.batch_axes(), "sp")
    row = lambda *s, dt=bf: jax.ShapeDtypeStruct(  # noqa: E731
        s, dt, sharding=NamedSharding(
            mt.mesh, P(mt.batch_axes(), *[None] * (len(s) - 1))))
    b, s, h = 4, 2048, 4
    args = (row(b, s, h, d), row(b, s, h, d), row(b, s, h, d),
            row(b, s, h, d, dt=f32), row(b, s, h, dt=f32))
    loss = lambda fn: lambda *a: jnp.sum(  # noqa: E731
        fn(*a, head_groups=2).astype(f32))
    hlo = jax.jit(jax.grad(loss(kda.sharded_chunk_kda(act)),
                           argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile().as_text()
    for kernel in ("ds_kda_prep_fwd", "ds_kda_prep_bwd", "ds_kda_fwd",
                   "ds_kda_bwd"):
        assert re.search(rf"%{kernel}[.\w]* = ", hlo), kernel
    assert "all-to-all" not in hlo and "all-gather" not in hlo
    with pytest.raises(Exception, match="[Mm]osaic"):
        jax.jit(jax.grad(loss(kda.chunk_kda))).lower(*args).compile()


@pytest.mark.parametrize("gate", ["a_channel", "a_head", "a_head_16_keys"])
def test_kda_preparation_kernels_compile_for_v5e(gate, monkeypatch):
    """The preparation's kernel pair (PR 35) at the cells' widths (one
    sequence of 16384, a head group of 8 heads of 128, bf16 with float32
    decays), compiled by Mosaic for one described v5e chip: the float32
    products of the inverse, the transposed products of the backward and
    the blocks of the model's [B, S, H d] layout are what interpret mode
    cannot refuse. Per shard on ``v5e:2x2`` they compile in the test above.
    ``a_head`` (PR 46): Gated DeltaNet's gate, rows as beta's, at all 32
    value heads of its cell: the [C, 1] columns, the [C, C] mask.
    ``a_head_16_keys`` (PR 53): q and k at the cell's 16 key heads, as its
    layer hands them in: blocks of 4 key heads (512 lanes) beside blocks of
    8 value heads, the pair's ``dq`` and ``dk`` summed in the kernel and
    stored at the key heads' width."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology description: {e}")
    from deepspeed_tpu.ops.pallas import kda as kernels
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf, f32 = jnp.bfloat16, jnp.float32
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    b, s, h, d, c = 1, 16384, 8 if gate == "a_channel" else 32, 128, \
        kernels.CHUNK
    n = s // c
    wide = (b, s, h, d)
    keys = (b, s, 16, d) if gate == "a_head_16_keys" else wide
    ins = (sd(keys, bf), sd(keys, bf), sd(wide, bf),
           sd(wide if gate == "a_channel" else wide[:3], f32),
           sd(wide[:3], f32))
    cts = (sd((b * h, n, c, d), f32), sd((b * h, n, c, d), bf),
           sd((b * h, n, c, d), bf), sd((b * h, n, c, c), bf),
           sd((b * h, n, c, d), bf), sd((b * h, n, d), f32))
    fwd = jax.jit(lambda *a: kernels._prepare_forward(
        *kernels._prep_inputs(*a, c)))
    bwd = jax.jit(lambda *a: (lambda ins, p: kernels._prep_gradients(
        kernels._prepare_backward(ins, a[5:], p), *a[:5]))(
            *kernels._prep_inputs(*a[:5], c)))
    for name, fn, args in (("ds_kda_prep_fwd", fwd, ins),
                           ("ds_kda_prep_bwd", bwd, (*ins, *cts))):
        compiled = fn.lower(*args).compile()
        assert re.search(rf"%{name}[.\w]* = .*custom-call",
                         compiled.as_text()), name
    assert [x.shape for x in jax.eval_shape(fwd, *ins)] == [
        x.shape for x in cts]
    assert [x.shape for x in jax.eval_shape(bwd, *ins, *cts)] == [
        x.shape for x in ins]
    with pytest.raises(ValueError, match="multiples of 128"):
        fwd.lower(*(sd(x.shape[:3] + (64,) * (len(x.shape) - 3), x.dtype)
                    for x in ins))


def test_ssd_kernels_compile_for_v5e_alone_and_per_shard(monkeypatch):
    """The Mamba-2 scan's kernel pair (PR 37) at the cell's widths (one
    sequence of 8192, 64 heads of 64 in one group, state 128, chunk 256;
    and Nemotron-H's 8 groups), forward and the rematted layer's backward,
    compiled by Mosaic for one described v5e chip: the row-form running
    sums, the one-hot products that spread them, the transposed products
    and the blocks of the model's own layouts are what interpret mode
    cannot refuse. Then ``sharded_chunk_ssd`` on ``v5e:2x2`` with the batch
    over ``fsdp``, where the bare call cannot be partitioned."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology description: {e}")
    from deepspeed_tpu.ops import ssd
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf, f32 = jnp.bfloat16, jnp.float32
    one = SingleDeviceSharding(topo.devices[0])

    def shapes(b, s, h, p, g, n, place):
        sd = lambda *dims, dt=bf: jax.ShapeDtypeStruct(  # noqa: E731
            dims, dt, sharding=place(len(dims)))
        return (sd(b, s, h, p), sd(b, s, h, dt=f32), sd(h, dt=f32),
                sd(b, s, g, n), sd(b, s, g, n))

    def grad(fn, chunk):
        layer = jax.checkpoint(lambda *a: fn(*a, chunk=chunk))
        return jax.jit(jax.grad(
            lambda *a: 0.5 * jnp.sum(layer(*a).astype(f32) ** 2),
            argnums=(0, 1, 2, 3, 4)))

    for h, g in ((64, 1), (128, 8)):
        args = shapes(1, 8192 * 64 // h, h, 64, g, 128, lambda _: one)
        hlo = grad(ssd.chunk_ssd, 256).lower(*args).compile().as_text()
        for kernel, calls in (("ds_ssd_fwd", 2), ("ds_ssd_bwd", 1)):
            found = re.findall(rf"%{kernel}[.\w]* = .*custom-call", hlo)
            assert len(found) == calls, (h, g, kernel, len(found))
    with pytest.raises(ValueError, match="not 1 x 64, 128 and 256"):
        jax.jit(lambda *a: ssd.chunk_ssd(*a, chunk=256)).lower(
            *shapes(1, 1024, 8, 64, 8, 128, lambda _: one))

    mt = MeshTopology(TopologyConfig(fsdp=4), devices=topo.devices)
    act = mt.sharding(mt.batch_axes(), "sp")
    rows = lambda n: NamedSharding(  # noqa: E731
        mt.mesh, P(*([mt.batch_axes()] + [None] * (n - 1)) if n > 1
                   else P()))
    args = shapes(4, 1024, 8, 64, 1, 128, rows)
    hlo = grad(ssd.sharded_chunk_ssd(act), 256).lower(
        *args).compile().as_text()
    for kernel in ("ds_ssd_fwd", "ds_ssd_bwd"):
        assert re.search(rf"%{kernel}[.\w]* = ", hlo), kernel
    assert "all-to-all" not in hlo and "all-gather" not in hlo
    with pytest.raises(Exception, match="[Mm]osaic"):
        grad(ssd.chunk_ssd, 256).lower(*args).compile()


@pytest.mark.parametrize("experts,held,f,router_grad,k,d,want", [
    (64, 16, 896, False, 8, 2304, (768, 36864)),
    (256, 8, 1024, True, 8, 2304, (1024, 6144)),
    (512, 32, 512, False, 10, 2048, (640, 14336)),
], ids=["mellum2-12b-ep4-zero3-1chip", "kimi-linear-48b-ep32-zero3-1chip",
         "qwen3-next-80b-ep16-zero3-1chip"])
def test_grouped_matmul_kernels_compile_for_v5e(
        monkeypatch, experts, held, f, router_grad, k, d, want):
    """The held experts' kernels (PRs 41, 48) under ``held_experts_ffn`` at
    the three cells' widths (16384 tokens; top 8, hidden 2304: 16 experts
    of 896 in chunks of 36,864 rows; 8 of 1024 in chunks of 6144, the
    routing weights' gradient on; top 10, hidden 2048: 32 of 512 in chunks
    of 14,336 at a row tile of 128), a case each, forward, remat's rerun and
    backward, compiled by Mosaic for one described v5e chip: an expert's
    weights and its float32 ``dW`` blocks held in VMEM at two buffers each
    (97 MB of the chip's 128 at 1024 wide), the transposed products, the
    copy from the aliased carry, and the add's one-hot product on a tile
    of the carry that is both an input and the output are what interpret
    mode cannot refuse. No scatter is left onto a float32 [N, D], and
    XLA still serves the gather of ``x``'s rows from VMEM. A width compiles
    for 17 to 19 s alone (a minute beside five busy workers), and it is
    neither the kernels nor the tokens (PR 50): the forward kernel alone
    is 1.4 s, one ``lax.sort`` of 131,072 int32 pairs, as the sweep
    holds, alone 15.8 s of XLA's TPU compiler, and 4096, 8192 and 16384 tokens
    read 17.0, 17.4 and 19.3 s at 1024 wide, while ``held_block`` and
    ``held_chunk`` return the cells' own pairs at 16384 alone: it stays."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology description: {e}")
    from deepspeed_tpu.moe import sharded_moe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf, f32 = jnp.bfloat16, jnp.float32
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    n = 16384
    block = sharded_moe.held_block(n, k, experts)
    chunk = sharded_moe.held_chunk(n, k, experts, held, block)
    assert (block, chunk) == want
    layer = jax.checkpoint(lambda x, idx, w, ex: (
        sharded_moe.held_experts_ffn(x, idx, w, ex, 0, block,
                                     router_grad, chunk)[0]))
    grad = jax.jit(jax.grad(
        lambda x, w, ex, idx: 0.5 * jnp.sum(
            layer(x, idx, w, ex).astype(f32) ** 2), argnums=(0, 1, 2)))
    hlo = grad.lower(
        sd((n, d), bf), sd((n, k), f32),
        {"w_gate": sd((held, d, f), bf), "w_up": sd((held, d, f), bf),
         "w_down": sd((held, f, d), bf)},
        sd((n, k), jnp.int32)).compile().as_text()
    for kernel in ("ds_moe_gmm_fwd", "ds_moe_gmm_bwd", "ds_moe_add_rows"):
        assert re.search(rf"%{kernel}[.\w]* = .*custom-call", hlo), kernel
    assert not re.search(rf"= f32\[{n},{d}\]\S* scatter\(", hlo)
    # the loop still carries ``x`` in VMEM (``S(1)``) for the row gather,
    # as with XLA's scatter-add as the body's last op: an add that asked
    # for more VMEM than an XLA op gets would stop that (PR 48)
    assert router_grad or re.search(
        rf"= bf16\[{n},{d}\]\S*S\(1\)\S* get-tuple-element\(", hlo)


def test_short_conv_kernels_compile_for_v5e_and_leave_one_copy_a_tensor(
        monkeypatch):
    """The short convolution's kernel pair (PR 43) at the two cells' widths
    ([1, 16384, 4096] with the norm of 128 and without; [1, 8192, 4352]
    with a bias), forward and the rematted gradient, compiled by Mosaic for
    one described v5e chip: the rolls along the rows, the folded sums of
    ``dw`` and the products with ones are what interpret mode cannot
    refuse. Then a whole KDA mixer at the cell's widths and four head
    groups: q, k and v leave their kernels in the layout the scan's
    kernels read, and a head group is an offset in those kernels' index
    maps (ISSUE 59), so between them lies NO copy of a tensor, under
    ds.kda_scan (until PR 59 one bf16 copy a tensor each way: the split
    into head groups, its merge in the backward) or under ds.mix_pre
    (where PR 43's parent relaid each out in float32 and bf16), and the
    scan's four kernels are called as often as the groups' ONE rolled loop
    holds them (once each direction), at four groups and at one. Then ``sharded_short_conv`` on ``v5e:2x2`` with the batch over
    ``fsdp``, where the bare call cannot be partitioned."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology description: {e}")
    import numpy as np

    from deepspeed_tpu.models.kimi_linear import (KimiLinear,
                                                  kimi_linear_config)
    from deepspeed_tpu.models.transformer import _remat_policy
    from deepspeed_tpu.ops import layers as L
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf, f32 = jnp.bfloat16, jnp.float32
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, bf, sharding=one)

    def grad(fn, n=3, **kw):
        layer = jax.checkpoint(lambda *a: fn(*a, **kw))
        return jax.jit(jax.grad(
            lambda *a: 0.5 * jnp.sum(layer(*a).astype(f32) ** 2),
            argnums=tuple(range(n))))

    def calls(hlo, kernel):
        return len(re.findall(rf"%{kernel}[.\w]* = .*custom-call", hlo))

    for s, c, bias, kw in (
            (16384, 4096, False, dict(norm_width=128, norm_scale=128 ** -0.5)),
            (16384, 4096, False, {}), (8192, 4352, True, {})):
        args = (sd(1, s, c), sd(4, c)) + ((sd(c),) if bias else ())
        hlo = grad(L.short_conv, len(args), **kw).lower(
            *args).compile().as_text()
        # the first forward is dead under this loss: remat's rerun is it
        assert (calls(hlo, "ds_short_conv_fwd"),
                calls(hlo, "ds_short_conv_bwd")) == (1, 1), (s, c, kw)
    with pytest.raises(ValueError, match="multiple of 128, not 4160"):
        jax.jit(L.short_conv).lower(sd(1, 8192, 4160), sd(4, 4160))

    # a KDA mixer of the cell, forward, remat's rerun and backward
    def mixer_hlo(groups):
        model = KimiLinear(config=kimi_linear_config(
            "48b-a3b", kda_head_groups=groups, param_dtype=bf))
        p = jax.eval_shape(lambda k: model._init_layer(k, ("kda", "dense")),
                           jax.random.PRNGKey(0))["kda"]
        p = jax.tree.map(lambda x: sd(*x.shape), p)
        _, kda_fn, conv_fn, norm_fn = model._layer_fns(None, None)

        def mixer(p, h):
            with jax.named_scope("ds.kda"):
                return model._kda(p, h, kda_fn, conv_fn, norm_fn)

        return jax.jit(jax.grad(lambda p, h: jnp.sum(jax.checkpoint(
            mixer, policy=_remat_policy("nothing_saveable"))(p, h).astype(
                f32) ** 2), argnums=(0, 1))).lower(
                    p, sd(1, 16384, 2304)).compile().as_text()

    def scan_calls(hlo):
        return tuple(calls(hlo, k) for k in (
            "ds_kda_prep_fwd", "ds_kda_prep_bwd", "ds_kda_fwd",
            "ds_kda_bwd"))

    # ONE group keeps nothing and the layer's rerun runs the scan again:
    # XLA merges the rerun's preparation with the backward rule's (no loop
    # hides it), ``ds_kda_fwd`` is the forward's, the rerun's and the
    # checkpoint form
    assert scan_calls(mixer_hlo(1)) == (2, 1, 3, 1)
    hlo = mixer_hlo(4)
    # four groups: ``o`` is kept, the rerun holds no kernel of the scan;
    # the forward and the backward rule each hold their calls once, the
    # body of the rolled loop over the four
    assert scan_calls(hlo) == (2, 1, 2, 1)
    assert (calls(hlo, "ds_short_conv_fwd"),
            calls(hlo, "ds_short_conv_bwd")) == (6, 3)
    # ISSUE 55: the gated norm behind the scan is its kernel pair (the
    # forward, remat's rerun for the output matmul, one backward) and
    # ds.mix_post holds nothing else; the scan's ``o`` reaches it, and
    # ``do`` leaves it, as the heads' stack the scan's kernels write and
    # read, so no instruction moves a bf16 [., 16384, ., 128] of ``o``'s
    # size, nor of q's, k's and v's (the checks below)
    assert (calls(hlo, "ds_gated_norm_fwd"),
            calls(hlo, "ds_gated_norm_bwd")) == (2, 1)
    post = [line for line in hlo.splitlines() if "ds.mix_post" in line]
    assert len(post) == 3 + sum("get-tuple-element" in x for x in post), [
        x[:120] for x in post]
    assert not any(re.search(r"bf16\[[\d,]+\]\{[^}]*S\(1\)", x)
                   for x in post)
    assert not re.search(
        r"= bf16\[4,1,8,16384,128\]\S* (copy|transpose)\(", hlo)
    moved = {"fwd": [], "bwd": [], "mix_pre": []}
    for line in hlo.splitlines():
        m = re.match(r"\s*%[\w.\-]+ = (bf16\[[\d,]+\])\S* "
                     r"(copy|transpose)\(.*op_name=\"([^\"]*)\"", line)
        if m and m.group(1) == "bf16[1,16384,4,8,128]":
            path = m.group(3)
            moved["mix_pre" if "ds.mix_pre" in path else
                  "bwd" if "transpose(jvp" in path.split(";")[0]
                  and "rematted" not in path else "fwd"].append(path)
    # q, k, v are not split into the head groups, forward or rerun, nor
    # their cotangents merged again (6, 3, [] until PR 59)
    assert (len(moved["fwd"]), len(moved["bwd"]), moved["mix_pre"]) == (
        0, 0, []), moved
    # and nothing of q's, k's, v's, the float32 g's or o's size is copied,
    # transposed, sliced or updated in place under the scan's scope, in a
    # fusion or out of one: the kernels read and write the whole arrays
    size = 16384 * 4096
    under_scan = [
        line.strip()[:160] for line in hlo.splitlines()
        for m in [re.match(
            r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* (copy|transpose|"
            r"dynamic-slice|dynamic-update-slice)\(.*op_name=\"[^\"]*"
            r"ds\.kda_scan", line)]
        if m and np.prod([int(d) for d in m.group(1).split(",")]) >= size]
    assert not under_scan, under_scan

    mt = MeshTopology(TopologyConfig(fsdp=4), devices=topo.devices)
    act = mt.sharding(mt.batch_axes(), "sp")
    rows = NamedSharding(mt.mesh, P(mt.batch_axes(), None, None))
    args = (jax.ShapeDtypeStruct((4, 2048, 512), bf, sharding=rows),
            jax.ShapeDtypeStruct((4, 512), bf,
                                 sharding=NamedSharding(mt.mesh, P())),
            jax.ShapeDtypeStruct((512,), bf,
                                 sharding=NamedSharding(mt.mesh, P())))
    hlo = grad(L.sharded_short_conv(act), norm_width=128).lower(
        *args).compile().as_text()
    for kernel in ("ds_short_conv_fwd", "ds_short_conv_bwd"):
        assert re.search(rf"%{kernel}[.\w]* = ", hlo), kernel
    assert "all-to-all" not in hlo and "all-gather" not in hlo
    with pytest.raises(Exception, match="[Mm]osaic"):
        grad(L.short_conv, norm_width=128).lower(*args).compile()


def test_gated_norm_kernels_compile_for_v5e_alone_and_per_shard(monkeypatch):
    """The gated norm's kernel pair (ISSUE 55) at the two cells' widths,
    compiled by Mosaic for one described v5e chip in both callers' forms
    (sigmoid with a bias and the bf16-rounded norm on four head groups'
    stack; SiLU in float32 on one group's) and on ``o`` as [B, S, H, d]:
    the lane reductions, the squeezed group and head axes of the stack's
    blocks and the sums' revisited output block are what interpret mode
    cannot refuse. Neither kernel asks for more VMEM than any XLA op gets
    (as the last op of a loop's body a larger limit costs the loop XLA's
    staging: PR 48), and no operand or result is staged in VMEM (``S(1)``)
    round the calls. Then ``sharded_gated_norm`` on ``v5e:2x2`` with the
    batch over ``fsdp``, where the bare call cannot be partitioned."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology description: {e}")
    from deepspeed_tpu.ops import layers as L
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf, f32 = jnp.bfloat16, jnp.float32
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, bf, sharding=one)
    kimi = dict(act="sigmoid", eps=1e-5, round_norm=True)
    qwen = dict(act="silu", eps=1e-6)

    def grad(fn, n, **kw):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a, **kw).astype(f32)),
            argnums=tuple(range(n))))

    gate = sd(1, 16384, 4096)
    for o, bias, kw in ((sd(4, 1, 8, 16384, 128), True, kimi),
                        (sd(1, 1, 32, 16384, 128), False, qwen),
                        (sd(1, 16384, 32, 128), True, kimi)):
        args = (o, gate, sd(128)) + ((sd(4096),) if bias else ())
        hlo = grad(L.gated_norm, len(args), **kw).lower(
            *args).compile().as_text()
        found = [line for line in hlo.splitlines()
                 if re.search(r"%ds_gated_norm_(fwd|bwd)[.\w]* = .*custom-call",
                              line)]
        assert len(found) == 2, (o.shape, len(found))
        for line in found:
            # (the sums' 32 KB may live there: no [16384, .] tensor does)
            assert not re.search(r"bf16\[[\d,]+\]\{[^}]*S\(1\)", line), line[:300]
            asked = re.findall(r'"scoped_memory_configs":\[([^\]]*)\]',
                               line)[0]
            assert all(int(n) <= 16 * 2 ** 20 for n in re.findall(
                r'"size":"(\d+)"', asked)), asked

    mt = MeshTopology(TopologyConfig(fsdp=4), devices=topo.devices)
    act = mt.sharding(mt.batch_axes(), "sp")
    put = lambda spec, *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, bf, sharding=NamedSharding(mt.mesh, spec))
    rows = P(mt.batch_axes(), None, None)
    args = (put(P(mt.batch_axes(), None, None, None), 4, 2048, 4, 128),
            put(rows, 4, 2048, 512), put(P(), 128), put(P(), 512))
    hlo = grad(L.sharded_gated_norm(act), 4, **kimi).lower(
        *args).compile().as_text()
    for kernel in ("ds_gated_norm_fwd", "ds_gated_norm_bwd"):
        assert re.search(rf"%{kernel}[.\w]* = ", hlo), kernel
    assert "all-to-all" not in hlo and "all-gather" not in hlo
    with pytest.raises(Exception, match="[Mm]osaic"):
        grad(L.gated_norm, 4, **kimi).lower(*args).compile()


def test_gated_conv_kernels_and_the_widest_held_backward_compile_for_v5e(
        monkeypatch):
    """PR 54's two shapes that interpret mode cannot refuse, compiled by
    Mosaic for one described v5e chip. The gated short convolution's
    kernel pair at the LFM2 cell's widths ([2, 8192, 3 x 2048] bf16, three
    taps): three BlockSpecs read the column runs of one array, the
    backward's last grid axis writes the three runs of ONE cotangent, and
    the rematted gradient holds the forward once (the first forward is
    dead under this loss) and no copy of either operand. Then the held
    experts' backward at hidden 2048 by an expert of 1536, the widest a
    cell has: an expert's weights and float32 ``dW`` blocks at two buffers
    each asked 127.3 MiB of the 126 a kernel may have, so past
    ``_RESIDENT_MAX`` the weights take one buffer
    (``grouped_matmul.backward``); at Kimi's 2304 by 1024 they keep two.
    Then ``sharded_gated_short_conv`` on ``v5e:2x2``, where the bare call
    cannot be partitioned."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology description: {e}")
    from deepspeed_tpu.moe import sharded_moe
    from deepspeed_tpu.ops import layers as L
    from deepspeed_tpu.ops.pallas import grouped_matmul
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf, f32 = jnp.bfloat16, jnp.float32
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda s, d=bf: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731

    def grad(fn):
        layer = jax.checkpoint(fn)
        return jax.jit(jax.grad(
            lambda a, w: 0.5 * jnp.sum(layer(a, w).astype(f32) ** 2),
            argnums=(0, 1)))

    compiled = grad(L.gated_short_conv).lower(
        sd((2, 8192, 6144)), sd((3, 2048))).compile()
    hlo = compiled.as_text()
    calls = lambda k: len(re.findall(  # noqa: E731
        rf"%{k}[.\w]* = .*custom-call", hlo))
    assert (calls("ds_gated_conv_fwd"), calls("ds_gated_conv_bwd")) == (1, 1)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    with pytest.raises(ValueError, match="multiple of 128, not 2080"):
        jax.jit(L.gated_short_conv).lower(sd((2, 8192, 6240)),
                                          sd((3, 2080)))

    # the held experts of a routed layer of the cell, backward included
    n, d, f, k, experts, held = 16384, 2048, 1536, 4, 64, 8
    block = sharded_moe.held_block(n, k, experts)
    chunk = sharded_moe.held_chunk(n, k, experts, held, block)
    assert (block, grouped_matmul.row_tile(block), chunk) == (640, 128, 9216)
    layer = jax.checkpoint(lambda x, idx, w, ex: (
        sharded_moe.held_experts_ffn(x, idx, w, ex, 0, block, False,
                                     chunk)[0]))
    hlo = jax.jit(jax.grad(
        lambda x, w, ex, idx: 0.5 * jnp.sum(
            layer(x, idx, w, ex).astype(f32) ** 2), argnums=(0, 2))).lower(
        sd((n, d)), sd((n, k), f32),
        {"w_gate": sd((held, d, f)), "w_up": sd((held, d, f)),
         "w_down": sd((held, f, d))},
        sd((n, k), jnp.int32)).compile().as_text()
    for kernel in ("ds_moe_gmm_fwd", "ds_moe_gmm_bwd", "ds_moe_add_rows"):
        assert re.search(rf"%{kernel}[.\w]* = .*custom-call", hlo), kernel
    wide = 2 * 3 * d * f * (2 + 4)
    assert wide > grouped_matmul._RESIDENT_MAX > 2 * 3 * 2304 * 1024 * 6

    mt = MeshTopology(TopologyConfig(fsdp=4), devices=topo.devices)
    act = mt.sharding(mt.batch_axes(), "sp")
    args = (jax.ShapeDtypeStruct(
        (4, 2048, 768), bf,
        sharding=NamedSharding(mt.mesh, P(mt.batch_axes(), None, None))),
            jax.ShapeDtypeStruct((3, 256), bf,
                                 sharding=NamedSharding(mt.mesh, P())))
    hlo = grad(L.sharded_gated_short_conv(act)).lower(
        *args).compile().as_text()
    for kernel in ("ds_gated_conv_fwd", "ds_gated_conv_bwd"):
        assert re.search(rf"%{kernel}[.\w]* = ", hlo), kernel
    assert "all-to-all" not in hlo and "all-gather" not in hlo
    with pytest.raises(Exception, match="[Mm]osaic"):
        grad(L.gated_short_conv).lower(*args).compile()


def test_mhc_kernels_and_the_cut_held_backward_compile_for_v5e(monkeypatch):
    """PR 56's and PR 57's shapes that interpret mode cannot refuse,
    compiled by Mosaic for one described v5e chip. The stream passes of
    ``ops/mhc.py`` at the Xing4 cell's widths (8192 tokens of 4 streams of
    3584 bf16, 24 coefficients a token in one 128-lane row): a row tile of
    [256, 14336], the masked lane sums that read a coefficient's column,
    the transposed products of the pre pass's backward and its ``dphi``,
    ``db`` and ``dalpha`` blocks summed across the grid; the coefficient
    kernels' transposes of a [128, 128] float32 tile and their reads with a
    sublane stride (a token tile as [8, 128] an entry). A rematted gradient
    holds the pre pass and the coefficients' forward twice (the forward and
    remat's rerun), the post pass once (its rerun's output is dead: the
    backward needs X, y and the coefficients alone) and each backward
    kernel once. PR 57: ``X`` has ONE consumer, so no ``add`` of two
    cotangents of the streams is left (``ds_mhc_pre_bwd`` takes the post
    pass's ``dX`` in, aliased to its own); under ``ds.mhc_coef`` lie the
    two custom calls and nothing of XLA's; and the rows that pass from
    kernel to kernel bring no copy staged through VMEM that PR 56's text
    did not have (59 there, among 6068 instructions; 2 among 266 now). Then the
    held experts' backward at
    hidden 3584 by an expert of 1024, cut into two column runs
    (``grouped_matmul.backward_geometry``): the second grid axis, the
    column blocks of ``dW`` and the copy from the aliased carry by
    columns."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology description: {e}")
    from deepspeed_tpu.moe import sharded_moe
    from deepspeed_tpu.ops import mhc
    from deepspeed_tpu.ops.pallas import grouped_matmul
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf, f32 = jnp.bfloat16, jnp.float32
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda s, d=bf: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    s, n, c = 8192, 4, 3584

    def sublayer(x, phi, b, alpha):
        u, h_post, h_res, _, on = mhc.mhc_pre(x, phi, b, alpha)
        return mhc.mhc_post(on, u * 0.5, h_post, h_res)

    layer = jax.checkpoint(sublayer)
    hlo = jax.jit(jax.grad(
        lambda *a: 0.5 * jnp.sum(layer(*a).astype(f32) ** 2),
        argnums=(0, 1, 2, 3))).lower(
        sd((1, s, n, c)), sd((n * c, 24)), sd((24,)), sd((3,))
    ).compile().as_text()
    calls = lambda k: len(re.findall(  # noqa: E731
        rf"%{k}[.\w]* = .*custom-call", hlo))
    assert [calls(f"ds_mhc_{k}") for k in (
        "pre_fwd", "pre_bwd", "coef_fwd", "coef_bwd", "post_fwd",
        "post_bwd")] == [2, 1, 2, 1, 1, 1]
    assert not re.search(r"= bf16\[(?:1,)?8192,14336\]\S* add\(", hlo)
    under_coef = [line for line in hlo.splitlines()
                  if re.search(r'op_name="[^"]*ds\.mhc_coef\b', line)
                  and " get-tuple-element(" not in line]
    assert len(under_coef) == 3 and all(
        re.search(r"%ds_mhc_coef_(?:fwd|bwd)[.\w]* = .*custom-call", line)
        for line in under_coef), under_coef
    # PR 56's text: 59 copies into VMEM and 188 asynchronous ones (the
    # [4, 8192] pieces of XLA's Sinkhorn), 2 and 9 now
    assert len(re.findall(r"S\(1\)\S* copy\(", hlo)) <= 59
    assert hlo.count(" copy-start(") <= 188
    assert len(re.findall(r"^\s*(?:ROOT )?%[\w.\-]+ = ", hlo, re.M)) < 600
    with pytest.raises(ValueError, match="multiple of 128"):
        jax.jit(sublayer).lower(sd((1, s, n, 3600)), sd((n * 3600, 24)),
                                sd((24,)), sd((3,)))

    tokens, d, f, k, experts, held = 8192, 3584, 1024, 4, 64, 8
    block = sharded_moe.held_block(tokens, k, experts)
    chunk = sharded_moe.held_chunk(tokens, k, experts, held, block)
    tile = grouped_matmul.row_tile(block)
    assert grouped_matmul.backward_geometry(d, f, tile, 2)[0] == 2
    routed = jax.checkpoint(lambda x, idx, w, ex: (
        sharded_moe.held_experts_ffn(x, idx, w, ex, 0, block, True,
                                     chunk)[0]))
    hlo = jax.jit(jax.grad(
        lambda x, w, ex, idx: 0.5 * jnp.sum(
            routed(x, idx, w, ex).astype(f32) ** 2), argnums=(0, 1, 2))
    ).lower(
        sd((tokens, d)), sd((tokens, k), f32),
        {"w_gate": sd((held, d, f)), "w_up": sd((held, d, f)),
         "w_down": sd((held, f, d))},
        sd((tokens, k), jnp.int32)).compile().as_text()
    for kernel in ("ds_moe_gmm_fwd", "ds_moe_gmm_bwd", "ds_moe_add_rows"):
        assert re.search(rf"%{kernel}[.\w]* = .*custom-call", hlo), kernel


@pytest.mark.parametrize("s,heads,kv,window", [
    (8192, 72, 8, 512), (8192, 48, 8, None), (16384, 32, 4, 1024)])
def test_flash_kernels_compile_for_v5e_at_the_laguna_cells_shapes(
        monkeypatch, s, heads, kv, window):
    """Shapes compiled by Mosaic for one described v5e chip. PR 60's: 8192
    tokens of 72 query heads over 8 key heads (9 a key head) with a window
    EQUAL to the kernel's block, and of 48 over 8 causal. PR 61: at a
    window of at most two blocks both kernels run the BAND (a group of
    rows against one span, `band_rows`), which sweeps 5,242,880 pairs a
    head for the 4,063,488 the mask leaves live where the loops' 31 masked
    tiles swept 8,126,464; the Mellum cell's window layers (16384 tokens,
    32 query heads over 4, window 1024: two blocks) are the band's widest
    span. Forward, remat's kept residuals and the one-pass backward with
    its sum of dk and dv over a group's query heads, under
    ``vmem_limit_bytes`` as it stands."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology description: {e}")
    import importlib
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.bfloat16, sharding=one)
    d = 128
    block = fa._block(s)
    assert block == 512
    if window is None:
        counts = fa.tile_counts(s, block, window, True)
        assert counts["skipped"] == 0 and counts["unmasked"] == 120
        assert fa.band_rows(block, window, True) is None
    else:
        assert fa.pair_counts(s, block, window, True) == {
            512: {"swept": 5_242_880, "live": 4_063_488},
            1024: {"swept": 18_874_368, "live": 16_253_440}}[window]
    from deepspeed_tpu.models.transformer import _remat_policy
    layer = jax.checkpoint(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           window=window),
        policy=_remat_policy("nothing_saveable"))
    hlo = jax.jit(jax.grad(
        lambda q, k, v: 0.5 * jnp.sum(
            layer(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2))
    ).lower(sd((1, s, heads, d)), sd((1, s, kv, d)),
            sd((1, s, kv, d))).compile().as_text()
    for kernel in ("ds_flash_fwd", "ds_flash_bwd"):
        assert len(re.findall(rf"%{kernel}[.\w]* = .*custom-call",
                              hlo)) == 1, kernel


@pytest.mark.parametrize("d,dv", [(64, 64), (128, 128), (192, 128),
                                  (256, 256)])
def test_the_flash_kernels_compile_for_v5e_at_their_residency_cap(
        monkeypatch, d, dv):
    """ISSUE 64: ``_resident_max_seq`` admits nothing that does not
    compile. The backward (what holds most of a row: q, do and the float32
    dq slab at PADDED lanes, two buffers each) and the forward at the
    longest row of whole 512-row blocks at or under the cap, two (batch x
    head) rows, bf16, for one described v5e chip; and the row the rule
    before it admitted at a head of 64 (65536: s x d <= 32768 x 128,
    unpadded) is refused by Mosaic, so it is two spans now."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology description: {e}")
    import importlib
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda s, t=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        s, t, sharding=one)
    sc = d ** -0.5

    def compiles(s):
        jax.jit(lambda q, k, v: fa._flash_fwd(
            q, k, v, causal=True, sc=sc)).lower(
                sd((2, s, d)), sd((2, s, d)), sd((2, s, dv))).compile()
        jax.jit(lambda q, k, v, o, lse, do: fa._flash_bwd(
            q, k, v, o, lse, do, causal=True, sc=sc)).lower(
                sd((2, s, d)), sd((2, s, d)), sd((2, s, dv)),
                sd((2, s, dv)), sd((2, 1, s), jnp.float32),
                sd((2, s, dv))).compile()

    cap = fa._resident_max_seq(d, dv)
    assert fa.segments(cap // 512 * 512, d, dv) == 1
    compiles(cap // 512 * 512)
    if d == 64:
        assert fa.segments(65536, d, dv) == 2
        with pytest.raises(Exception, match="vmem"):
            compiles(65536)


def test_a_latent_layer_at_32768_compiles_for_v5e_in_two_spans(monkeypatch):
    """The new cell's attention call (ISSUE 64): 32768 tokens of 32 heads
    at a key of 192 and a value of 128, a row longer than the backward
    holds (27594), under the cells' whole-layer checkpoint, forward and the
    three gradients, compiled by Mosaic for one described v5e chip: three
    calls of each kernel (the pairs of two causal spans), no other kernel,
    and the merge under its scope."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology description: {e}")
    import importlib
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.bfloat16, sharding=one)
    s, heads = 32768, 32
    assert fa.segments(s, 192, 128) == 2
    from deepspeed_tpu.models.transformer import _remat_policy
    layer = jax.checkpoint(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
        policy=_remat_policy("nothing_saveable"))
    hlo = jax.jit(jax.grad(
        lambda q, k, v: 0.5 * jnp.sum(
            layer(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2))
    ).lower(sd((1, s, heads, 192)), sd((1, s, heads, 192)),
            sd((1, s, heads, 128))).compile().as_text()
    for kernel in ("ds_flash_fwd", "ds_flash_bwd"):
        assert len(re.findall(rf"%{kernel}[.\w]* = .*custom-call",
                              hlo)) == 3, kernel
    assert "ds.flash_merge" in hlo
    assert len(re.findall(r"= .*custom-call\(.*tpu_custom_call", hlo)) == 6


@pytest.mark.parametrize("s,heads,kv,d,rot", [
    (8192, 72, 8, 128, 128),    # the Laguna cell's window layers
    (8192, 48, 8, 128, 64),     # its full layer: half the head rotated
    (16384, 32, 4, 128, 128),   # the Mellum cell
    (16384, 16, 2, 256, 64),    # the Qwen3-Next cell: a head of two tiles
])
def test_rope_kernels_compile_for_v5e_alone_and_per_shard(
        monkeypatch, s, heads, kv, d, rot):
    """The rotation's kernel pair (ISSUE 62) at four cells' widths, compiled
    by Mosaic for one described v5e chip inside a rematted flash layer: the
    lane rolls, the selects of a partial rotation, a head's column run of
    the projection's [1, S, H D] and the heads' stack on the other side are
    what interpret mode cannot refuse. The step holds each kernel for q and
    for k: twice forward (remat's rerun makes the flash kernels' q and k
    again; ``o`` and ``lse`` are kept), once backward. Neither asks for more VMEM
    than any XLA op gets, and no transpose or copy of q's size is left
    beside them. Then ``sharded_flash_attention`` on ``v5e:2x2`` with the
    batch over ``fsdp`` and the tables whole on every shard."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology description: {e}")
    from deepspeed_tpu.models.transformer import _remat_policy
    from deepspeed_tpu.ops import layers as L
    from deepspeed_tpu.ops.pallas.flash_attention import (
        flash_attention, sharded_flash_attention)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf, f32 = jnp.bfloat16, jnp.float32
    tables = L.rotary_tables(*L.rotary_embedding(s, rot), d)
    assert tables.wide is not None

    def step(attn, b, *shardings):
        """The gradients of a rematted layer's projections-to-attention
        part: q and k reach the kernels as a matmul's [B, S, H D]."""
        def layer(x, wq, wk, v):
            q = (x @ wq).reshape(b, s, heads, d)
            k = (x @ wk).reshape(b, s, kv, d)
            return L.rotary_attention(attn, q, k, v, tables, causal=True)
        layer = jax.checkpoint(layer,
                               policy=_remat_policy("nothing_saveable"))
        shapes = ((b, s, 256), (256, heads * d), (256, kv * d),
                  (b, s, kv, d))
        return jax.jit(jax.grad(
            lambda *a: 0.5 * jnp.sum(layer(*a).astype(f32) ** 2),
            argnums=(0, 1, 2, 3))).lower(*(
                jax.ShapeDtypeStruct(dims, bf, sharding=sh)
                for dims, sh in zip(shapes, shardings)))

    one = SingleDeviceSharding(topo.devices[0])
    hlo = step(flash_attention, 1, one, one, one, one).compile().as_text()
    for kernel, n in (("ds_rope_fwd", 4), ("ds_rope_bwd", 2)):
        found = [line for line in hlo.splitlines() if re.search(
            rf"%{kernel}[.\w]* = .*custom-call", line)]
        assert len(found) == n, (kernel, len(found))
        for line in found:
            asked = re.findall(r'"scoped_memory_configs":\[([^\]]*)\]',
                               line)[0]
            assert all(int(m) <= 16 * 2 ** 20 for m in re.findall(
                r'"size":"(\d+)"', asked)), asked
    # q is laid out for the flash kernels by the pair alone
    assert not re.search(
        rf"= bf16\[(1,)?{heads},{s},{d}\]\S* (copy|transpose)\(", hlo)
    assert not re.search(
        rf"= bf16\[1,{s},{heads},{d}\]\S* (copy|transpose)\(", hlo)

    mt = MeshTopology(TopologyConfig(fsdp=4), devices=topo.devices)
    rows = NamedSharding(mt.mesh, P(mt.batch_axes()))
    whole = NamedSharding(mt.mesh, P())
    sharded = sharded_flash_attention(mt.mesh, mt.batch_axes())
    hlo = step(sharded, 4, rows, whole, whole, rows).compile().as_text()
    for kernel in ("ds_rope_fwd", "ds_rope_bwd"):
        assert re.search(rf"%{kernel}[.\w]* = ", hlo), kernel
    assert "all-to-all" not in hlo


@pytest.mark.parametrize("s,form", [
    (32768, "pairs"),       # the Kanana cell: interleaved pairs, two spans
    (8192, "halves"),       # the Xing4.0 cell
    (16384, "none"),        # the Kimi cell's latent layer: nothing rotated
])
def test_latent_kernels_compile_for_v5e_alone_and_per_shard(
        monkeypatch, s, form):
    """Latent attention's operands as ONE pass (ISSUE 65) at the three
    cells' shapes, 32 heads of 128 + 64 over a value of 128, compiled by
    Mosaic for one described v5e chip inside a rematted flash layer: the
    tiles of two heads cut by selects and 32-bit lane rolls of bf16 rows,
    the 64 lanes at a head's tail loaded and stored alone, the shared key's
    float32 scratch. The step holds ``ds_latent_fwd`` twice (remat's rerun
    makes the flash kernels' q, k, v again) and ``ds_latent_bwd`` once,
    neither asks for more VMEM than any XLA op gets, and no copy or
    transpose of q's, k's or v's size is left beside them. Then per shard
    on ``v5e:2x2`` with the batch over ``fsdp``."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology description: {e}")
    from deepspeed_tpu.models.transformer import _remat_policy
    from deepspeed_tpu.ops import layers as L
    from deepspeed_tpu.ops.pallas.flash_attention import (
        flash_attention, sharded_flash_attention)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf, f32 = jnp.bfloat16, jnp.float32
    heads, nope, rope, dv = 32, 128, 64, 128
    tables = None if form == "none" else L.latent_rotary_tables(
        *L.rotary_embedding(s, rope), pairs=form == "pairs")

    def step(attn, b, *shardings):
        """The gradients of a rematted layer's projections-to-attention
        part: the three reach the kernels as matmuls' outputs."""
        def layer(x, wq, wkv, wpe):
            q = (x @ wq).reshape(b, s, heads, nope + rope)
            kv = (x @ wkv).reshape(b, s, heads, nope + dv)
            assert L.hands_latent(attn, q, kv, x @ wpe, tables)
            return L.latent_attention(attn, q, kv, x @ wpe, tables,
                                      pairs=form == "pairs", causal=True)
        layer = jax.checkpoint(layer,
                               policy=_remat_policy("nothing_saveable"))
        shapes = ((b, s, 256), (256, heads * (nope + rope)),
                  (256, heads * (nope + dv)), (256, rope))
        return jax.jit(jax.grad(
            lambda *a: 0.5 * jnp.sum(layer(*a).astype(f32) ** 2),
            argnums=(0, 1, 2, 3))).lower(*(
                jax.ShapeDtypeStruct(dims, bf, sharding=sh)
                for dims, sh in zip(shapes, shardings)))

    one = SingleDeviceSharding(topo.devices[0])
    hlo = step(flash_attention, 1, one, one, one, one).compile().as_text()
    for kernel, n in (("ds_latent_fwd", 2), ("ds_latent_bwd", 1)):
        found = [line for line in hlo.splitlines() if re.search(
            rf"%{kernel}[.\w]* = .*custom-call", line)]
        assert len(found) == n, (kernel, len(found))
        for line in found:
            asked = re.findall(r'"scoped_memory_configs":\[([^\]]*)\]',
                               line)[0]
            assert all(int(m) <= 16 * 2 ** 20 for m in re.findall(
                r'"size":"(\d+)"', asked)), asked
    # q, k and v are laid out for the flash kernels by the pass alone
    for width in (nope + rope, dv):
        assert not re.search(
            rf"= bf16\[(1,)?{heads},{s},{width}\]\S* (copy|transpose)\(",
            hlo), width
        assert not re.search(
            rf"= bf16\[1,{s},{heads},{width}\]\S* (copy|transpose)\(", hlo)

    mt = MeshTopology(TopologyConfig(fsdp=4), devices=topo.devices)
    rows = NamedSharding(mt.mesh, P(mt.batch_axes()))
    whole = NamedSharding(mt.mesh, P())
    sharded = sharded_flash_attention(mt.mesh, mt.batch_axes())
    hlo = step(sharded, 4, rows, whole, whole, whole).compile().as_text()
    for kernel in ("ds_latent_fwd", "ds_latent_bwd"):
        assert re.search(rf"%{kernel}[.\w]* = ", hlo), kernel
    assert "all-to-all" not in hlo


def test_router_kernels_compile_for_v5e_with_no_sort_gather_or_scatter(
        monkeypatch):
    """The router ALONE at the Nemotron cell's shape (ISSUE 67: 8192 tokens
    of 4096 over 512 experts, top 22, sigmoid with a selection bias,
    renormalised and scaled), value and gradient, compiled by Mosaic for
    one described v5e chip: the text holds ``ds_router_fwd`` and
    ``ds_router_bwd`` once each, no ``sort``, ``gather`` or ``scatter``
    (``lax.top_k``'s full sort, the gather of 180,224 single scores, the
    ``bincount``'s scatter-add and the gather's transpose: 37 ms of the
    cell's 306), the scores reach the kernel experts-first with no copy
    (the matmul writes that layout), and neither kernel asks for more VMEM
    than any XLA op gets (an op of the layer scan's body: PR 48). What XLA
    stages in VMEM round the calls is its own choice at that limit and is
    not held here."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology description: {e}")
    from deepspeed_tpu.moe import sharded_moe
    from deepspeed_tpu.ops.pallas import router
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    f32 = jnp.float32
    tokens, hidden, experts, k = 8192, 4096, 512, 22
    assert router.fits(tokens, experts, k)
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda dims, dt=f32: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dt, sharding=one)

    def loss(x, w, bias, ct):
        with jax.named_scope("ds.moe_router"):
            logits = jnp.matmul(x, w, preferred_element_type=f32)
            idx, weights, _, load = sharded_moe.sigmoid_top_k(
                logits, bias, k, scaling=2.5)
        return jnp.sum(weights * ct), (idx, load)

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).lower(
        sd((tokens, hidden), jnp.bfloat16), sd((hidden, experts)),
        sd((experts,)), sd((tokens, k))).compile().as_text()
    for kernel in ("ds_router_fwd", "ds_router_bwd"):
        found = [line for line in hlo.splitlines() if re.search(
            rf"%{kernel}[.\w]* = .*custom-call", line)]
        assert len(found) == 1, (kernel, len(found))
        asked = re.findall(r'"scoped_memory_configs":\[([^\]]*)\]',
                           found[0])[0]
        assert all(int(m) <= 16 * 2 ** 20 for m in re.findall(
            r'"size":"(\d+)"', asked)), asked
    for op in ("sort", "gather", "scatter"):
        assert not re.search(rf" {op}\(", hlo), op
    # the scores are handed over experts-first: no copy of a [N, E] tensor
    assert not re.search(
        rf"= f32\[({tokens},{experts}|{experts},{tokens})\]\S* "
        r"(copy|transpose)\(", hlo)

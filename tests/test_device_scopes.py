"""Device scopes, set-up phase spans and compile seconds (ISSUE 24): the
names the program gives to what runs on the chip, checked on the CPU at
the ``tiny`` preset. A CPU run shows names and counts, never a time."""

import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu import telemetry
from deepspeed_tpu.models.base import get_model_class
from deepspeed_tpu.telemetry import scopes


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    telemetry.shutdown()
    yield
    telemetry.shutdown()


def _tiny_engine(extra=None):
    """The benchmark's training configuration at the tiny preset: flash
    attention (interpreted), segment remat, chunked loss, ZeRO-3 bf16
    over the 8 virtual devices (so the flash kernels sit in a
    shard_map)."""
    model = get_model_class("mistral")(
        size="tiny", max_seq_len=128, num_layers=2, attn_impl="flash",
        remat_policy="segments", loss_chunk=64)
    cfg = {"train_batch_size": 8, "bf16": {"enabled": True},
           "zero_optimization": {"stage": 3},
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
           "gradient_clipping": 1.0, "mesh": {"fsdp": -1},
           "steps_per_print": 10 ** 9}
    cfg.update(extra or {})
    engine, *_ = ds.initialize(model=model, config=cfg)
    rng = np.random.default_rng(0)
    tok = rng.integers(0, model.config.vocab_size, (8, 129))
    return engine, (tok[:, :-1], tok[:, 1:])


@pytest.fixture(scope="module")
def step_hlo():
    engine, batch = _tiny_engine()
    batch = engine._put_batch(batch)
    return engine._train_step.lower(engine.state, batch).compile().as_text()


# ---- op_name -> scope path ------------------------------------------------
@pytest.mark.parametrize("op_name,path", [
    ("jit(train_step)/jvp(ds.layers)/while/body/closed_call/ds.attn/mul",
     "fwd:ds.layers/ds.attn"),
    ("jit(train_step)/transpose(jvp(ds.layers))/while/body/ds.mlp/dot",
     "bwd:ds.layers/ds.mlp"),
    # remat's recomputation runs in the backward pass
    ("jit(train_step)/jvp(ds.layers)/checkpoint/rematted_computation/"
     "ds.attn/ds.flash_fwd/pallas_call",
     "bwd:ds.layers/ds.attn/ds.flash_fwd"),
    ("jit(train_step)/transpose(jvp(ds.loss_head))/while/body/dot_general",
     "bwd:ds.loss_head"),
    # the chunked head's custom_vjp: its forward rule holds the gradient's
    # matmuls, its backward rule scales what the forward left
    ("jit(train_step)/jvp(ds.loss_head)/while/body/closed_call/"
     "bcd,bcv->dv/dot_general", "fwd:ds.loss_head"),
    ("jit(train_step)/jvp(ds.loss_head)/while/body/closed_call/"
     "bcv,dv->bcd/dot_general", "fwd:ds.loss_head"),
    ("jit(train_step)/transpose(jvp(ds.loss_head))/mul",
     "bwd:ds.loss_head"),
    ("jit(train_step)/ds.optimizer/ds.grad_clip/reduce_sum",
     "ds.optimizer/ds.grad_clip"),
    ("jit(train_step)/jvp(ds.embed)/jit(_take)/gather", "fwd:ds.embed"),
    ("jit(train_step)/jit(_where)/select_n", ""),
])
def test_scope_of(op_name, path):
    assert scopes.scope_of(op_name) == path


def test_an_unnamed_instruction_takes_its_holders_scope():
    hlo = """
%body (p: f32[4]) -> f32[4] {
  %p = f32[4] parameter(0)
  %copy.1 = f32[4] copy(%p)
  ROOT %mul.1 = f32[4] multiply(%copy.1, %copy.1), metadata={op_name="ds.attn/mul"}
}

%fused (q: f32[4]) -> f32[4] {
  %q = f32[4] parameter(0)
  ROOT %neg.1 = f32[4] negate(%q), metadata={op_name="jit(f)/ds.optimizer/neg"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4] parameter(0)
  %while.1 = f32[4] while(%x), condition=%cond, body=%body, metadata={op_name="jit(f)/transpose(jvp(ds.layers))/while"}
  ROOT %fusion.1 = f32[4] fusion(%while.1), kind=kLoop, calls=%fused
}
"""
    got = scopes.op_scopes(hlo)
    assert got["while.1"] == "bwd:ds.layers"
    assert got["copy.1"] == "bwd:ds.layers"             # no metadata
    assert got["mul.1"] == "bwd:ds.layers/ds.attn"      # cut loose, rejoined
    assert got["fusion.1"] == "ds.optimizer"            # the root's scope
    assert got["x"] == ""


def test_an_unnamed_instruction_nothing_holds_stays_under_no_scope():
    """What the compiler puts into the entry computation for a layer that
    is unrolled there (relayout and asynchronous copies with no metadata,
    or an argument's name: 4.8 ms a step in the Granite cell, PR 34) is
    counted under no scope, and ``unscoped_ms.*`` says how much it is:
    naming it after its user is a ``tracing`` PR's to decide, for every
    cell at once."""
    hlo = """
%fused (q: f32[4]) -> f32[4] {
  %q = f32[4] parameter(0)
  ROOT %neg.1 = f32[4] negate(%q), metadata={op_name="jit(f)/ds.optimizer/neg"}
}

ENTRY %main (x: f32[4], w: f32[4]) -> (f32[4], f32[4]) {
  %x = f32[4] parameter(0)
  %w = f32[4] parameter(1), metadata={op_name="state['w']"}
  %copy.7 = f32[4] copy(%w), metadata={op_name="state['w']"}
  %copy-start.1 = (f32[4], f32[4], u32[]) copy-start(%copy.7)
  %copy-done.1 = f32[4] copy-done(%copy-start.1)
  %mul.2 = f32[4] multiply(%x, %copy-done.1), metadata={op_name="jit(f)/jvp(ds.layers)/ds.mamba/mul"}
  %fusion.2 = f32[4] fusion(%mul.2), kind=kLoop, calls=%fused
  %copy.9 = f32[4] copy(%fusion.2)
  ROOT %tuple.1 = (f32[4], f32[4]) tuple(%copy.9, %mul.2)
}
"""
    got = scopes.op_scopes(hlo)
    for name in ("copy.7", "copy-start.1", "copy-done.1", "copy.9"):
        assert got[name] == "", name
    assert got["mul.2"] == "fwd:ds.layers/ds.mamba"
    assert got["fusion.2"] == "ds.optimizer"    # a fusion has its root's


# ---- the compiled train step at the tiny preset ----------------------------
def test_every_instruction_of_the_train_step_is_scoped_or_counted(step_hlo):
    got = scopes.op_scopes(step_hlo)
    names = set(re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", step_hlo,
                           re.M))
    assert names and names == set(got)
    known = set(scopes.DEVICE_SCOPES)
    paths = {p for p in got.values() if p}
    for p in paths:
        direction, _, path = p.rpartition(":")
        assert direction in ("", "fwd", "bwd"), p
        parts = path.split("/")
        assert set(parts) <= known, p
        # a scoped instruction lies in one of the disjoint parts of a step
        assert parts[0] in scopes.TOP_SCOPES, p
    for scope in ("ds.layers", "ds.loss_head"):
        assert any(p.startswith(f"fwd:{scope}") for p in paths), scope
        assert any(p.startswith(f"bwd:{scope}") for p in paths), scope
    assert any(p.startswith("ds.optimizer") for p in paths)
    # the optimizer is not differentiated: it has no direction
    assert not any(re.match(r"(fwd|bwd):ds\.optimizer", p) for p in paths)
    unscoped = sum(1 for p in got.values() if not p)
    assert 0 < unscoped < len(got)      # parameters at least; counted


def test_every_vocab_sized_dot_is_the_loss_heads(step_hlo):
    """The head's matmuls resolve to ds.loss_head, forward or backward,
    so unscoped_ms.train cannot take the head's time: x_c @ W and dW
    carry a vocabulary dimension, dX_c = dlogits @ W^T contracts it."""
    got = scopes.op_scopes(step_hlo)
    vocab = str(get_model_class("mistral")(size="tiny").config.vocab_size)
    dots = {m.group(1): m.group(2).split(",") for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
        r"(?:dot|convolution)\(", step_hlo, re.M)}
    head = {n for n in dots if got[n].endswith(":ds.loss_head")}
    assert len(head) == 3, head
    vocab_sized = {n for n, dims in dots.items() if vocab in dims}
    assert len(vocab_sized) == 2 and vocab_sized <= head


def test_remat_counts_as_backward(step_hlo):
    got = scopes.op_scopes(step_hlo)
    remat = [m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*rematted_computation", step_hlo,
        re.M)]
    assert remat, "segment remat left no rematted_computation in the HLO"
    assert all(got[n].startswith("bwd:") for n in remat)


def test_scope_list_equals_the_scopes_found(step_hlo):
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', step_hlo):
        found.update(re.findall(r"ds\.[A-Za-z0-9_]+", op_name))
    assert found == set(scopes.DEVICE_SCOPES)
    assert set(scopes.TOP_SCOPES) <= set(scopes.DEVICE_SCOPES)


def test_the_eager_apply_program_carries_the_optimizer_scopes():
    """``step()`` of the forward/backward/step triple runs the compiled
    step's ``update . finish`` (engine._step_parts), so the same scopes
    name the same operations there: everything traced from the unscale
    to the new parameters is under ds.optimizer, the norm and the clip
    under ds.grad_clip. What is left outside is the next loss scale, the
    step counter and the metrics: scalars. fp16, so that the overflow
    bit and the skip are in the program."""
    engine, _ = _tiny_engine({"bf16": {"enabled": False},
                              "fp16": {"enabled": True}})
    grads = jax.tree.map(
        lambda p, s: jax.ShapeDtypeStruct(p.shape, jnp.float32, sharding=s),
        engine.state["params"], engine.grad_shardings)
    apply = engine._build_apply_grads()
    hlo = apply.lower(engine.state, grads).compile().as_text()
    got = scopes.op_scopes(hlo)
    assert set(got.values()) == {"", "ds.optimizer",
                                 "ds.optimizer/ds.grad_clip"}
    # instructions the trace made (they carry an op_name; a parameter's
    # is its argument's name)
    traced = re.findall(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* (?!parameter)"
        r".*op_name=", hlo, re.M)
    assert len(traced) > 100
    outside = [(n, dims) for n, dims in traced if not got[n]]
    assert outside and all(dims == "" for _, dims in outside), outside


# ---- kernel names ---------------------------------------------------------
def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


@pytest.mark.parametrize("direction,name", [("fwd", "ds_flash_fwd"),
                                            ("bwd", "ds_flash_bwd")])
def test_flash_pallas_calls_carry_their_names(direction, name):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    q = jnp.ones((1, 128, 4, 16), jnp.float32)
    k = v = jnp.ones((1, 128, 2, 16), jnp.float32)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True).sum()

    fn = fwd if direction == "fwd" else jax.grad(fwd, argnums=(0, 1, 2))
    names = _pallas_names(jax.make_jaxpr(fn)(q, k, v).jaxpr, [])
    assert name in names, names


# ---- set-up phase spans, first_step, step_boundary -------------------------
def test_setup_spans_appear_once_and_nest():
    engine, batch = _tiny_engine({"telemetry": {"enabled": True}})
    for _ in range(2):
        engine.train_batch(batch).block_until_ready()
    tracer = telemetry.get_tracer()
    totals = tracer.totals()
    for name in ("init/topology", "init/state", "init/build_step",
                 "first_step"):
        assert totals[name][1] == 1, (name, totals.get(name))
    assert totals["step_boundary"][1] == 2
    assert totals["train_batch"][1] == 2
    by_name = {}
    for s in tracer.spans():
        by_name.setdefault(s.name, []).append(s)
    # the init phases follow one another at the top level
    init = [by_name[n][0] for n in ("init/topology", "init/state",
                                    "init/build_step")]
    assert all(s.depth == 0 for s in init)
    assert all(a.ts_us + a.dur_us <= b.ts_us for a, b in zip(init, init[1:]))
    # first_step lies inside the first compiled_step, inside train_batch
    first, step0 = by_name["first_step"][0], by_name["compiled_step"][0]
    assert first.depth == step0.depth + 1 == 2
    assert step0.ts_us <= first.ts_us
    assert first.ts_us + first.dur_us <= step0.ts_us + step0.dur_us + 1
    # step_boundary comes after train_batch has closed, at the top level
    for tb, sb in zip(by_name["train_batch"], by_name["step_boundary"]):
        assert sb.depth == 0 and sb.ts_us >= tb.ts_us + tb.dur_us - 1


def test_spans_allocate_nothing_with_telemetry_off():
    engine, batch = _tiny_engine()
    engine.train_batch(batch).block_until_ready()
    assert not telemetry.is_active()
    assert telemetry.get_tracer() is None
    assert telemetry.get_registry() is None
    assert telemetry.get_step_recorder() is None
    from deepspeed_tpu.utils import telemetry_probe
    assert telemetry_probe.tel_span("init/state") is telemetry_probe.NULL_CM


def test_import_seconds_is_stamped():
    assert isinstance(ds.IMPORT_SECONDS, float) and ds.IMPORT_SECONDS > 0


# ---- compile seconds by phase; the MFU gauge is gone -----------------------
def test_compile_seconds_have_a_phase_per_compile_event():
    telemetry.configure()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    snap = telemetry.get_registry().snapshot()
    events = {tuple(sorted(s["labels"].items())): s["value"]
              for s in snap["ds_jax_compile_total"]["values"]}
    seconds = {tuple(sorted(s["labels"].items())): s["value"]
               for s in snap["ds_compile_seconds_total"]["values"]}
    assert (("phase", "backend_compile"),) in events
    assert set(events) == set(seconds)
    assert all(v >= 0 for v in seconds.values())
    assert "ds_jax_compile_seconds_total" not in snap


def test_op_scopes_exported_and_mfu_gauge_gone(tmp_path):
    engine, batch = _tiny_engine(
        {"telemetry": {"enabled": True, "executable_ledger": True}})
    engine.train_batch(batch).block_until_ready()
    paths = telemetry.export_artifacts(str(tmp_path), prefix="t")
    import json
    with open(paths["op_scopes"]) as f:
        maps = json.load(f)
    step = maps["compiled_step"]
    assert any(p.startswith("bwd:ds.layers") for p in step.values())
    assert any("ds.flash_bwd" in p for p in step.values())
    with open(paths["prometheus"]) as f:
        prom = f.read()
    # spelled in two halves so that a grep for the gauge's name over the
    # tree finds nothing at all (ISSUE 24's acceptance check)
    assert "ds_" + "mfu" not in prom
    assert "ds_ledger_dispatched_flops_total" in prom
    assert "deepspeed_tpu.telemetry.scopes" in sys.modules


# ---- the kind of work and the bytes of each instruction (ISSUE 36) --------
# an optimized module by hand, as the TPU compiler prints one: a row of
# the table of kinds each
_WORK_HLO = """
%fused_dot (p0: bf16[8,16], p1: bf16[16,32], p2: f32[32]) -> f32[8,32] {
  %p0 = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[16,32]{1,0:T(8,128)(2,1)} parameter(1)
  %p2 = f32[32]{0:T(256)} parameter(2)
  %convolution.1 = f32[8,32]{1,0:T(8,128)} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(f)/jvp(ds.layers)/ds.kda/dot_general"}
  %broadcast.1 = f32[8,32]{1,0:T(8,128)} broadcast(%p2), dimensions={1}
  ROOT %add.1 = f32[8,32]{1,0:T(8,128)} add(%convolution.1, %broadcast.1), metadata={op_name="jit(f)/jvp(ds.layers)/ds.kda/ds.mix_pre/add"}
}

%fused_loop (q0: f32[8,32]) -> bf16[8,32] {
  %q0 = f32[8,32]{1,0:T(8,128)} parameter(0)
  %exp.1 = f32[8,32]{1,0:T(8,128)} exponential(%q0), metadata={op_name="jit(f)/jvp(ds.layers)/ds.kda/ds.mix_pre/exp"}
  ROOT %convert.1 = bf16[8,32]{1,0:T(8,128)(2,1)} convert(%exp.1), metadata={op_name="jit(f)/jvp(ds.layers)/ds.kda/ds.mix_pre/convert"}
}

%fused_transpose (r0: bf16[8,32]) -> bf16[32,8] {
  %r0 = bf16[8,32]{1,0:T(8,128)(2,1)} parameter(0)
  %bitcast.1 = bf16[8,32]{1,0:T(8,128)(2,1)} bitcast(%r0)
  ROOT %transpose.1 = bf16[32,8]{1,0:T(8,128)(2,1)} transpose(%bitcast.1), dimensions={1,0}
}

%fused_dus (s0: bf16[4,32,8], s1: bf16[32,8], s2: s32[]) -> bf16[4,32,8] {
  %s0 = bf16[4,32,8]{2,1,0:T(8,128)(2,1)} parameter(0)
  %s1 = bf16[32,8]{1,0:T(8,128)(2,1)} parameter(1)
  %s2 = s32[]{:T(128)} parameter(2)
  %constant.9 = s32[]{:T(128)} constant(0)
  %bitcast.2 = bf16[1,32,8]{2,1,0:T(8,128)(2,1)} bitcast(%s1)
  ROOT %dynamic-update-slice.1 = bf16[4,32,8]{2,1,0:T(8,128)(2,1)} dynamic-update-slice(%s0, %bitcast.2, %s2, %constant.9, %constant.9)
}

%fused_sliced (t0: bf16[64,32], t1: bf16[16,32]) -> bf16[16,32] {
  %t0 = bf16[64,32]{1,0:T(8,128)(2,1)} parameter(0)
  %t1 = bf16[16,32]{1,0:T(8,128)(2,1)} parameter(1)
  %slice.9 = bf16[16,32]{1,0:T(8,128)(2,1)} slice(%t0), slice={[16:32], [0:32]}
  ROOT %add.9 = bf16[16,32]{1,0:T(8,128)(2,1)} add(%slice.9, %t1)
}

%cond (c: (s32[], bf16[4,32,8])) -> pred[] {
  %c = (s32[]{:T(128)}, bf16[4,32,8]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %i.1 = s32[]{:T(128)} get-tuple-element(%c), index=0
  %constant.4 = s32[]{:T(128)} constant(4)
  ROOT %lt.1 = pred[]{:T(512)} compare(%i.1, %constant.4), direction=LT
}

%body (b: (s32[], bf16[4,32,8])) -> (s32[], bf16[4,32,8]) {
  %b = (s32[]{:T(128)}, bf16[4,32,8]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %i.2 = s32[]{:T(128)} get-tuple-element(%b), index=0
  %x.2 = bf16[4,32,8]{2,1,0:T(8,128)(2,1)} get-tuple-element(%b), index=1
  %copy.2 = bf16[4,32,8]{2,0,1:T(8,128)(2,1)} copy(%x.2)
  %closed_call.1 = bf16[4,32,8]{2,1,0:T(8,128)(2,1)} custom-call(%copy.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp(ds.layers)/while/body/ds.kda/ds.kda_scan/ds.kda_fwd/pallas_call"}
  %constant.1 = s32[]{:T(128)} constant(1)
  %next.1 = s32[]{:T(128)} add(%i.2, %constant.1)
  ROOT %tuple.2 = (s32[]{:T(128)}, bf16[4,32,8]{2,1,0:T(8,128)(2,1)}) tuple(%next.1, %closed_call.1)
}

ENTRY %main (x: bf16[8,16], w: bf16[16,32], b: f32[32], buf: bf16[4,32,8]) -> (bf16[4,32,8], bf16[32,32]) {
  %x = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(0)
  %w = bf16[16,32]{1,0:T(8,128)(2,1)} parameter(1)
  %b = f32[32]{0:T(256)} parameter(2)
  %buf = bf16[4,32,8]{2,1,0:T(8,128)(2,1)} parameter(3)
  %fusion.1 = f32[8,32]{1,0:T(8,128)} fusion(%x, %w, %b), kind=kOutput, calls=%fused_dot, metadata={op_name="jit(f)/jvp(ds.layers)/ds.kda/ds.mix_pre/add"}
  %fusion.2 = bf16[8,32]{1,0:T(8,128)(2,1)} fusion(%fusion.1), kind=kLoop, calls=%fused_loop, metadata={op_name="jit(f)/jvp(ds.layers)/ds.kda/ds.mix_pre/convert"}
  %copy.1 = bf16[8,32]{0,1:T(8,128)(2,1)} copy(%fusion.2)
  %fusion.3 = bf16[32,8]{1,0:T(8,128)(2,1)} fusion(%copy.1), kind=kLoop, calls=%fused_transpose
  %constant.2 = s32[]{:T(128)} constant(2)
  %fusion.4 = bf16[4,32,8]{2,1,0:T(8,128)(2,1)} fusion(%buf, %fusion.3, %constant.2), kind=kLoop, calls=%fused_dus
  %all-gather-start.1 = (bf16[16,32]{1,0:T(8,128)(2,1)}, bf16[64,32]{1,0:T(8,128)(2,1)}) all-gather-start(%w), dimensions={0}, metadata={op_name="jit(f)/jvp(ds.layers)/ds.mlp/all_gather"}
  %all-gather-done.1 = bf16[64,32]{1,0:T(8,128)(2,1)} all-gather-done(%all-gather-start.1)
  %slice.1 = bf16[32,32]{1,0:T(8,128)(2,1)} slice(%all-gather-done.1), slice={[0:32], [0:32]}
  %fusion.5 = bf16[16,32]{1,0:T(8,128)(2,1)} fusion(%all-gather-done.1, %w), kind=kLoop, calls=%fused_sliced
  %dynamic-update-slice.2 = bf16[4,32,8]{2,1,0:T(8,128)(2,1)} dynamic-update-slice(%buf, %fusion.3, %constant.2, %constant.2, %constant.2)
  %constant.3 = s32[]{:T(128)} constant(0)
  %tuple.1 = (s32[]{:T(128)}, bf16[4,32,8]{2,1,0:T(8,128)(2,1)}) tuple(%constant.3, %fusion.4)
  %while.1 = (s32[]{:T(128)}, bf16[4,32,8]{2,1,0:T(8,128)(2,1)}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(f)/jvp(ds.layers)/while"}
  %gte.1 = bf16[4,32,8]{2,1,0:T(8,128)(2,1)} get-tuple-element(%while.1), index=1
  %frobnicate.1 = bf16[32,32]{1,0:T(8,128)(2,1)} frobnicate(%slice.1)
  ROOT %tuple.3 = (bf16[4,32,8]{2,1,0:T(8,128)(2,1)}, bf16[32,32]{1,0:T(8,128)(2,1)}) tuple(%gte.1, %frobnicate.1)
}
"""


@pytest.mark.parametrize("name,kind,nbytes,mixed", [
    # a dot fusion with its epilogue: 8x16 + 16x32 in bf16, the bias and
    # the result in float32; it fused ds.kda's dot under ds.mix_pre's root
    ("fusion.1", "matmul", 256 + 1024 + 128 + 1024, True),
    ("fusion.2", "elementwise", 1024 + 512, False),     # a loop fusion
    ("copy.1", "move", 512 + 512, False),
    ("fusion.3", "move", 512 + 512, False),             # transpose only
    # a dynamic-update-slice fusion runs in place: the update read and
    # written and the index, not the buffer twice
    ("fusion.4", "move", 512 + 512 + 4, False),
    ("closed_call.1", "kernel", 2048 + 2048, False),
    ("all-gather-start.1", "collective", 1024 + 4096 + 1024, False),
    ("all-gather-done.1", "collective", 4096 + 1024 + 4096, False),
    ("slice.1", "move", 2048 + 2048, False),            # what it reads
    # a fusion that only slices an operand reads the slice of it
    ("fusion.5", "elementwise", 1024 + 1024 + 1024, False),
    ("dynamic-update-slice.2", "move", 512 + 512 + 3 * 4, False),
    ("while.1", "control", 2 * (4 + 2048), False),
    ("copy.2", "move", 2048 + 2048, False),              # a leaf in the body
    ("tuple.1", "control", 4 + 2048 + 4 + 2048, False),
    ("x", "control", 256, False),
    ("convolution.1", "matmul", 1024 + 256 + 1024, False),
    ("lt.1", "elementwise", 1 + 4 + 4, False),
    ("frobnicate.1", "other", 2048 + 2048, False),       # not in the table
])
def test_the_kind_bytes_and_mix_of_each_instruction(name, kind, nbytes,
                                                    mixed):
    row = scopes.op_work(_WORK_HLO)[name]
    assert (row["kind"], row["bytes"], row["mixed"]) == (kind, nbytes, mixed)
    assert row["kind"] in scopes.KINDS


def test_op_work_carries_the_scopes_op_scopes_returns():
    work = scopes.op_work(_WORK_HLO)
    got = scopes.op_scopes(_WORK_HLO)
    assert list(got) == list(work)              # the same walk, in order
    assert got == {name: row["scope"] for name, row in work.items()}
    assert got["fusion.1"] == "fwd:ds.layers/ds.kda/ds.mix_pre"
    assert got["copy.2"] == "fwd:ds.layers"     # its holder's
    assert got["closed_call.1"] == \
        "fwd:ds.layers/ds.kda/ds.kda_scan/ds.kda_fwd"
    assert got["all-gather-done.1"] == ""
    assert set(work["fusion.1"]) == {"scope", "kind", "bytes", "mixed"}


def test_a_scatter_is_a_move_only_if_it_assigns():
    hlo = """
%assign (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  ROOT %b = f32[] parameter(1)
}

%plus (c: f32[], d: f32[]) -> f32[] {
  %c = f32[] parameter(0)
  %d = f32[] parameter(1)
  ROOT %add.3 = f32[] add(%c, %d)
}

%wrapped (e: f32[8,4]) -> f32[2,4] {
  %e = f32[8,4]{1,0} parameter(0)
  ROOT %slice.5 = f32[2,4]{1,0} slice(%e), slice={[0:2], [0:4]}
}

ENTRY %main (t: f32[8,4], i: s32[2,1], u: f32[2,4]) -> f32[8,4] {
  %t = f32[8,4]{1,0} parameter(0)
  %i = s32[2,1]{1,0} parameter(1)
  %u = f32[2,4]{1,0} parameter(2)
  %scatter.1 = f32[8,4]{1,0} scatter(%t, %i, %u), update_window_dims={1}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%assign
  %slice-start.1 = ((f32[8,4]{1,0}), f32[2,4]{1,0}, s32[]) async-start(%scatter.1), calls=%wrapped
  %slice-done.1 = f32[2,4]{1,0} async-done(%slice-start.1)
  %buffer.1 = f32[8,4]{1,0} custom-call(), custom_call_target="AllocateBuffer"
  ROOT %scatter.2 = f32[8,4]{1,0} scatter(%scatter.1, %i, %slice-done.1), update_window_dims={1}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%plus
}
"""
    work = scopes.op_work(hlo)
    assert work["scatter.1"]["kind"] == "move"
    assert work["scatter.2"]["kind"] == "elementwise"
    # an asynchronous pair is what it wraps, both halves
    assert work["slice-start.1"]["kind"] == "move"
    assert work["slice-done.1"]["kind"] == "move"
    assert work["buffer.1"]["kind"] == "control"
    assert work["scatter.1"]["bytes"] == 128 + 128 + 8 + 32


def test_no_instruction_of_the_train_step_is_of_an_unknown_kind(step_hlo):
    work = scopes.op_work(step_hlo)
    assert {row["kind"] for row in work.values()} <= set(scopes.KINDS)
    unknown = sorted(n for n, row in work.items() if row["kind"] == "other")
    assert not unknown, unknown
    kinds = {row["kind"] for row in work.values()}
    assert {"matmul", "elementwise", "move", "control",
            "collective"} <= kinds
    assert all(row["bytes"] >= 0 for row in work.values())


def test_op_work_is_exported_beside_op_scopes(tmp_path):
    import json
    engine, batch = _tiny_engine(
        {"telemetry": {"enabled": True, "executable_ledger": True}})
    engine.train_batch(batch).block_until_ready()
    paths = telemetry.export_artifacts(str(tmp_path), prefix="t")
    assert paths["op_work"] == str(tmp_path / "t.op_work.json")
    with open(paths["op_work"]) as f:
        work = json.load(f)["compiled_step"]
    with open(paths["op_scopes"]) as f:
        text = f.read()
    # op_scopes.json is what it was: the same names in the same order
    # with the same paths, in the same bytes
    led = telemetry.get_ledger()
    assert text == json.dumps(led.op_scopes_by_name())
    assert json.loads(text)["compiled_step"] == {
        name: row["scope"] for name, row in work.items()}
    assert work == led.op_work_by_name()["compiled_step"]
    assert not any(row["kind"] == "other" for row in work.values())


# ---- the hyper-connected streams' scopes (ISSUE 56) ------------------------
@pytest.mark.parametrize("scope, file, function", [
    ("ds.mhc", "models/xing4.py", "_sublayer"),
    ("ds.mhc_pre", "ops/mhc.py", "mhc_pre"),
    ("ds.mhc_pre", "ops/pallas/mhc.py", "_pre_forward"),
    ("ds.mhc_pre", "ops/pallas/mhc.py", "_pre_backward"),
    ("ds.mhc_coef", "ops/mhc.py", "coefficients"),
    ("ds.mhc_coef", "ops/pallas/mhc.py", "_coef_forward"),
    ("ds.mhc_coef", "ops/pallas/mhc.py", "_coef_backward"),
    ("ds.mhc_post", "ops/mhc.py", "mhc_post"),
    ("ds.mhc_post", "ops/pallas/mhc.py", "_post_forward"),
    ("ds.mhc_post", "ops/pallas/mhc.py", "_post_backward"),
    ("ds.mhc_spread", "models/xing4.py", "_layer_stack"),
    ("ds.mhc_fold", "models/xing4.py", "_layer_stack"),
    # the gate a head (ISSUE 60)
    ("ds.attn_gate", "models/laguna.py", "_attention"),
    # the rotation, either form (ISSUE 62)
    ("ds.rope", "ops/layers.py", "rotate"),
    ("ds.rope", "ops/pallas/rope.py", "_call"),
    # a row in spans (ISSUE 64), and the latent attention's own rotation:
    # since ISSUE 65 XLA's form and the one pass that stands in for it
    # (``models/stack.py`` ``_mla`` opened it until then)
    ("ds.flash_merge", "ops/pallas/flash_attention.py", "_spans_fwd"),
    ("ds.flash_merge", "ops/pallas/flash_attention.py", "_spans_bwd"),
    ("ds.rope", "ops/layers.py", "latent_attention"),
    ("ds.rope", "ops/pallas/rope.py", "_latent_call"),
    # experts that work in a latent (ISSUE 66)
    ("ds.moe_latent", "moe/sharded_moe.py", "moe_ffn_held"),
])
def test_a_registered_scope_is_opened_where_the_list_says(scope, file,
                                                          function):
    """``MHC_SCOPES``, ``GATE_SCOPES``, ``SPAN_SCOPES`` and ``LATENT_SCOPES`` name, a scope, the file and the
    function that opens it: the function's source holds the scope's name
    as a literal, and every list of the registry is in ``KNOWN_SCOPES``
    (what a metric file may name: ``tests/test_benchmark_contract.py``)."""
    import ast
    import pathlib

    import deepspeed_tpu
    assert scope in (scopes.MHC_SCOPES + scopes.GATE_SCOPES
                     + scopes.WINDOW_SCOPES + scopes.SPAN_SCOPES
                     + scopes.LATENT_SCOPES) and scope in scopes.KNOWN_SCOPES
    source = (pathlib.Path(deepspeed_tpu.__file__).parent / file).read_text()
    body = next(ast.get_source_segment(source, node)
                for node in ast.walk(ast.parse(source))
                if isinstance(node, ast.FunctionDef)
                and node.name == function)
    assert f'"{scope}"' in body
    assert set(scopes.TOP_SCOPES) <= scopes.KNOWN_SCOPES

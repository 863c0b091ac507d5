"""Granite 4.0-H (ISSUE 34): Mamba-2 state-space layers with a NoPE
grouped-query attention layer among them, a SwiGLU in every layer, the four
muP multipliers and a tied head, checked on the CPU at tiny sizes against
the plain float32 reference the benchmark keeps
(``benchmark/architectures/granite_hybrid.py``, which imports nothing from
the program); and the stack of kinds' first caller, Kimi-Linear, held to
the program it had before the stack moved to ``models/stack.py``. A CPU run
shows results and counts, never a time."""

import json
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu import telemetry
from deepspeed_tpu.models import GraniteHybrid, KimiLinear
from deepspeed_tpu.models.stack import stack_plan
from deepspeed_tpu.ops import layers as L
from deepspeed_tpu.ops.ssd import chunk_ssd, recurrent_ssd
from deepspeed_tpu.telemetry import scopes

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
from architectures import granite_hybrid as arch  # noqa: E402
from kinds import train_job  # noqa: E402
from lib import modelspec  # noqa: E402

from helpers import hlo_text  # noqa: E402  (tests/helpers)

CONFIG = json.loads(
    (BENCH / "configs" / "granite-4.0-h-micro-zero3-1chip.json").read_text())


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    telemetry.shutdown()
    yield
    telemetry.shutdown()


def _err(got, want):
    return float(jnp.max(jnp.abs(got - want))) / (
        float(jnp.max(jnp.abs(want))) + 1e-30)


# ---- the chunked scan against the recurrence -------------------------------
def _ssd_inputs(b=2, s=128, h=4, p=8, g=1, n=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = jnp.exp(jax.random.uniform(k[1], (b, s, h), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    return (jax.random.normal(k[0], (b, s, h, p)), dt,
            -jax.random.uniform(k[2], (h,), minval=1.0, maxval=16.0),
            jax.random.normal(k[3], (b, s, g, n)),
            jax.random.normal(k[4], (b, s, g, n)))


@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("bc_groups", [1, 2, 4])
def test_chunked_ssd_matches_the_recurrence_forward_and_backward(
        bc_groups, chunk):
    """B and C shared by all four heads (as published), by two, by one."""
    args = _ssd_inputs(g=bc_groups)
    want = recurrent_ssd(*args)
    got = chunk_ssd(*args, chunk=chunk)
    assert got.shape == want.shape and _err(got, want) < 1e-5
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    grad = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, r in zip("x dt A B C".split(),
                          grad(lambda *a: chunk_ssd(*a, chunk=chunk)),
                          grad(recurrent_ssd)):
        assert _err(g, r) < 2e-5, name


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_chunked_ssd_stays_finite_and_right_at_the_fastest_decays(dtype):
    """dt at its largest (0.1) times A at its least (-16) is -1.6 a token,
    -410 over a whole chunk of 256: a product exp(a_i) exp(-a_j) would be
    inf x 0 (the float32 overflow PR 31's chip runs found in KDA's decay
    products and no tiny test had); the differences are taken first."""
    x, dt, _, B, C = _ssd_inputs(s=512, h=2, p=8, n=16)
    dt = jnp.full_like(dt, 0.1)
    A = jnp.asarray([-16.0, -1.0])
    want = recurrent_ssd(x, dt, A, B, C)
    cast = lambda v: v.astype(dtype)  # noqa: E731
    got = chunk_ssd(cast(x), dt, A, cast(B), cast(C), chunk=256)
    assert got.dtype == jnp.dtype(dtype)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _err(got.astype(jnp.float32), want) < (
        1e-5 if dtype == "float32" else 2e-2)
    grads = jax.grad(lambda *a: jnp.sum(jnp.square(chunk_ssd(
        *a, chunk=256).astype(jnp.float32))), argnums=(0, 1, 2, 3, 4))(
        cast(x), dt, A, cast(B), cast(C))
    ref = jax.grad(lambda *a: jnp.sum(jnp.square(recurrent_ssd(*a))),
                   argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    for name, g, r in zip("x dt A B C".split(), grads, ref):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert _err(g.astype(jnp.float32), r) < (
            1e-4 if dtype == "float32" else 5e-2), name


def test_chunked_ssd_agrees_with_the_benchmarks_recurrence():
    """The reference's own token-by-token scan (another formulation: a
    state a head, B and C repeated) gives what the program's two forms
    give; a bad shape is refused."""
    args = _ssd_inputs(g=2, seed=5)
    want = arch.ssm_recurrence(*args)
    assert _err(recurrent_ssd(*args), want) < 1e-6
    assert _err(chunk_ssd(*args, chunk=32), want) < 1e-5
    with pytest.raises(ValueError, match="multiple of the chunk"):
        chunk_ssd(*args, chunk=48)
    with pytest.raises(ValueError, match="groups of B and C"):
        x, dt, A, B, C = args       # three heads on two groups of B and C
        chunk_ssd(x[:, :, :3], dt[:, :, :3], A[:3], B, C, chunk=32)


# ---- the whole model against the plain reference ---------------------------
def _tiny(**kw):
    return GraniteHybrid(size="tiny", **kw)


def _batch(model, b=2, s=128, seed=0):
    tok = np.random.default_rng(seed).integers(
        0, model.config.vocab_size, (b, s + 1))
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def _weights(model, seed=3):
    """Seeded weights under which every layer carries weight in the
    logits: at the init's own scale the one attention layer adds 0.2% to
    the final hidden state (uniform softmax, a small output projection
    times 0.22), and no check could see a fault in it."""
    boost = {"wq": 16.0, "wk": 16.0, "wv": 8.0, "wo": 8.0}
    return jax.tree_util.tree_map_with_path(
        lambda path, w: w * boost.get(path[-1].key, 1.0),
        model.init(jax.random.PRNGKey(seed)))


def _ref_loss(params, tokens, targets, m):
    hidden = arch.final_hidden(params, tokens, m) / m["logits_scaling"]
    return arch.loss_of(hidden, params["embed"]["tokens"].T, targets)


@pytest.mark.parametrize("variant", ["plain", "flash_chunked_loss",
                                     "no_remat"])
def test_loss_and_gradients_match_the_float32_reference(variant):
    kw = {"plain": {},
          "flash_chunked_loss": dict(attn_impl="flash", loss_chunk=64),
          "no_remat": dict(remat=False)}[variant]
    model = _tiny(**kw)
    params = _weights(model)
    tokens, targets = _batch(model)
    m = modelspec.reference_model(arch, model)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(_ref_loss)(params, tokens,
                                                     targets, m)
        got, got_g = jax.value_and_grad(model.loss)(params,
                                                    (tokens, targets))
    assert abs(float(got) - float(want)) <= 2e-5 * float(want)
    flat_w = jax.tree_util.tree_leaves_with_path(want_g)
    flat_g = jax.tree_util.tree_leaves_with_path(got_g)
    assert [p for p, _ in flat_w] == [p for p, _ in flat_g]
    for (path, w), (_, g) in zip(flat_w, flat_g):
        name = jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(w))) > 0, name
        assert _err(g, w) < 2e-3, name


class _UntiedHead(GraniteHybrid):
    """A head that is not the embedding table (its rows reversed)."""
    def _project_vocab(self, params, x):
        other = {**params, "embed": {
            "tokens": jnp.flip(params["embed"]["tokens"], 0)}}
        return super()._project_vocab(other, x)


FAULTS = {
    None: {},
    "softmax_scale_head_dim_rsqrt_in_place_of_the_multiplier":
        dict(attention_multiplier=None),
    "no_residual_multiplier": dict(residual_multiplier=1.0),
    "logits_not_divided": dict(logits_scaling=1.0),
    "no_embedding_multiplier": dict(embedding_multiplier=1.0),
    "untied_head": {},
    "targets_off_by_one": {},
}


@pytest.mark.parametrize("fault", list(FAULTS), ids=lambda f: f or "none")
def test_the_cells_limits_catch_a_planted_fault(fault):
    """The benchmark's own decision (``kinds/train_job.py`` ``decide`` at
    the configuration's ``check``) on the program's tail logits and loss
    against the reference's: the program passes, each planted departure
    from the published equations does not."""
    right = _tiny(loss_chunk=64)
    cls = _UntiedHead if fault == "untied_head" else GraniteHybrid
    model = cls(size="tiny", loss_chunk=64, **FAULTS[fault])
    params = _weights(right)
    tokens, targets = _batch(right)
    m = modelspec.reference_model(arch, right)
    with jax.default_matmul_precision("highest"):
        want_loss, want_tail = arch.reference(params, tokens, targets, m, 32)
        got_tail = model.apply(params, tokens)[:, -32:]
        if fault == "targets_off_by_one":
            targets = jnp.roll(targets, 1, axis=1)
        got_loss = float(model.loss(params, (tokens, targets)))
    numbers = train_job.tail_numbers(got_tail, want_tail, None)
    ok = train_job.decide(numbers, want_loss, got_loss, CONFIG["check"])
    assert ok == (fault is None), numbers
    if fault is None:
        assert numbers["logits_err_max"] < 1e-4 > numbers["loss_err"]


# ---- the configuration, the counts, the plan -------------------------------
def test_the_configuration_file_builds_the_published_model():
    """``lib/modelspec.py`` holds the model as built to every published
    key of the file (``arch.WIDTHS``: the multipliers and the Mamba sizes
    demanded); a preset that drifts fails the run."""
    model = modelspec.build_model(CONFIG, arch, {})
    c = model.config
    assert c.num_params() == 772160448
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    assert n == c.num_params()
    assert (model.lead, model.period, model.repeats, model.left) == (
        0, 1, 5, 5)     # five Mamba layers under one scan, five unrolled
    m = modelspec.reference_model(arch, model)
    assert m["attention_multiplier"] == 1 / 64 and m["logits_scaling"] == 8
    # the program's estimate and the benchmark's count agree to 1%
    assert c.flops_per_token(8192) == pytest.approx(
        arch.train_flops_per_token(m, 8192), rel=0.01)
    drifted = json.loads(json.dumps(CONFIG))
    drifted["residual_multiplier"] = 0.25
    with pytest.raises(ValueError, match="residual_multiplier"):
        modelspec.build_model(drifted, arch, {})
    whole = GraniteHybrid(size="4.0-h-micro").config
    assert 3.0e9 < whole.num_params() < 3.4e9       # "3B"
    assert whole.layer_types.count("attention") == 4
    tiny = _tiny()
    assert tiny.config.num_params() == sum(
        x.size for x in jax.tree.leaves(
            jax.eval_shape(tiny.init, jax.random.PRNGKey(0))))


@pytest.mark.parametrize("kinds,lead,want", [
    ("MMMMMAMMMM", 0, (1, 5, 5)),           # the cell's cut: layers 0 to 9
    ("MMMMMAMMMM" * 4, 0, (10, 4, 0)),      # the published 40 layers
    ("MMAMM", 0, (1, 2, 3)),                # the tiny preset
])
def test_stack_plan_of_the_granite_patterns(kinds, lead, want):
    assert stack_plan(list(kinds), lead) == want


def test_required_operations_by_hand():
    m = modelspec.reference_model(arch, modelspec.build_model(
        CONFIG, arch, {}))
    parts = arch.forward_flops_per_token(m, 8192)
    assert parts["ssd_state"] == 9 * 4 * 64 * 64 * 128
    assert parts["ffn"] == 10 * 6 * 2048 * 8192
    assert parts["head"] == 2 * 2048 * 12544
    assert parts["attention"] == 4 * 64 * 32 * 8193 / 2
    fwd = arch.ssd_call_cost(m, 1, 8192, backward=False)
    assert fwd == {"flops": 9 * 4 * 64 * 64 * 128 * 8192,
                   "bytes": 9 * 8192 * (64 * (2 * 64 * 2 + 4) + 512)}
    bwd = arch.ssd_call_cost(m, 1, 8192, backward=True)
    assert bwd["flops"] == 2 * fwd["flops"] and bwd["bytes"] > fwd["bytes"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert arch.least_seconds(fwd, peaks)[1] == "memory"
    flash = arch.gqa_flash_call_cost(m, 1, 8192, backward=False)
    assert flash["flops"] == 4 * 64 * 32 * 8192 * 8193 // 2
    assert arch.least_seconds(flash, peaks)[1] == "compute"


# ---- the engine ------------------------------------------------------------
_DS_CONFIG = {
    "train_batch_size": 8, "bf16": {"enabled": True},
    "zero_optimization": {"stage": 3},
    "optimizer": {"type": "AdamW",
                  "params": {"lr": 3e-4, "weight_decay": 0.1}},
    "gradient_clipping": 1.0, "mesh": {"fsdp": -1},
    "steps_per_print": 10 ** 9}


@pytest.fixture(scope="module")
def granite_engine():
    model = _tiny(attn_impl="flash", loss_chunk=64)
    engine, *_ = ds.initialize(model=model, config=dict(_DS_CONFIG))
    return engine, _batch(model, b=8)


def test_engine_trains_through_the_compiled_step(granite_engine):
    """``ds.initialize`` and the engine's compiled step as for every other
    family: no ``with_stats``, no ``after_step``, a falling loss, and the
    tied table's gradient reaches it from the lookup and from the head."""
    engine, batch = granite_engine
    assert not hasattr(engine.module, "after_step")
    before = np.asarray(engine.state["master"]["embed"]["tokens"]).copy()
    losses = [float(engine.train_batch(batch)) for _ in range(4)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert "lm_head" not in engine.state["master"]
    moved = np.abs(np.asarray(
        engine.state["master"]["embed"]["tokens"]) - before)
    assert np.all(moved.max(axis=1) > 0)    # every row: the head's share


def test_step_scopes_are_the_lists(granite_engine):
    engine, batch = granite_engine
    hlo = engine._train_step.lower(
        engine.state, engine._put_batch(batch)).compile().as_text()
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        found.update(re.findall(r"ds\.[A-Za-z0-9_]+", op_name))
    assert found == (set(scopes.DEVICE_SCOPES) | set(scopes.SSM_SCOPES)
                     | set(scopes.MIXER_SCOPES))
    by_op = scopes.op_scopes(hlo)
    paths = {p for p in by_op.values() if p}
    for scope in ("ds.mamba/ds.ssd", "ds.attn/ds.flash_fwd", "ds.mlp"):
        assert any(p.startswith("fwd:ds.layers") and scope in p
                   for p in paths), scope
    for scope in ("ds.mamba/ds.ssd", "ds.flash_bwd", "ds.mlp"):
        assert any(p.startswith("bwd:ds.layers") and scope in p
                   for p in paths), scope
    # the scan stands inside the mixer's scope (but for a dozen broadcasts
    # of its constants, the mask and the zero state, which remat's trace
    # names by the innermost scope alone)
    scan = [p for p in by_op.values() if "ds.ssd" in p]
    assert sum("ds.mamba" in p for p in scan) > 0.99 * len(scan)


def test_the_mixer_parts_lie_inside_ds_mamba_and_no_kind_is_unknown(
        granite_engine):
    """ISSUE 36: the convolution and what lies before and after the scan
    are named inside ds.mamba, straight under it in both directions and
    never inside the attention layer or the FFN (the compiler moves an
    instruction or two of them into the scan's loop, whose path then
    holds theirs); the table of kinds knows every instruction of the
    step."""
    engine, batch = granite_engine
    hlo = engine._train_step.lower(
        engine.state, engine._put_batch(batch)).compile().as_text()
    work = scopes.op_work(hlo)
    paths = {row["scope"] for row in work.values()}
    for part in scopes.MIXER_SCOPES:
        mine = {p for p in paths if re.search(rf"{re.escape(part)}\b", p)}
        assert {f"{d}:ds.layers/ds.mamba/{part}"
                for d in ("fwd", "bwd")} <= mine, (part, mine)
        assert all("ds.layers/ds.mamba/" in p and "ds.attn" not in p
                   and "ds.mlp" not in p for p in mine), (part, mine)
    unknown = sorted(n for n, row in work.items() if row["kind"] == "other")
    assert not unknown, unknown


def test_the_named_scopes_are_metadata_and_nothing_else(granite_engine,
                                                        monkeypatch):
    """The step compiled with every ``jax.named_scope`` a null context is
    the same optimized program once ``metadata={...}`` is taken out."""
    named, bare = hlo_text.bare_step(*granite_engine, _DS_CONFIG, monkeypatch)
    assert re.search(r"\bds\.[a-z_]+", named) is None     # all metadata
    assert bare == named


# ---- the stack's first caller is the program it was ------------------------
def _parent_layer(self, p, x, mixers, scanned: bool):
    """``KimiLinear._layer`` as it stood at commit c6a61a3."""
    from deepspeed_tpu.models.transformer import _remat_policy
    c = self.config
    layer = lambda p, x: self._channel(  # noqa: E731
        p, self._mix(p, x, *mixers))
    if not c.remat:
        return layer(p, x)
    return jax.checkpoint(layer, prevent_cse=not scanned,
                          policy=_remat_policy(c.remat_policy))(p, x)


def _parent_layer_stack(self, layers, x, pin, *, attn_fn, positions,
                        act_sharding=None):
    """``KimiLinear._layer_stack`` as it stood at commit c6a61a3."""
    from deepspeed_tpu.ops.kda import chunk_kda, sharded_chunk_kda
    if attn_fn is None:
        if self.config.attn_impl == "flash":
            from deepspeed_tpu.ops.pallas.flash_attention import \
                flash_attention
            attn_fn = flash_attention
        else:
            attn_fn = L.dot_product_attention
    mixers = (attn_fn, chunk_kda if act_sharding is None
              else sharded_chunk_kda(act_sharding))
    stats = {"lead": {}, "period": {}, "tail": {}}

    def unrolled(group, n, x):
        for i in range(n):
            x, stats[group][str(i)] = _parent_layer(
                self, layers[group][str(i)], x, mixers, False)
            x = pin(x)
        return x

    x = unrolled("lead", self.lead, x)
    if self.repeats:
        def body(x, slots):
            counts = {}
            for j in range(self.period):
                x, counts[str(j)] = _parent_layer(
                    self, slots[str(j)], x, mixers, True)
                x = pin(x)
            return x, counts

        x, stats["period"] = jax.lax.scan(body, x, layers["period"])
    x = unrolled("tail", self.left, x)
    return x, {g: {k: v for k, v in slots.items() if v}
               for g, slots in stats.items()}


def _parent_init_layers(self, key):
    """The ``layers`` of ``KimiLinear.init`` as it stood at c6a61a3."""
    lk = iter(jax.random.split(key, len(self.kinds)))
    at = self.lead + self.period * self.repeats
    return {
        "lead": {str(i): self._init_layer(next(lk), self.kinds[i])
                 for i in range(self.lead)},
        "period": {str(j): self._init_layer(
            next(lk), self.kinds[self.lead + j], (self.repeats,))
            for j in range(self.period if self.repeats else 0)},
        "tail": {str(i): self._init_layer(next(lk), self.kinds[at + i])
                 for i in range(self.left)},
    }


def _parent_conv(x, w, bias=None):
    """The causal convolution as ``KimiLinear._kda`` defined it locally."""
    n, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(xp[:, i:i + s] * w[i] for i in range(n))


def _kimi_step_text(monkeypatch, parent: bool, **model_kw):
    if parent:
        monkeypatch.setattr(KimiLinear, "_layer_stack", _parent_layer_stack)
        monkeypatch.setattr(KimiLinear, "_init_layers", _parent_init_layers)
        monkeypatch.setattr(L, "causal_conv", _parent_conv)
    model = KimiLinear(size="tiny", moe_held_experts=8, **model_kw)
    engine, *_ = ds.initialize(model=model, config=dict(_DS_CONFIG))
    tok = np.zeros((8, model.config.max_seq_len), np.int32)
    text = engine._train_step.lower(
        engine.state, engine._put_batch((tok, tok))).as_text()
    monkeypatch.undo()
    # summed on the host: a hundred small reductions over eight virtual
    # devices can time out their rendezvous when the test workers fill the
    # machine's cores, and XLA's CPU runtime then aborts the process
    leaves = jax.device_get(jax.tree.leaves(engine.state["master"]))
    return text, float(sum(np.abs(x.astype(np.float64)).sum()
                           for x in leaves))


@pytest.mark.parametrize("model_kw", [
    dict(), dict(attn_impl="flash", loss_chunk=64, kda_head_groups=2)],
    ids=["default", "the_cells_switches"])
def test_kimi_step_is_the_parents_program(monkeypatch, model_kw):
    """The stack of kinds and the causal convolution moved (to
    ``models/stack.py`` and ``ops/layers.py``) and nothing else did: with
    the parent's own definitions patched back in, Kimi-Linear's lowered
    train step is the same text (no source locations in either) and its
    seeded weights the same numbers. Mistral's is held to its parent's by
    ``tests/test_kimi_linear.py``."""
    now, weights_now = _kimi_step_text(monkeypatch, False, **model_kw)
    parent, weights_parent = _kimi_step_text(monkeypatch, True, **model_kw)
    assert "loc(" not in now
    assert now == parent
    assert weights_now == weights_parent


def test_importing_the_package_loads_no_state_space_scan():
    """``ops/ssd.py`` and ``ops/kda.py`` are imported where a model's
    stack is traced, so ``deepspeed_tpu.IMPORT_SECONDS`` (the benchmark's
    ``setup_import_s``) does not grow with the families."""
    code = ("import sys, deepspeed_tpu, deepspeed_tpu.models as m; "
            "assert m.GraniteHybrid and m.KimiLinear; "
            "bad = [k for k in sys.modules if k.endswith(('ops.ssd', "
            "'ops.kda', 'ops.pallas.kda'))]; assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=str(BENCH.parent))

"""The families the benchmark trains, one row each, and what their test
files share (ISSUE 58). A family is ONE row of ``FAMILIES``: its class, the
cell's switches at ``tiny``, its configuration file and reference module,
the rule of its seeded weights, the numbers its engine cases expect, its
step's scope set, the two-layer cut its pin lowers, and for every property
that crosses families (rematted and kept residuals, the short convolution,
the delta-rule scan) either the row's value or the reason it has none. A
new family adds a row here and its own files (``README.md`` Development).

A program is built ONCE a file: ``program(family, cut, ...)`` is the one
memo of a model, its engine, the step's lowered and compiled text, its
kernel calls and its loss and gradients, and the cases that need one
program sit in one file (a file is one worker's under ``--dist
loadfile``; ``tests/conftest.py`` drops the memo at a file's end, so no
file meets an engine another file trained). Importing this puts ``benchmark/`` on ``sys.path`` (the
references are ``architectures/``'s)."""

import collections
import contextlib
import dataclasses
import functools
import importlib
import json
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu import models, telemetry
from deepspeed_tpu.models import ouro, stack, transformer
from deepspeed_tpu.ops import layers as L
from deepspeed_tpu.ops.pallas import _common
from deepspeed_tpu.telemetry import scopes as S

from helpers import short_conv_reference

BENCH = pathlib.Path(__file__).resolve().parents[2] / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
from lib import modelspec  # noqa: E402


# the engine every family's tiny model is trained and lowered under: ZeRO-3
# bf16 over every device of the mesh
DS_CONFIG = {
    "train_batch_size": 8, "bf16": {"enabled": True},
    "zero_optimization": {"stage": 3},
    "optimizer": {"type": "AdamW",
                  "params": {"lr": 3e-4, "weight_decay": 0.1}},
    "gradient_clipping": 1.0, "mesh": {"fsdp": -1},
    "steps_per_print": 10 ** 9}

_MOE = {"ds.moe_router", "ds.moe_experts", "ds.moe_gmm_fwd",
        "ds.moe_gmm_bwd", "ds.moe_add_rows"}
_STEP = dict(attn_impl="flash", loss_chunk=64)


def _overrides(config, *keys):
    """The values a configuration file seeds its model with."""
    got = json.loads((BENCH / "configs" / f"{config}.json").read_text())[
        "program"]["model_overrides"]
    return {key: got[key] for key in keys}


def _drawn(scale, spread):
    """A norm weight drawn round ``scale`` times its start."""
    return lambda w, keys: scale * w + spread * jax.random.normal(
        next(keys), w.shape, w.dtype)


@dataclasses.dataclass(frozen=True)
class Family:
    cls: type
    # the cell's switches at ``tiny``: what ``tiny(name)`` builds
    cell: dict = dataclasses.field(default_factory=dict)
    # ... and what the cell's compiled step adds to them
    step: dict = dataclasses.field(default_factory=lambda: dict(_STEP))
    config: str | None = None       # benchmark/configs/<config>.json
    arch: str | None = None         # benchmark/architectures/<arch>.py
    # the seeded weights (``weights``): a factor a leaf name, and a
    # function of (leaf, keys) a name that is drawn or shifted
    boost: dict = dataclasses.field(default_factory=dict)
    special: dict = dataclasses.field(default_factory=dict)
    held: int = 0                   # experts held of the router's
    # the engine cases' numbers (``tests/helpers/family_suite.py``): routed
    # layers a step (``calls``), the range of rows a held expert, the
    # dispatch's block, the steps trained, where the selection bias lies
    # and its shape and grid tolerance (rtol, atol), and which form shows
    # the registry one step ``behind`` (``traced`` | ``one_device``)
    engine: dict = dataclasses.field(default_factory=dict)
    scopes: frozenset = frozenset(S.DEVICE_SCOPES)
    # the two-layer cut that holds every kind of layer the family has:
    # what ``tests/test_step_pins.py`` lowers (and ``mistral``, ``mellum``
    # also at their presets' own depth)
    two_layers: dict = dataclasses.field(default_factory=dict)
    # attention-layer applications in the TRACED step under remat (a
    # scan's body is traced once: Ouro's 2 layers x 4 passes are one
    # application, Mellum's two kinds of attention layer are two), or the
    # reason the row has no rematted case
    rematted: int | str = 1
    # ... and the cut whose loss and gradients are held to ``policy=None``'s
    # bit for bit ({}: the cell's own layers). Not ``two_layers``: two kinds
    # are no period, both layers are unrolled, and XLA compiles an unrolled
    # layer's rerun to other bits than its forward on the CPU (Mellum's
    # table differs by one bit in 77 of 32768 elements, PR 58)
    kept: dict = dataclasses.field(default_factory=dict)
    # the smallest stack that holds the op (one layer of the kind), or why
    # the family has none
    short_conv: dict | str = "no layer calls ops.layers.short_conv"
    scan: dict | str = "no layer calls ops.kda.chunk_kda"
    # forwards of the scan's kernels a backward in the rematted step (two
    # where the head groups are a loop and ``o`` is kept, three in one group)
    scan_runs: int = 0


_norm = _drawn(1.0, 0.3)
FAMILIES = {
    # the control: the one-kind decoder every older cell runs
    "mistral": Family(
        models.Mistral, cell=dict(sliding_window=64),
        rematted="one kind of layer under one scan: "
                 "test_kept_residuals.py::test_every_policy_keeps_the_name"),
    "kimi_linear": Family(
        models.KimiLinear, cell=dict(moe_held_experts=8),
        # the KDA heads run in two groups, as the cell's run in four: with
        # one, the scan keeps nothing
        step=dict(_STEP, kda_head_groups=2),
        config="kimi-linear-48b-ep32-zero3-1chip", arch="kimi_linear",
        held=8,
        # 8 x 128 tokens x top-8 of 256 experts: 32 a held expert if even
        engine=dict(calls=4, per_expert=(16, 48), steps=4, behind="traced",
                    bias=("period", (2, 256)), bias_tol=(1e-7, 1e-3)),
        scopes=frozenset(set(S.DEVICE_SCOPES) - {"ds.attn"}
                         | set(S.KIND_SCOPES) | set(S.MIXER_SCOPES)),
        two_layers=dict(num_layers=2, kda_layers=(1,), full_attn_layers=(2,),
                        first_k_dense_replace=0),
        short_conv=dict(num_layers=1, kda_layers=(1,), full_attn_layers=(),
                        first_k_dense_replace=0),
        scan=dict(num_layers=1, kda_layers=(1,), full_attn_layers=(),
                  first_k_dense_replace=0), scan_runs=2),
    "granite_hybrid": Family(
        models.GraniteHybrid, config="granite-4.0-h-micro-zero3-1chip",
        arch="granite_hybrid",
        # at the init's own scale the one attention layer adds 0.2% to the
        # final hidden state (uniform softmax, a small output projection
        # times 0.22), and no check could see a fault in it
        boost={"wq": 16.0, "wk": 16.0, "wv": 8.0, "wo": 8.0},
        scopes=frozenset(set(S.DEVICE_SCOPES) | set(S.SSM_SCOPES)
                         | set(S.MIXER_SCOPES)),
        two_layers=dict(num_layers=2, layer_types=["mamba", "attention"]),
        short_conv=dict(num_layers=1, layer_types=["mamba"])),
    "mellum": Family(
        models.Mellum, cell=dict(moe_held_experts=16),
        config="mellum2-12b-ep4-zero3-1chip", arch="mellum",
        # sharper scores, larger values, larger experts (``PERF.md``
        # section 2: at the init's own scale a softmax is near uniform and
        # a layer's output projection small)
        boost={"tokens": 0.02, "wq": 4.0, "wk": 4.0, "wv": 8.0, "wo": 8.0,
               "w_gate": 6.0, "w_up": 6.0, "w_down": 8.0},
        held=16,
        # 8 x 128 tokens x top-8 of 64 experts: 128 a held expert if even
        engine=dict(calls=4, per_expert=(96, 160), block=256, steps=4,
                    behind="traced"),
        scopes=frozenset(set(S.DEVICE_SCOPES) - {"ds.attn", "ds.mlp"}
                         | set(S.WINDOW_SCOPES) | _MOE),
        two_layers=dict(num_layers=2, layer_types=["sliding_attention",
                                                   "full_attention"]),
        rematted=2),
    "ouro": Family(
        models.Ouro, config="ouro-2.6b-pp6-zero3-1chip", arch="ouro",
        engine=dict(steps=4, behind="traced"),
        scopes=frozenset(set(S.DEVICE_SCOPES) | set(S.LOOP_SCOPES)),
        two_layers=dict(num_layers=2)),
    "qwen3_next": Family(
        # the attention layer's ``w_q`` / ``w_k`` from 2 as the benchmark's
        # configuration sets them
        models.Qwen3Next, cell=dict(moe_held_experts=32, qk_norm_init=2.0),
        config="qwen3-next-80b-ep16-zero3-1chip", arch="qwen3_next",
        # a small embedding under larger values, outputs and experts; a
        # shared expert's gate and a decay off their flat middle; every
        # norm weight drawn (they start at 0 or 1, where ``(1 + w)`` and
        # ``w`` cannot be told from a missing weight); slow heads too: the
        # state has to matter
        boost={"tokens": 0.05, "wv": 4.0, "wo": 8.0, "w_ba": 20.0,
               "w_gate": 6.0, "w_up": 6.0, "w_down": 8.0,
               "shared_gate": 50.0},
        special={**dict.fromkeys(("ln1_scale", "ln2_scale", "scale",
                                  "q_norm", "k_norm", "o_norm"), _norm),
                 "A_log": lambda w, keys: w - 4.0},
        held=32,
        # 8 x 128 tokens x top-10 of 512 experts: 20 a held expert if even
        engine=dict(calls=4, per_expert=(12, 30), block=128, steps=4,
                    behind="one_device"),
        scopes=frozenset(set(S.DEVICE_SCOPES) - {"ds.attn", "ds.mlp"}
                         | set(S.GDN_SCOPES) | set(S.MIXER_SCOPES)
                         | (set(S.KIND_SCOPES) - {"ds.kda", "ds.mla"})
                         | {"ds.rope"}),
        two_layers=dict(num_layers=2, full_attention_interval=2),
        # its one head group keeps nothing of its scans, its gated attention
        # layer's flash kernels do: two such layers, one scan
        kept=dict(num_layers=2, full_attention_interval=1),
        scan=dict(num_layers=1, full_attention_interval=2), scan_runs=3),
    "lfm2_moe": Family(
        # three layers that hold every kind (a dense conv layer, a routed
        # attention layer, a routed conv layer): two layers fewer to
        # compile a case than the preset's own five
        models.Lfm2Moe, cell=dict(moe_held_experts=8, num_layers=3,
                                  layer_types=["conv", "full_attention",
                                               "conv"]),
        config="lfm2-24b-ep8-zero3-1chip", arch="lfm2_moe",
        # a larger table under larger projections, outputs and experts (at
        # the init's own scale a layer of hidden 64 adds a hundredth of the
        # embedding), an expert bias that moves the selection past the
        # mask's margin, every norm weight drawn, scores of deviation 9
        boost={"tokens": 5.0, "w_in": 6.0, "w_out": 8.0, "wv": 4.0,
               "wo": 8.0, "w_gate": 6.0, "w_up": 6.0, "w_down": 8.0,
               "router_bias": 10.0},
        special={**dict.fromkeys(("ln1_scale", "ln2_scale", "scale"), _norm),
                 **dict.fromkeys(("q_norm", "k_norm"), _drawn(3.0, 0.5))},
        held=8,
        # 8 x 128 tokens x top-4 of 64 experts: 64 a held expert if even
        engine=dict(calls=2, per_expert=(40, 90), block=128, steps=4,
                    behind="one_device", bias=("tail", (64,)),
                    bias_tol=(1e-5, 2e-2), cell="train-conv-s8k-1chip"),
        scopes=frozenset(set(S.DEVICE_SCOPES) | set(S.LFM_SCOPES) | _MOE
                         | {"ds.qk_norm", "ds.rope"}),
        two_layers=dict(num_layers=2, layer_types=["full_attention", "conv"],
                        num_dense_layers=0),
        short_conv="its gated convolution is ops.layers.gated_short_conv: "
                   "tests/test_gated_short_conv.py"),
    "xing4_0": Family(
        # three layers that hold both kinds (a leading dense layer, two
        # routed ones under the scan) at the seeded values of the
        # benchmark's configuration (``assumed``: the coefficients' static
        # and input-dependent parts at comparable deviation)
        models.Xing4, cell=dict(
            moe_held_experts=8, num_layers=3, **_overrides(
                "xing4.0-29b-ep8-zero3-1chip", "mhc_alpha_init",
                "mhc_b_std")),
        config="xing4.0-29b-ep8-zero3-1chip", arch="xing4",
        # ... sharper attention scores, alphas apart from one another
        boost={"tokens": 5.0, "wq_b": 3.0, "w_kva": 4.0, "w_kvb": 3.0,
               "wo": 8.0, "w_gate": 6.0, "w_up": 6.0, "w_down": 8.0,
               "router_bias": 10.0},
        special={**dict.fromkeys(("ln1_scale", "ln2_scale", "scale",
                                  "q_norm", "kv_norm"), _norm),
                 "alpha": lambda w, keys: w * jnp.asarray([1.0, 0.7, 1.3],
                                                          w.dtype)},
        held=8,
        # the one-device case steps by its own hook: the Sinkhorn residual
        # is read between the steps
        engine=dict(calls=2, per_expert=(40, 90), steps=3,
                    behind="one_device", one_device_steps=0,
                    bias=("period", (2, 64)), bias_tol=(1e-5, 2e-2),
                    cell="train-mhc-s8k-1chip"),
        scopes=frozenset(set(S.DEVICE_SCOPES) | set(S.MHC_SCOPES) | _MOE
                         | {"ds.rope", "ds.moe_shared"}),
        two_layers=dict(num_layers=2, first_k_dense_replace=1,
                        mhc_alpha_init=(2.0, 2.0, 0.5),
                        mhc_b_std=(2.0, 2.0, 0.5)),
        # the leading dense layer and the scanned routed ones: two traced
        rematted=2),
    "laguna": Family(
        # the preset's own five layers: a leading dense full layer, three
        # routed window layers under the scan, a routed full layer
        models.Laguna, cell=dict(moe_held_experts=8),
        config="laguna-s-2.1-ep32-zero3-1chip", arch="laguna",
        # Mellum's rule (sharper scores, larger values, outputs and
        # experts, the shared expert and the dense lead among them: they
        # share the leaf names); the gate's projection is drawn at unit
        # logits and needs none
        boost={"tokens": 0.02, "wq": 4.0, "wk": 4.0, "wv": 8.0, "wo": 8.0,
               "w_gate": 6.0, "w_up": 6.0, "w_down": 8.0},
        held=8,
        # 8 x 128 tokens x top-10 of 256 experts: 40 a held expert if even
        engine=dict(calls=4, per_expert=(24, 60), block=128, steps=4,
                    behind="traced", cell="train-lag-s8k-1chip"),
        scopes=frozenset(set(S.DEVICE_SCOPES) - {"ds.attn"}
                         | set(S.WINDOW_SCOPES) | set(S.GATE_SCOPES) | _MOE
                         | {"ds.moe_shared"}),
        # a dense window layer and a routed full one: both widths of the
        # attention, both channel mixers
        two_layers=dict(num_layers=2,
                        layer_types=["sliding_attention", "full_attention"],
                        mlp_layer_types=["dense", "sparse"]),
        # the leading layer, the scanned window layers, the full tail
        rematted=3),
    "deepseek_v3": Family(
        # three layers that hold both kinds (a leading dense layer, two
        # routed ones under the scan), every one rotated latent attention
        models.DeepseekV3, cell=dict(moe_held_experts=16, num_layers=3),
        config="kanana-2-30b-ep8-zero3-1chip", arch="deepseek_v3",
        # Xing4.0's rule for the leaves they share (a larger table under
        # sharper scores, larger outputs and experts, a bias that moves the
        # selection past the mask's margin), the direct query in wq_b's
        # place, every norm weight drawn
        boost={"tokens": 5.0, "wq": 3.0, "w_kva": 4.0, "w_kvb": 3.0,
               "wo": 8.0, "w_gate": 6.0, "w_up": 6.0, "w_down": 8.0,
               "router_bias": 10.0},
        special=dict.fromkeys(("ln1_scale", "ln2_scale", "scale",
                               "kv_norm"), _norm),
        held=16,
        # 8 x 128 tokens x top-6 of 128 experts: 48 a held expert if even
        engine=dict(calls=2, per_expert=(30, 70), steps=4,
                    behind="one_device", bias=("period", (2, 128)),
                    bias_tol=(1e-5, 2e-2), cell="train-mla-s32k-1chip"),
        scopes=frozenset(set(S.DEVICE_SCOPES) | _MOE
                         | {"ds.rope", "ds.moe_shared"}),
        two_layers=dict(num_layers=2, first_k_dense_replace=1),
        # the leading dense layer and the scanned routed ones: two traced
        rematted=2),
    "nemotron_h": Family(
        # the preset's own five layers, each ONE sublayer: a routed and a
        # Mamba-2 layer twice under the scan, an attention layer behind them
        models.NemotronH, cell=dict(moe_held_experts=8),
        config="nemotron-3-super-120b-ep64-zero3-1chip", arch="nemotron_h",
        # a larger table under sharper scores, larger values and outputs
        # (Granite's rule for its one attention layer), larger mixers and
        # experts, a bias that moves the selection past the mask's margin,
        # every norm weight drawn (the gated norm's among them: a group's
        # weight has to differ from another's)
        boost={"tokens": 5.0, "wq": 16.0, "wk": 16.0, "wv": 8.0, "wo": 8.0,
               "w_in": 4.0, "w_out": 8.0, "w_dn": 32.0, "w_up": 4.0,
               "w_down": 4.0, "router_bias": 10.0},
        special=dict.fromkeys(("ln1_scale", "scale", "norm"), _norm),
        held=8,
        # 8 x 128 tokens x top-22 of 512 experts: 44 a held expert if even
        engine=dict(calls=2, per_expert=(28, 62), block=128, steps=4,
                    behind="one_device", bias=("period", (2, 512)),
                    bias_tol=(1e-5, 2e-2), cell="train-lmoe-s8k-1chip"),
        scopes=frozenset(set(S.DEVICE_SCOPES) - {"ds.mlp"}
                         | set(S.SSM_SCOPES) | set(S.MIXER_SCOPES) | _MOE
                         | set(S.LATENT_SCOPES) | {"ds.moe_shared"}),
        # three layers hold the three kinds; none repeats, all unrolled
        two_layers=dict(num_layers=3, hybrid_override_pattern="ME*"),
        short_conv="its Mamba-2 mixer is models/stack.py Mamba2, Granite's: "
                   "the granite_hybrid row holds the op's step"),
}
# the rows whose step is rematted with a kept residual in it, a delta-rule
# scan, a short convolution: what the cross-family cases are parametrised by
REMATTED = [n for n, row in FAMILIES.items() if isinstance(row.rematted, int)]
SCANNED = [n for n, row in FAMILIES.items() if isinstance(row.scan, dict)]
SHORT_CONV = [n for n, row in FAMILIES.items()
              if isinstance(row.short_conv, dict)]


def config_of(family: str) -> dict:
    """The family's benchmark configuration file."""
    return json.loads((BENCH / "configs" / f"{FAMILIES[family].config}.json"
                       ).read_text())


def arch_of(family: str):
    """The module of ``benchmark/architectures`` its reference comes from."""
    return importlib.import_module(f"architectures.{FAMILIES[family].arch}")


def tiny(family: str, **kw):
    """The family's ``tiny`` model at its cell's switches; a caller's own
    ``layer_types`` stand for the row's cut of the layers."""
    cell = dict(FAMILIES[family].cell)
    if "layer_types" in kw:
        cell.pop("layer_types", None), cell.pop("num_layers", None)
    return FAMILIES[family].cls(size="tiny", **{**cell, **kw})


def weights(family: str, model, seed=3):
    """Seeded weights under which every part the family adds carries weight
    in the logits at the tiny widths (the row's ``boost`` and ``special``
    say which and why): ONE walk over ``model.init``'s tree."""
    row = FAMILIES[family]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def one(path, w):
        name = path[-1].key
        if name in row.special:
            return row.special[name](w, keys)
        return w * row.boost.get(name, 1.0)

    return jax.tree_util.tree_map_with_path(
        one, model.init(jax.random.PRNGKey(seed)))


@functools.lru_cache(maxsize=None)
def right(family: str, held: int = 0, **kw):
    """``_reference_says`` of the right model's boosted weights with
    ``held`` of the router's experts held (0: the cell's)."""
    model = tiny(family, **kw, **({"moe_held_experts": held} if held else {}))
    return _reference_says(arch_of(family), config_of(family), model,
                           weights(family, model))


# ---- fixtures and inputs ---------------------------------------------------
@pytest.fixture(autouse=True)
def _telemetry_isolation():
    telemetry.shutdown()
    yield
    telemetry.shutdown()


def _err(got, want):
    return float(jnp.max(jnp.abs(got - want))) / (
        float(jnp.max(jnp.abs(want))) + 1e-30)


def _close(got, want, tol, what=""):
    err = _err(got, want)
    assert err <= tol, f"{what}: {err} of {float(jnp.max(jnp.abs(want)))}"


def _batch(model, b=2, s=128, seed=0):
    tok = np.random.default_rng(seed).integers(
        0, model.config.vocab_size, (b, s + 1))
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def _reference_says(arch, config, model, params):
    """``params``, a batch, what the float32 reference ``arch`` says of them
    at the margin of the cell's own ``check`` (loss, tail logits, mask), and
    the reference's model ``m``: what a family's reference comparison and
    its planted faults are both held to."""
    tokens, targets = _batch(model)
    m = modelspec.reference_model(arch, model, config["check"])
    with jax.default_matmul_precision("highest"):
        want = arch.reference(params, tokens, targets, m, 32)
    return params, tokens, targets, want, m


def _reference_grads(arch, params, tokens, targets, m):
    """The gradient of the float32 reference's loss, for an ``arch`` whose
    head is ``lm_head``; one program (eager, every line of the reference
    compiles alone)."""
    def loss(params, tokens, targets):
        hidden, _ = arch._forward(params, tokens, m)
        return arch.loss_of(hidden, params["lm_head"], targets)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.grad(loss))(params, tokens, targets)


def tail_loss_grads(model, params, tokens, targets, grads=True, tail=32):
    """(the tail logits, the loss, its gradients or None) of the program as
    ONE jitted program: eager, every line of an unrolled layer and every
    interpreted kernel call compiles alone."""
    def run(params, batch):
        logits = model.apply(params, batch[0])[:, -tail:]
        if not grads:
            return logits, model.loss(params, batch), None
        return (logits, *jax.value_and_grad(model.loss)(params, batch))
    return jax.jit(run)(params, (tokens, targets))


def kimi_ref_loss(params, tokens, targets, m):
    arch = arch_of("kimi_linear")
    hidden, _ = arch._forward(params, tokens, m)
    return arch.loss_of(hidden, params["lm_head"], targets)


def _kda_inputs(b=2, s=192, h=3, dk=32, dv=16, seed=0):
    rng = np.random.default_rng(seed)
    l2 = lambda x: x / np.sqrt((x ** 2).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = l2(rng.normal(size=(b, s, h, dk))) / np.sqrt(dk)
    k = l2(rng.normal(size=(b, s, h, dk)))
    v = rng.normal(size=(b, s, h, dv))
    g = -np.exp(rng.uniform(-6, 0.5, size=(b, s, h, dk)))
    g[..., 0] = -1.6        # a fast channel: -102 over a chunk of 64
    g[..., 1] = -4.0
    beta = 1 / (1 + np.exp(-rng.normal(size=(b, s, h))))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


def _as_bf16(args):
    """q, k, v rounded to bfloat16 as the model hands them in; g and beta
    stay float32."""
    return [a.astype(jnp.bfloat16) for a in args[:3]] + list(args[3:])


def _walk_eqns(jaxpr):
    """Every equation of ``jaxpr``, through its sub-jaxprs (scan, map,
    remat, custom_vjp) but not into a Pallas kernel's body, whose values
    are VMEM."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _walk_eqns(sub)


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


def step_scopes(hlo: str) -> set:
    """Every ``ds.`` scope a compiled step's ``op_name``s carry."""
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        found.update(re.findall(r"ds\.[A-Za-z0-9_]+", op_name))
    return found


# ---- a program, built once a file ------------------------------------------
def keep_nothing(patch):
    """``jax.checkpoint(policy=None)`` wherever a model asks
    ``_remat_policy``: what every policy name meant before PR 47."""
    for module in (transformer, stack, ouro):
        patch.setattr(module, "_remat_policy", lambda name: None)


# what can be patched in while a program is traced, by name
_PATCHES = {
    None: lambda patch: None,
    "keep_nothing": keep_nothing,
    # ``ops.layers.short_conv`` as PR 43's parent had it
    "short_conv_reference": lambda patch: patch.setattr(
        L, "short_conv", short_conv_reference.short_conv),
    # every ``jax.named_scope`` a null context
    "bare": lambda patch: patch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()),
}
CUTS = ("cell", "two_layers", "kept", "short_conv", "scan")


class Program:
    """One family's tiny model at one cut of its layers, and what is built
    of it; every part is made on first use, under the patch, and kept."""

    def __init__(self, family, cut, dtype, patch, switches):
        self.row, self.dtype, self.patch = FAMILIES[family], dtype, patch
        self.key = (family, cut, dtype, switches)
        cut = {} if cut == "cell" else getattr(self.row, cut)
        assert isinstance(cut, dict), cut
        self.switches = {**self.row.step, **cut, **dict(switches)}

    @contextlib.contextmanager
    def patched(self):
        with pytest.MonkeyPatch.context() as patch:
            _PATCHES[self.patch](patch)
            yield

    @functools.cached_property
    def model(self):
        return tiny(self.key[0], **self.switches)

    @functools.cached_property
    def batch(self):
        """The eight rows an engine case trains on."""
        return _batch(self.model, b=8)

    @functools.cached_property
    def engine(self):
        """``ds.initialize`` under ``DS_CONFIG`` on the eight devices. A
        patch changes what is TRACED, not the state: a patched program
        takes the unpatched one's engine and traces a step of its own."""
        if self.patch is not None:
            return _program(*self.key[:3], None, self.key[3]).engine
        engine, *_ = ds.initialize(model=self.model, config=dict(DS_CONFIG))
        return engine

    @functools.cached_property
    def step(self):
        """The engine's jitted train step; under a patch a new ``jax.jit``
        of the same function (the engine's own keeps its trace)."""
        if self.patch is None:
            return self.engine._train_step
        return self.engine._build_train_step()

    def lower(self, batch=None):
        """The engine's train step lowered on ``batch`` (the eight rows)."""
        engine = self.engine
        with self.patched():
            return self.step.lower(
                engine.state, engine._put_batch(batch or self.batch))

    @functools.cached_property
    def hlo(self) -> str:
        """The compiled train step's text: a whole compile, which the
        engine's first ``train_batch`` finds again (one jitted function)."""
        return self.lower().compile().as_text()

    @functools.cached_property
    def step_kernel_calls(self):
        """The kernels of the engine's train step (eight virtual devices,
        so the scans run per shard), by name."""
        engine = self.engine
        tok = np.zeros((8, self.model.config.max_seq_len), np.int32)
        with self.patched():
            return kernel_calls(self.step, engine.state,
                                engine._put_batch((tok, tok)))

    @functools.lru_cache(maxsize=None)
    def loss_and_grads(self, seed=3):
        """(loss, gradients) of the model alone, one device, on weights of
        ``seed`` cast to the program's dtype and ``_batch``'s two rows."""
        model = self.model
        params = jax.tree.map(lambda x: x.astype(self.dtype),
                              model.init(jax.random.PRNGKey(seed)))

        def loss(p, batch):
            out = model.loss(p, batch)
            return out[0] if isinstance(out, tuple) else out

        with self.patched():
            return jax.device_get(jax.jit(jax.value_and_grad(loss))(
                params, _batch(model)))


@functools.lru_cache(maxsize=None)
def _program(family, cut, dtype, patch, switches):
    return Program(family, cut, dtype, patch, switches)


def program(family, cut="cell", *, dtype="bfloat16", patch=None, **switches):
    """THE memo: the ``Program`` of (family, cut of its layers, weights'
    dtype, what is patched in, further switches), one a file."""
    assert cut in CUTS and patch in _PATCHES, (cut, patch)
    return _program(family, cut, dtype, patch,
                    tuple(sorted(switches.items())))


def flat_grads(grads) -> dict:
    """{path: gradient as float32}, and some gradient is not zero."""
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert any(np.any(np.asarray(g, np.float32) != 0) for _, g in flat)
    return {jax.tree_util.keystr(path): np.asarray(g, np.float32)
            for path, g in flat}

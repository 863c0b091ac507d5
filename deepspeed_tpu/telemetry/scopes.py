"""Device scopes: the names the program gives to what runs on the chip.

The model, the engine's train step and the flash kernels open
``jax.named_scope`` regions whose names start with ``ds.``. A named scope
is HLO metadata: it reaches the compiled program as part of every
instruction's ``op_name`` (``jit(train_step)/jvp(ds.layers)/while/...``
for the forward of the layer scan,
``jit(train_step)/transpose(jvp(ds.layers))/while/...`` for its
backward) and costs nothing at run time. A device trace names each event
by its HLO instruction (``%while.16``), so the join from a trace event to
a scope is the map this module builds from ``Compiled.as_text()``:
instruction name -> scope path.

A scope path is the ``ds.`` names on the instruction's ``op_name`` from
the outside in, joined by ``/``, behind ``fwd:`` or ``bwd:`` where the
instruction was made by differentiation::

    fwd:ds.layers/ds.attn/ds.flash_fwd
    bwd:ds.layers/ds.mlp
    ds.optimizer/ds.grad_clip
    ""                                   (under no scope)

- ``bwd`` is ``transpose(jvp(...))``, and remat's recomputation
  (``rematted_computation``), which runs only in the backward pass;
  ``fwd`` is ``jvp(...)`` alone.
- A fusion is one instruction and carries one ``op_name``: that of its
  root (XLA's choice), so a fusion takes the scope of its root even when
  it fused ops of two scopes.
- An instruction the compiler made without metadata, inside the body of
  a ``while``, ``call`` or ``conditional``, takes the scope of the
  instruction that holds it.

The same walk says what KIND of work each instruction is and how many
bytes stand at its boundary (``op_work``): a scope says whose the time is,
and blurs where XLA fuses across two scopes; the kind is read from the
instruction itself and does not. Exactly one of ``KINDS``:

- ``matmul``: a ``dot`` or ``convolution`` (what the TPU lowers a ``dot``
  to), or a fusion that holds one (its elementwise epilogue counts with
  it);
- ``kernel``: a ``custom-call`` to ``tpu_custom_call`` (a Mosaic kernel);
- ``collective``: ``all-gather``, ``reduce-scatter``, ``all-reduce``,
  ``all-to-all``, ``collective-permute`` and the like, and their
  ``-start`` / ``-done`` halves;
- ``move``: an op that changes where or how data lies and computes
  nothing (``copy``, ``transpose``, ``slice``, ``dynamic-update-slice``,
  ``concatenate``, ``pad``, ``broadcast``, ``bitcast``, ``gather``, a
  ``scatter`` that assigns, ...), and a fusion that holds only such;
- ``elementwise``: every other instruction that does arithmetic (a loop
  or input fusion with no dot, a ``reduce``, a ``select``, a ``convert``);
- ``control``: ``while``, ``call``, ``conditional``, ``tuple``,
  ``get-tuple-element``, ``parameter``, ``constant``: holders and
  bookkeeping, never a leaf with time of its own;
- ``other``: an opcode the tables below do not know. Never silently one
  of the above: the tests hold it to nothing in the steps they compile,
  and whoever meets one extends the table.

``bytes`` is the size of the instruction's result plus its operands, from
the shapes in the text (a tuple: the sum of its parts); for a fusion it
is what crosses its boundary. Two things are counted by what they touch
and not whole: an operand that the instruction, or the fusion's
computation, only slices (``slice``, ``dynamic-slice``: the projection of
which a fusion reads one part, the stacked buffer of which a loop reads
one layer) counts the slices; a ``dynamic-update-slice`` at the root,
which XLA runs in place, counts the update read and written and not the
buffer twice. It is still an UPPER bound on the HBM traffic: an operand
or result the compiler holds in VMEM, or aliases some other way, is
counted.

Host-only text analysis, one walk per executable (telemetry/ledger.py).
The scopes are opened where the work is defined, by name, because the
model and the kernels never import this package (the zero-import
contract); ``tests/test_device_scopes.py`` holds the two lists equal.
"""

from __future__ import annotations

import re
from typing import Optional

from .collectives import _COMPUTATION_RE

# every device scope the program opens, and where
DEVICE_SCOPES = (
    "ds.embed",        # models/transformer.py _final_hidden
    "ds.layers",       # models/transformer.py _final_hidden: the layer scan
    "ds.attn",         # models/transformer.py block: norm, qkv, attention, wo
    "ds.mlp",          # models/transformer.py block: norm and FFN
    "ds.flash_fwd",    # ops/pallas/flash_attention.py _flash_fwd
    "ds.flash_bwd",    # ops/pallas/flash_attention.py _flash_bwd
    "ds.loss_head",    # models/transformer.py: final norm, head, loss
    "ds.optimizer",    # runtime/engine.py _step_parts: finish and update
    "ds.grad_clip",    # the same: the grad norm (finish), the clip (update)
)
# what a stack of several kinds of layer opens inside ds.layers in place of
# ds.attn (models/kimi_linear.py); ``tests/test_kimi_linear.py`` holds this
# list equal to what that model's step carries
KIND_SCOPES = (
    "ds.kda",          # models/kimi_linear.py _mix: KDA's projections,
    #                    convolutions, gates, norm and output matmul
    "ds.kda_scan",     # ops/kda.py chunk_kda: the chunked delta rule (the
    #                    four kernels, a head group after the other, and
    #                    what XLA leaves round them; the backward rule
    #                    opens it again, outside the forward's)
    "ds.kda_prep_fwd",  # ops/pallas/kda.py _prepare_forward: ds_kda_prep_fwd
    "ds.kda_prep_bwd",  # ops/pallas/kda.py _prepare_backward: ds_kda_prep_bwd
    "ds.kda_fwd",      # ops/pallas/kda.py _forward: ds_kda_fwd, either form
    "ds.kda_bwd",      # ops/pallas/kda.py _backward: ds_kda_bwd
    "ds.mla",          # models/kimi_linear.py _mix: latent attention
    #                    (ds.flash_fwd / ds.flash_bwd inside it, and at the
    #                    cell's widths ds.rope of WINDOW_SCOPES: the kernels
    #                    that lay q, k, v out)
    "ds.moe_router",   # moe/sharded_moe.py moe_ffn_held: float32 router;
    #                    at a cell's shapes the selection is the kernels
    #                    ds_router_fwd / ds_router_bwd (ops/pallas/router.py;
    #                    the backward rule opens the scope again)
    "ds.moe_experts",  # moe/sharded_moe.py held_experts_ffn, fwd and bwd:
    #                    the sorts, the gathers and the three kernels
    #                    (the backward rule opens it again)
    "ds.moe_gmm_fwd",  # ops/pallas/grouped_matmul.py forward: ds_moe_gmm_fwd
    "ds.moe_gmm_bwd",  # ops/pallas/grouped_matmul.py backward: ds_moe_gmm_bwd
    "ds.moe_add_rows",  # ops/pallas/grouped_matmul.py add_rows: a chunk's
    #                    rows summed into their tokens, ds_moe_add_rows
    "ds.moe_shared",   # moe/sharded_moe.py moe_ffn_held: the shared expert
)
# what a stack of Mamba-2 and attention layers opens inside ds.layers beside
# ds.attn and ds.mlp (models/granite_hybrid.py); ``tests/
# test_granite_hybrid.py`` holds this list equal to what that model's step
# carries
SSM_SCOPES = (
    "ds.mamba",        # models/granite_hybrid.py, models/nemotron_h.py
    #                    _one_layer: the Mamba-2 mixer of models/stack.py
    #                    Mamba2 (norm, projections, convolution, gate, norm)
    "ds.ssd",          # ops/ssd.py chunk_ssd: the chunked state-space scan
    #                    (the kernels and XLA's copies round them; the
    #                    backward rule opens it again, outside the forward's)
    "ds.ssd_fwd",      # ops/pallas/ssd.py _forward: ds_ssd_fwd, either form
    "ds.ssd_bwd",      # ops/pallas/ssd.py _backward: ds_ssd_bwd
)
# the parts of a recurrent mixer round its scan, opened inside ds.kda and
# ds.mamba alike; both model tests add this list to what they expect. The
# projections need no scope: inside the mixer and outside the scan they
# are what kind "matmul" finds
MIXER_SCOPES = (
    "ds.conv",         # ops/layers.py short_conv: the short convolution
    #                    with its SiLU and, for KDA's q and k, the head's
    #                    l2 norm (KDA's three, Mamba-2's one): the kernels
    #                    ds_short_conv_fwd / ds_short_conv_bwd of
    #                    ops/pallas/short_conv.py and nothing else (the
    #                    backward rule opens the scope itself)
    "ds.mix_pre",      # between the input projections and the scan, without
    #                    the convolution's pass. models/kimi_linear.py _kda:
    #                    beta, the decay's softplus and g;
    #                    models/stack.py Mamba2._mamba: dt's softplus,
    #                    the split into x, B, C
    "ds.mix_post",     # between the scan and the output projection. _kda
    #                    and models/qwen3_next.py _gdn: the gated per-head
    #                    norm as ops/layers.py gated_norm, the kernels
    #                    ds_gated_norm_fwd / ds_gated_norm_bwd of
    #                    ops/pallas/gated_norm.py and nothing else (the op
    #                    opens the scope, in its backward rule too);
    #                    _mamba, still XLA's: D x, the silu(z) gate, the
    #                    gated norm over the whole row or a group's run
)
# what a stack of window and full attention layers, each routed, opens
# inside ds.layers in place of ds.attn (models/mellum.py, models/laguna.py), beside
# ds.moe_router and ds.moe_experts of KIND_SCOPES; ``tests/test_mellum.py``
# holds this list equal to what that model's step carries. The kernels'
# ds.flash_fwd / ds.flash_bwd lie inside the scope of their layer's kind in
# the forward and in the backward rule (remat keeps the forward kernel's
# declared residuals and does not rerun it), so one kind's
# kernel time is read by ``ds\.attn_swa\b.*ds\.flash_``
WINDOW_SCOPES = (
    "ds.attn_swa",     # models/mellum.py _one_layer: a sliding_attention
    #                    layer's norm, projections, rotary, kernel, wo
    "ds.attn_full",    # the same of a full_attention layer
    "ds.rope",         # the rotation of q and k, opened by the op in
    #                    either form: ops/layers.py rotate (apply_rotary,
    #                    XLA's fusions) or, at a lane-aligned head on the
    #                    flash kernels, ops/pallas/rope.py _call: the
    #                    kernels ds_rope_fwd / ds_rope_bwd and nothing else
    #                    (rotation and relayout to [B x H, S, D] in one
    #                    pass; the backward rule opens the scope itself;
    #                    under no ds.flash_* scope). models/transformer.py
    #                    _qkv rotates under ds.attn alone, so a Mistral or
    #                    Ouro step carries ds.rope only where the kernels run.
    #                    In a latent-attention layer (ops/layers.py
    #                    latent_attention) at whole lane tiles of nope and
    #                    value it holds ds_latent_fwd / ds_latent_bwd alone
    #                    (ops/pallas/rope.py _latent_call: the rotation, the
    #                    key's concatenation and q's, k's, v's relayout in
    #                    one pass, also where nothing is rotated: Kimi's
    #                    ds.mla/ds.rope); else apply_rotary's fusions
)
# what a looped stack opens beside ds.attn and ds.mlp (models/ouro.py);
# ``tests/test_ouro.py`` holds this list equal to what that model's step
# carries. The sublayers' output norms lie inside ds.attn / ds.mlp, the T
# exits' head inside ds.loss_head
LOOP_SCOPES = (
    "ds.loop",         # models/ouro.py _exit_states, inside ds.layers: one
    #                    pass's body (the scan over the layers and the
    #                    final norm), so that the loop's own carry, the
    #                    stacking of the exits and the sum of the passes'
    #                    gradients are what ds.layers holds outside it
    "ds.exit_gate",    # models/ouro.py loss, inside ds.loss_head: the
    #                    gate, the exit distribution, its entropy, the
    #                    mixing of the exits' losses and the statistics
)
# what a stack of Gated DeltaNet and gated attention layers, each routed,
# opens inside ds.layers in place of ds.attn (models/qwen3_next.py), beside
# ds.kda_scan and its kernels' scopes, the three ds.moe_* and the two
# grouped-matmul kernels' of KIND_SCOPES, MIXER_SCOPES and ds.rope of
# WINDOW_SCOPES; ``tests/test_qwen3_next_engine.py`` holds the step to
# them. The scan's time in THIS family is read by
# ``ds\.gdn\b.*ds\.kda_scan``, the attention kernels' by
# ``ds\.attn_gated\b.*ds\.flash_``
GDN_SCOPES = (
    "ds.gdn",          # models/qwen3_next.py _one_layer: a Gated DeltaNet
    #                    layer's norm, projections, convolutions (ds.conv),
    #                    beta and the gate a head (ds.mix_pre), the scan
    #                    (ds.kda_scan), the gated norm (ds.mix_post), wo
    "ds.attn_gated",   # the same of a gated attention layer: norm,
    #                    projections, QK-norm, partial rotation (ds.rope),
    #                    kernel, the output's sigmoid gate, wo
    "ds.qk_norm",      # models/qwen3_next.py _attention: the (1 + w)
    #                    RMSNorm of q and k a head, before the rotation
)
# what a stack of gated short-convolution and grouped-query attention layers
# opens inside ds.layers (models/lfm2_moe.py) beside ds.attn and ds.mlp of
# DEVICE_SCOPES (its attention kind and its leading dense layer), ds.qk_norm
# of GDN_SCOPES, ds.rope of WINDOW_SCOPES, ds.moe_router, ds.moe_experts and
# the three grouped-matmul kernels' of KIND_SCOPES (no ds.moe_shared: the
# family has no shared expert); ``tests/test_lfm2_moe_engine.py`` holds the
# step to them
LFM_SCOPES = (
    "ds.gconv",        # models/lfm2_moe.py _one_layer: a conv mixer whole
    #                    (its norm and the three parts below)
    "ds.gconv_in",     # models/lfm2_moe.py _conv: the hidden -> 3 x hidden
    #                    input projection [B | Cg | X]
    "ds.gconv_mix",    # ops/layers.py gated_short_conv: Cg * conv(B * X),
    #                    the kernels ds_gated_conv_fwd / ds_gated_conv_bwd
    #                    of ops/pallas/short_conv.py and nothing else (the
    #                    backward rule opens the scope itself)
    "ds.gconv_out",    # models/lfm2_moe.py _conv: the output projection
)
# what a stack whose residual path is several streams mixed by
# manifold-constrained hyper-connections opens inside ds.layers
# (models/xing4.py) beside ds.attn and ds.mlp of DEVICE_SCOPES (its latent
# attention and its leading dense layers), ds.rope of WINDOW_SCOPES and the
# routed layers' four and their kernels' of KIND_SCOPES;
# ``tests/test_xing4_engine.py`` holds the step to them
MHC_SCOPES = (
    "ds.mhc",          # models/xing4.py _sublayer: both stream passes of a
    #                    sublayer and the coefficients between them (opened
    #                    twice, round the sublayer, which lies outside)
    "ds.mhc_pre",      # ops/mhc.py mhc_pre: the pass in front of a
    #                    sublayer, the kernels ds_mhc_pre_fwd / ds_mhc_pre_bwd
    #                    of ops/pallas/mhc.py and nothing else on the chip
    #                    (_pre_forward, _pre_backward: the backward rule
    #                    opens the scope itself); the jax.numpy form elsewhere
    "ds.mhc_coef",     # H_post's sigmoid, the clamp, exp, the Sinkhorn
    #                    iterations, the residual: on the chip the kernels
    #                    ds_mhc_coef_fwd / ds_mhc_coef_bwd of
    #                    ops/pallas/mhc.py and nothing else (_coef_forward,
    #                    _coef_backward: 128-lane rows in and out); the
    #                    jax.numpy form elsewhere (ops/mhc.py coefficients)
    "ds.mhc_post",     # ops/mhc.py mhc_post: the pass behind a sublayer,
    #                    ds_mhc_post_fwd / ds_mhc_post_bwd of
    #                    ops/pallas/mhc.py (_post_forward, _post_backward)
    "ds.mhc_spread",   # models/xing4.py _layer_stack: the embedding copied
    #                    to the streams
    "ds.mhc_fold",     # models/xing4.py _layer_stack: the streams summed
)
# what a window-and-full stack whose attention output is gated a head opens
# inside ds.attn_swa / ds.attn_full of WINDOW_SCOPES (models/laguna.py),
# beside ds.rope, ds.mlp of DEVICE_SCOPES (its leading dense layer) and the
# routed layers' four and their kernels' of KIND_SCOPES (ds.moe_shared
# among them); ``tests/test_laguna_engine.py`` holds the step to them
GATE_SCOPES = (
    "ds.attn_gate",    # models/laguna.py _attention: the gate's narrow
    #                    projection (hidden -> one column a head, float32
    #                    logits), its sigmoid and the multiply of a head's
    #                    channels in front of wo
)
# what a flash call opens beside ds.flash_fwd / ds.flash_bwd of DEVICE_SCOPES
# where a (batch x head) row is longer than its kernels hold and runs in
# equal spans (ops/pallas/flash_attention.py segments: 32768 keys of 192 in
# models/deepseek_v3.py's published context; gauge ds_flash_segments). A
# DeepSeek-V3 stack's step (plain pre-norm, latent attention of
# models/stack.py LatentAttention in every layer) otherwise carries ds.attn
# and ds.mlp of DEVICE_SCOPES, ds.rope of WINDOW_SCOPES and the routed
# layers' four and their kernels' of KIND_SCOPES (ds.moe_shared among
# them); ``tests/test_deepseek_v3_engine.py`` holds the step to them, and
# ``tests/test_flash_spans.py`` a row in spans to this one
SPAN_SCOPES = (
    "ds.flash_merge",  # ops/pallas/flash_attention.py _spans_fwd,
    #                    _spans_bwd: everything outside the two kernels
    #                    that a row in spans costs: a query span's partial
    #                    outputs merged by their log-sum-exp, the row's
    #                    delta, dq summed over key spans and dk, dv over
    #                    query spans, the concatenations back to the row
    #                    (the backward rule opens it itself)
)
# what a routed layer whose experts work in a latent opens beside the
# routed layers' four and their kernels' of KIND_SCOPES
# (models/nemotron_h.py: a stack whose every layer is ONE sublayer, ds.mamba
# with SSM_SCOPES and MIXER_SCOPES inside it, ds.attn, or a routed layer; no
# ds.mlp); ``tests/test_nemotron_h_engine.py`` holds the step to them
LATENT_SCOPES = (
    "ds.moe_latent",   # moe/sharded_moe.py moe_ffn_held: the projection
    #                    hidden -> latent in front of the held experts and
    #                    latent -> hidden behind them (opened twice, round
    #                    ds.moe_experts, which lies outside)
)
# every list above: what a metric file may name
KNOWN_SCOPES = frozenset(
    DEVICE_SCOPES + KIND_SCOPES + SSM_SCOPES + MIXER_SCOPES + WINDOW_SCOPES
    + LOOP_SCOPES + GDN_SCOPES + LFM_SCOPES + MHC_SCOPES + GATE_SCOPES
    + SPAN_SCOPES + LATENT_SCOPES)
# the scopes that split a train step into disjoint parts; the others lie
# inside one of these
TOP_SCOPES = ("ds.embed", "ds.layers", "ds.loss_head", "ds.optimizer")

_SCOPE_RE = re.compile(r"ds\.[A-Za-z0-9_]+")
_OP_NAME_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_INSTRUCTION_RE = re.compile(r"^\s*(?P<root>ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*")
_CALLED_RE = re.compile(
    r"\b(?:body|condition|to_apply|calls|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_CALLED_LIST_RE = re.compile(
    r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_OPCODE_RE = re.compile(r"\s*([\w\-]+)\(")
_PAREN_RE = re.compile(r"[()]")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_HALF_RE = re.compile(r"-(?:start|update|done)$")
_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_SHAPE_RE = re.compile(r"\b([a-z]+\d+\w*|pred)\[([\d,<=]*)\]")

KINDS = ("matmul", "kernel", "collective", "move", "elementwise", "control",
         "other")
# the opcodes of each kind; one in none of them is "other". An
# asynchronous half is looked up without its -start / -update / -done
_OPCODES = {
    "matmul": ("dot", "convolution"),
    "collective": (
        "all-gather", "reduce-scatter", "all-reduce", "all-to-all",
        "collective-permute", "collective-broadcast", "ragged-all-to-all",
        "send", "recv"),
    "move": (
        "copy", "transpose", "slice", "dynamic-slice",
        "dynamic-update-slice", "concatenate", "pad", "broadcast",
        "reshape", "dynamic-reshape", "bitcast", "bitcast-convert",
        "gather", "reverse"),
    "control": (
        "while", "call", "conditional", "tuple", "get-tuple-element",
        "parameter", "constant", "after-all", "partition-id", "replica-id",
        "opt-barrier", "add-dependency", "domain", "get-dimension-size"),
    "elementwise": (
        "add", "subtract", "multiply", "divide", "remainder", "power",
        "maximum", "minimum", "abs", "negate", "sign", "floor", "ceil",
        "round-nearest-afz", "round-nearest-even", "exponential",
        "exponential-minus-one", "log", "log-plus-one", "sqrt", "rsqrt",
        "cbrt", "tanh", "tan", "sine", "cosine", "atan2", "logistic", "erf",
        "is-finite", "not", "and", "or", "xor", "shift-left",
        "shift-right-arithmetic", "shift-right-logical", "popcnt",
        "count-leading-zeros", "compare", "select", "clamp", "convert",
        "reduce-precision", "stochastic-convert", "real", "imag", "complex",
        "reduce", "reduce-window", "select-and-scatter", "map", "sort",
        "topk", "iota", "rng", "rng-bit-generator",
        "rng-get-and-update-state", "cholesky", "triangular-solve", "fft"),
}
_KIND_OF = {code: k for k, codes in _OPCODES.items() for code in codes}
# custom calls by target: a Mosaic kernel; what the TPU compiler puts in
# for itself (met in the four cells' steps): the halves of a fused
# collective, a buffer reserved, a promise about a gather's indices,
# slices laid end to end, indices packed; and the CPU's top-k (a sort)
_CUSTOM_CALLS = {"tpu_custom_call": "kernel",
                 "AsyncCollectiveStart": "collective",
                 "AsyncCollectiveDone": "collective",
                 "AllocateBuffer": "control",
                 "AssumeGatherIndicesInBound": "control",
                 "ConcatBitcast": "move",
                 "GatherScatterIndicesBitpacked": "elementwise",
                 "TopK": "elementwise"}
# what a fusion (or the computation an async pair wraps) is, from the
# kinds it holds: the first of these that is there; "control" alone is a
# fusion of nothing but parameters and constants, which moves them
_HELD_ORDER = ("other", "kernel", "matmul", "collective", "elementwise",
               "move")
_SLICES = ("slice", "dynamic-slice")
_BYTES = {"pred": 1.0}        # bytes an element; filled as dtypes are met


def scope_of(op_name: str) -> str:
    """The scope path of one ``op_name`` ("" if it names no scope)."""
    names = []
    for n in _SCOPE_RE.findall(op_name):
        if n not in names:      # jvp(ds.x)/.../ds.x names it once
            names.append(n)
    if not names:
        return ""
    if "transpose(" in op_name or "rematted_computation" in op_name:
        direction = "bwd:"
    elif "jvp(" in op_name:
        direction = "fwd:"
    else:
        direction = ""
    return direction + "/".join(names)


def _join(outer: str, inner: str) -> str:
    """``inner`` (an instruction's own path) placed under ``outer`` (the
    path of the instruction that holds it). An inner path that starts at
    a top scope stands alone; one that does not was cut loose from its
    ``jvp(...)`` prefix by the compiler and hangs under the holder's."""
    if not outer:
        return inner
    if not inner:
        return outer
    o_dir, _, o_path = outer.rpartition(":")
    i_dir, _, i_path = inner.rpartition(":")
    if i_path.split("/")[0] in TOP_SCOPES:
        return inner
    have = o_path.split("/")
    path = have + [n for n in i_path.split("/") if n not in have]
    direction = o_dir or i_dir
    return (direction + ":" if direction else "") + "/".join(path)


def _shape_bytes(text: str) -> int:
    """Bytes of every array shape in ``text`` (``bf16[1,8192]{...}``; a
    tuple is the sum of its parts; layouts and memory spaces are passed
    over; a dynamic bound counts in full)."""
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(text):
        width = _BYTES.get(dtype)
        if width is None:           # f32, bf16, s4, f8e4m3fn, c64: the bits
            bits = "".join(ch for ch in dtype.split("e")[0] if ch.isdigit())
            width = _BYTES[dtype] = int(bits) / 8
        n = 1
        for d in dims.replace("<=", "").split(","):
            if d:
                n *= int(d)
        total += n * width
    return int(total)


def _group_end(line: str, start: int) -> int:
    """Index after the parenthesis group that opens at ``line[start]``."""
    depth = 0
    for m in _PAREN_RE.finditer(line, start):
        depth += 1 if m.group() == "(" else -1
        if depth == 0:
            return m.end()
    return len(line)


def op_work(hlo_text: str) -> dict[str, dict]:
    """{instruction name: {"scope", "kind", "bytes", "mixed"}} over every
    instruction of every computation of an optimized HLO module
    (``Compiled.as_text()``): the scope path as ``op_scopes`` gives it,
    the kind of work (one of ``KINDS``), the bytes at the instruction's
    boundary and, for a fusion, whether it fused instructions of another
    scope path than its root's (the module docstring has all four). Names
    carry no ``%``."""
    own: dict[str, str] = {}            # instruction -> its own path
    where: dict[str, str] = {}          # instruction -> its computation
    roots: dict[str, str] = {}          # computation -> ROOT instruction
    caller: dict[str, str] = {}         # computation -> calling instruction
    body: dict[str, str] = {}           # fusion or async op -> computation
    members: dict[str, list] = {}       # computation -> its instructions
    opcode: dict[str, str] = {}
    result: dict[str, int] = {}         # instruction -> bytes of its result
    operands: dict[str, list] = {}      # instruction -> operand names
    target: dict[str, str] = {}         # custom-call -> its target
    number: dict[str, int] = {}         # parameter -> which one it is
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION_RE.match(line)
        if m:
            comp = m.group("name")
            members[comp] = []
            continue
        m = _INSTRUCTION_RE.match(line)
        if m is None or comp is None:
            continue
        name = m.group("name")
        where[name] = comp
        members[comp].append(name)
        if m.group("root"):
            roots[comp] = name
        # "<result shape> <opcode>(<operands>), <attributes>"
        at = m.end()
        shape_end = (_group_end(line, at) if line.startswith("(", at)
                     else line.find(" ", at))
        code = _OPCODE_RE.match(line, shape_end)
        if code is None:
            opcode[name], rest = "", line
            result[name], operands[name] = 0, []
        else:
            args_end = _group_end(line, code.end() - 1)
            args = line[code.end():args_end - 1]
            rest = line[args_end:]
            # both halves of an asynchronous pair are what they wrap
            opcode[name] = _HALF_RE.sub("", code.group(1))
            result[name] = _shape_bytes(line[at:shape_end])
            operands[name] = _OPERAND_RE.findall(args)
            if opcode[name] == "parameter":
                number[name] = int(args) if args.isdigit() else 0
        op = _OP_NAME_RE.search(rest)
        own[name] = scope_of(op.group(1)) if op else ""
        called = _CALLED_RE.findall(rest)
        for group in _CALLED_LIST_RE.findall(rest):
            called += [c.strip().lstrip("%") for c in group.split(",")
                       if c.strip()]
        for c in called:
            caller.setdefault(c, name)
        if called and opcode[name] in ("fusion", "async"):
            body[name] = called[0]
        elif opcode[name] == "scatter" and called:
            body[name] = called[-1]     # to_apply: how updates combine
        elif opcode[name] == "custom-call":
            t = _TARGET_RE.search(rest)
            target[name] = t.group(1) if t else ""

    def named(name: str) -> str:
        """The instruction's own path; a fusion without one has its
        root's."""
        if not own[name] and opcode[name] == "fusion" and name in body:
            return own.get(roots.get(body[name]), "")
        return own[name]

    resolved: dict[str, str] = {}

    def resolve(name: str) -> str:
        if name in resolved:
            return resolved[name]
        resolved[name] = ""             # a cycle cannot occur; be safe
        path = named(name)
        holder = caller.get(where[name])
        if holder is not None and holder in own:
            path = _join(resolve(holder), path)
        resolved[name] = path
        return path

    kinds: dict[str, str] = {}

    def held(name: str) -> str:
        """What the computation ``name`` wraps is, from what it holds."""
        have = {kind(i) for i in members.get(body[name], ())}
        return next((k for k in _HELD_ORDER if k in have), "move")

    def kind(name: str) -> str:
        if name in kinds:
            return kinds[name]
        kinds[name] = "other"           # a cycle cannot occur; be safe
        base = opcode[name]
        if base == "async" and name not in body:
            first = operands[name][:1]  # the -done of an async-start
            k = kind(first[0]) if first and first[0] in opcode else "other"
        elif base in ("fusion", "async"):
            k = held(name) if name in body else "other"
        elif base == "scatter":
            k = "move" if held(name) == "move" else "elementwise"
        elif base == "custom-call":
            k = _CUSTOM_CALLS.get(target.get(name, ""), "other")
        else:
            k = _KIND_OF.get(base, "other")
        kinds[name] = k
        return k

    def mixed(name: str) -> bool:
        if opcode[name] != "fusion" or name not in body:
            return False
        mine = named(name)
        return any(own[i] and own[i] != mine
                   for i in members.get(body[name], ()))

    def crossing(comp: str) -> tuple[dict, Optional[int]]:
        """What a fused computation brings across its boundary in part:
        ({parameter number: bytes read of it} for a parameter that is only
        sliced, or updated in place; the bytes written where the root
        updates a slice of a parameter in place, else None)."""
        users: dict[str, list] = {}
        for i in members.get(comp, ()):
            for k, o in enumerate(operands[i]):
                users.setdefault(o, []).append((i, k))
        reads = {number[p]: min(result[p], sum(result[u] for u, _ in us))
                 for p, us in users.items()
                 if p in number and all(opcode[u] in _SLICES and k == 0
                                        for u, k in us)}
        written = None
        root = roots.get(comp)
        if root and opcode[root] == "dynamic-update-slice":
            into, update = operands[root][:2]
            while opcode.get(into) == "bitcast" and len(users[into]) == 1:
                into = operands[into][0]
            if into in number and len(users[into]) == 1:
                reads[number[into]] = 0
                written = result[update]
        return reads, written

    def size(name: str) -> int:
        known = [result.get(o, 0) for o in operands[name]]
        out = result[name]
        if opcode[name] in _SLICES and known:       # reads what it yields
            known[0] = min(known[0], out)
        elif opcode[name] == "dynamic-update-slice" and len(known) > 1:
            known[0], out = 0, known[1]             # in place
        elif opcode[name] == "fusion" and name in body:
            reads, written = crossing(body[name])
            for i, n in reads.items():
                if i < len(known):
                    known[i] = min(known[i], n)
            if written is not None:
                out = written
        return out + sum(known)

    return {name: {"scope": resolve(name), "kind": kind(name),
                   "bytes": size(name), "mixed": mixed(name)}
            for name in own}


def op_scopes(hlo_text: str) -> dict[str, str]:
    """{instruction name: scope path} over every instruction of every
    computation of an optimized HLO module (``Compiled.as_text()``).
    Names carry no ``%``; "" means the instruction is under no scope."""
    return {name: w["scope"] for name, w in op_work(hlo_text).items()}

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

Host-only text analysis, one walk per executable (telemetry/ledger.py).
The scopes are opened where the work is defined, by name, because the
model and the kernels never import this package (the zero-import
contract); ``tests/test_device_scopes.py`` holds the two lists equal.
"""

from __future__ import annotations

import re

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
    #                    four kernels and XLA's copies round them; the two
    #                    backward rules open it again, outside the forward's)
    "ds.kda_prep_fwd",  # ops/pallas/kda.py _prepare_forward: ds_kda_prep_fwd
    "ds.kda_prep_bwd",  # ops/pallas/kda.py _prepare_backward: ds_kda_prep_bwd
    "ds.kda_fwd",      # ops/pallas/kda.py _forward: ds_kda_fwd, either form
    "ds.kda_bwd",      # ops/pallas/kda.py _backward: ds_kda_bwd
    "ds.mla",          # models/kimi_linear.py _mix: latent attention
    #                    (ds.flash_fwd / ds.flash_bwd inside it)
    "ds.moe_router",   # moe/sharded_moe.py moe_ffn_held: float32 router
    "ds.moe_experts",  # moe/sharded_moe.py held_experts_ffn, fwd and bwd
    "ds.moe_shared",   # moe/sharded_moe.py moe_ffn_held: the shared expert
)
# what a stack of Mamba-2 and attention layers opens inside ds.layers beside
# ds.attn and ds.mlp (models/granite_hybrid.py); ``tests/
# test_granite_hybrid.py`` holds this list equal to what that model's step
# carries
SSM_SCOPES = (
    "ds.mamba",        # models/granite_hybrid.py _one_layer: the Mamba-2
    #                    mixer (norm, projections, convolution, gate, norm)
    "ds.ssd",          # ops/ssd.py chunk_ssd: the chunked state-space scan,
    #                    forward, remat's reruns and backward
)
# the scopes that split a train step into disjoint parts; the others lie
# inside one of these
TOP_SCOPES = ("ds.embed", "ds.layers", "ds.loss_head", "ds.optimizer")

_SCOPE_RE = re.compile(r"ds\.[A-Za-z0-9_]+")
_OP_NAME_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_INSTRUCTION_RE = re.compile(r"^\s*(?P<root>ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=")
_CALLED_RE = re.compile(
    r"\b(?:body|condition|to_apply|calls|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_CALLED_LIST_RE = re.compile(
    r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")


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


def op_scopes(hlo_text: str) -> dict[str, str]:
    """{instruction name: scope path} over every instruction of every
    computation of an optimized HLO module (``Compiled.as_text()``).
    Names carry no ``%``; "" means the instruction is under no scope."""
    own: dict[str, str] = {}            # instruction -> its own path
    where: dict[str, str] = {}          # instruction -> its computation
    roots: dict[str, str] = {}          # computation -> ROOT instruction
    caller: dict[str, str] = {}         # computation -> calling instruction
    fusion_body: dict[str, str] = {}    # fusion instruction -> computation
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION_RE.match(line)
        if m:
            comp = m.group("name")
            continue
        m = _INSTRUCTION_RE.match(line)
        if m is None or comp is None:
            continue
        name = m.group("name")
        where[name] = comp
        if m.group("root"):
            roots[comp] = name
        op = _OP_NAME_RE.search(line)
        own[name] = scope_of(op.group(1)) if op else ""
        called = _CALLED_RE.findall(line)
        for group in _CALLED_LIST_RE.findall(line):
            called += [c.strip().lstrip("%") for c in group.split(",")
                       if c.strip()]
        for c in called:
            caller.setdefault(c, name)
        if " fusion(" in line and called:
            fusion_body[name] = called[0]

    resolved: dict[str, str] = {}

    def resolve(name: str) -> str:
        if name in resolved:
            return resolved[name]
        resolved[name] = ""             # a cycle cannot occur; be safe
        path = own[name]
        if not path and name in fusion_body:
            root = roots.get(fusion_body[name])
            path = own.get(root, "") if root else ""
        holder = caller.get(where[name])
        if holder is not None and holder in own:
            path = _join(resolve(holder), path)
        resolved[name] = path
        return path

    return {name: resolve(name) for name in own}


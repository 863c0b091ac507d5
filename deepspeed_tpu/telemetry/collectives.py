"""HLO collective accounting (ISSUE 5 tentpole part 2).

XLA fuses collectives into the compiled step, so per-op wall time is
unobservable from the host (comm/comm.py logs shapes at trace time and
leaves timing to the profiler). What IS knowable exactly is the
*static* collective content of each compiled executable: this module
walks the optimized HLO text of a registered executable
(``Compiled.as_text()``), finds every
all-reduce/all-gather/reduce-scatter/all-to-all/collective-permute
(sync or async ``-start`` form), decodes the payload bytes from the
result shapes, and attributes each op to the mesh axis (or axis
combination) whose device groups match the instruction's
``replica_groups`` — the T3-style per-axis traffic matrix the overlap
analysis needs.

Combined with the executable ledger's per-executable dispatch counts
and the span tracer's measured window, ``traffic_matrix()`` rows give
honest algbw/busbw LOWER bounds per (axis, op): every dispatched byte
moved somewhere inside the measured window.

Pure host-side text analysis: never imports the model, never runs
device code; one walk per *newly registered executable*, never per
dispatch.
"""

from __future__ import annotations

import collections
import itertools
import re
from typing import Optional

import numpy as np

# HLO primitive -> comm-facade op name (comms_logging.get_bw formulas)
HLO_TO_COMM_OP = {
    "all-reduce": "all_reduce",
    "all-gather": "all_gather",
    "reduce-scatter": "reduce_scatter",
    "all-to-all": "all_to_all",
    "ragged-all-to-all": "all_to_all",
    "collective-permute": "ppermute",
    "collective-broadcast": "broadcast",
}

_OP_RE = re.compile(
    r"=\s*(?P<shapes>[^=]*?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"ragged-all-to-all|collective-permute|collective-broadcast)"
    r"(?P<start>-start)?\(")
_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]*(?:e[0-9a-z]+)?)\[([0-9,]*)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{(\{[0-9,{} ]*\})\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{([0-9,{} ]*)\}")
# computation header: "%name (params) -> type {" / "ENTRY %main (..) -> .. {"
_COMPUTATION_RE = re.compile(
    r"^\s*(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s+\(.*->.*\{\s*$")
_CUSTOM_CALL_RE = re.compile(r'custom_call_target="([^"]+)"')
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_RESULT_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_SLICE_OF_RE = re.compile(r"\bslice\(%?([\w.\-]+)\)")
_PERMUTE_OF_RE = re.compile(r"collective-permute(?:-start)?\(%?([\w.\-]+)")

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "u2": 1, "u4": 1,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}


def _dtype_bytes(name: str) -> int:
    if name in _DTYPE_BYTES:
        return _DTYPE_BYTES[name]
    if name.startswith("f8") or name.startswith("e4") \
            or name.startswith("e5"):
        return 1
    return 4


def _shapes_bytes(text: str, result_half: bool = False) -> tuple[int, int]:
    """(total bytes, total elements) of every ``dtype[dims]`` shape
    token in ``text`` (handles variadic tuple results). The ratio is
    the instruction's effective wire width — 1.x bytes/element once
    qwZ/qgZ put int8/fp8 payloads (plus fp32 block scales) on the
    wire, 4.0 for a plain fp32 collective. ``result_half``: the tuple
    of an async ``all-gather-start`` / ``collective-permute-start``
    carries its operands beside its results (and ``u32[]`` contexts);
    only the results are payload."""
    total = 0
    elements = 0
    shapes = _SHAPE_RE.findall(text)
    if result_half:
        # (operands..., results..., context scalars...): the results
        arrays = [s for s in shapes if s[1]]
        shapes = arrays[len(arrays) // 2:]
    for dtype, dims in shapes:
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _dtype_bytes(dtype)
        elements += n
    return total, elements


def _parse_groups(line: str) -> Optional[list[list[int]]]:
    """Device-id groups from either HLO syntax: literal
    ``{{0,2},{1,3}}`` braces or the iota form
    ``[groups,size]<=[dims]T(perm)``."""
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        groups = []
        for grp in re.findall(r"\{([0-9, ]*)\}", m.group(1)):
            ids = [int(x) for x in grp.replace(" ", "").split(",") if x]
            if ids:
                groups.append(ids)
        return groups or None
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = ids.transpose(perm)
        return [r.tolist() for r in ids.reshape(n_groups, group_size)]
    return None


def mesh_axis_groups(mesh) -> dict[frozenset, str]:
    """{partition-of-device-ids -> axis label} for every non-empty
    combination of the mesh's axes (size-1 groups excluded: they move
    no bytes). A collective whose ``replica_groups`` match one of
    these partitions ran along that axis (combinations label as
    ``"dp+tp"``). Best-effort: an exotic mesh yields fewer matches and
    the caller falls back to an ``"n<group_size>"`` label."""
    if mesh is None:
        return {}
    try:
        devices = np.asarray(mesh.devices)
        ids = np.vectorize(lambda d: int(d.id))(devices)
        axes = list(mesh.axis_names)
    except Exception:
        return {}
    table: dict[frozenset, str] = {}
    n = ids.ndim
    for r in range(1, n + 1):
        for subset in itertools.combinations(range(n), r):
            perm = ([i for i in range(n) if i not in subset]
                    + list(subset))
            grp = ids.transpose(perm).reshape(-1, int(np.prod(
                [ids.shape[i] for i in subset])))
            if grp.shape[1] <= 1:
                continue
            key = frozenset(frozenset(int(x) for x in row)
                            for row in grp)
            # r ascends, so a single axis wins over an equivalent
            # multi-axis flattening of size-1 axes
            table.setdefault(key, "+".join(axes[i] for i in subset))
    return table


def _permute_axis(pairs: list[tuple[int, int]], mesh) -> Optional[str]:
    """Mesh axis a collective-permute rotates along: every
    source->target pair differs in exactly that one mesh coordinate."""
    if mesh is None:
        return None
    try:
        ids = np.vectorize(lambda d: int(d.id))(np.asarray(mesh.devices))
        axes = list(mesh.axis_names)
        coord = {int(ids[idx]): idx for idx in np.ndindex(ids.shape)}
        moved: set[int] = set()
        for s, t in pairs:
            cs, ct = coord[s], coord[t]
            moved |= {i for i in range(len(cs)) if cs[i] != ct[i]}
        if len(moved) == 1:
            return axes[moved.pop()]
    except Exception:
        pass
    return None


def analyze_hlo(hlo_text: str, mesh=None,
                n_devices: Optional[int] = None) -> list[dict]:
    """Per-collective-instruction records
    ``{op, hlo_op, bytes, elements, wire_bytes_per_el, group_size,
    axis, groups}`` from optimized HLO text. ``bytes`` is the full
    logical payload per device group participant (the reference
    comms-logging convention get_bw expects: full tensor for
    all-reduce / gathered output for all-gather / full input for
    reduce-scatter), decoded from the actual result dtypes — an int8
    qwZ/qgZ payload counts 1 byte/element, so the quantized wire's win
    lands in ``ds_hlo_collective_bytes_total{axis,op}`` without any
    assumed element width. Async ``-start`` ops count once; their
    ``-done`` halves are ignored.

    The TPU compiler emits a reduce-scatter as a ``kCustom`` fusion
    whose computation is named ``all-reduce-scatter*`` and holds an
    ``all-reduce`` of the full input followed by a ``dynamic-slice``
    (seen in the v5e HLO of a ZeRO-3 step, PR 21); such an all-reduce
    is recorded as the reduce-scatter it implements.

    Every record carries the instruction's ``op_name`` metadata (the
    jaxpr path with its ``ds.`` scopes: the owner of a collective no
    call site asked for), and a collective-permute that is the TPU
    compiler's own form of a gather or scatter says so under
    ``implements`` (seen in the v5e:2x2 HLO of the ZeRO-3 step, PR 28):
    ``"collective_matmul"`` for a ring step of a windowed einsum (the
    all-gather or reduce-scatter beside a dot, pipelined with it; the
    permute keeps the dot's ``op_name``), ``"reduce_scatter"`` for the
    halo shift after an ``all-reduce-scatter`` fusion whose padded
    shards (8064 rows for 32000 / 4) are not the plan's. Its ``op``
    stays ``ppermute``: that is what is on the wire."""
    axis_table = mesh_axis_groups(mesh)
    records: list[dict] = []
    computation = ""
    scattered: set[str] = set()     # results of all-reduce-scatter fusions
    for line in hlo_text.splitlines():
        header = _COMPUTATION_RE.match(line)
        if header is not None:
            computation = header.group("name")
            continue
        m = _OP_RE.search(line)
        if m is None:
            # a scattered value, or (once there is one) a slice of one
            sliced = scattered and _SLICE_OF_RE.search(line)
            if ("calls=%all-reduce-scatter" in line
                    or (sliced and sliced.group(1) in scattered)):
                result = _RESULT_RE.match(line)
                if result is not None:
                    scattered.add(result.group(1))
            continue
        if "-done" in line.split("=", 1)[0]:
            continue
        hlo_op = m.group("op")
        fused_rs = (hlo_op == "all-reduce"
                    and computation.startswith("all-reduce-scatter"))
        out_bytes, out_elements = _shapes_bytes(
            m.group("shapes"),
            result_half=bool(m.group("start")) and hlo_op in (
                "all-gather", "collective-permute"))
        groups = _parse_groups(line)
        axis = None
        named = _OP_NAME_RE.search(line)
        op_name = named.group(1) if named else ""
        implements = None
        if hlo_op == "collective-permute":
            if op_name.rsplit("/", 1)[-1] == "dot_general":
                implements = "collective_matmul"
            elif _PERMUTE_OF_RE.search(line).group(1) in scattered:
                implements = "reduce_scatter"
            pm = _PAIRS_RE.search(line)
            pairs = []
            if pm:
                pairs = [tuple(int(x) for x in p.replace(" ", "")
                               .split(","))
                         for p in re.findall(r"\{([0-9, ]+)\}",
                                             pm.group(1))]
            group_size = len({d for p in pairs for d in p}) or 2
            axis = _permute_axis(pairs, mesh)
        else:
            if groups:
                group_size = max(len(g) for g in groups)
                key = frozenset(frozenset(g) for g in groups
                                if len(g) > 1)
                axis = axis_table.get(key)
            else:
                group_size = n_devices or (
                    int(np.asarray(mesh.devices).size)
                    if mesh is not None else 0)
                axis = "world" if group_size else None
        if group_size <= 1:
            continue        # degenerate single-participant group
        payload = out_bytes
        elements = out_elements
        if hlo_op == "reduce-scatter":
            payload = out_bytes * group_size
            elements = out_elements * group_size
        if fused_rs:
            # the all-reduce's result IS the full input: already the
            # reduce-scatter payload convention
            hlo_op = "reduce-scatter"
        records.append({
            "op": HLO_TO_COMM_OP[hlo_op],
            "hlo_op": hlo_op + ("-start" if m.group("start") else ""),
            "bytes": int(payload),
            "elements": int(elements),
            "wire_bytes_per_el": (payload / elements if elements
                                  else 0.0),
            "group_size": int(group_size),
            "axis": axis or f"n{group_size}",
            "groups": len(groups) if groups else 1,
            "op_name": op_name,
            **({"implements": implements} if implements else {}),
        })
    return records


def custom_call_targets(hlo_text: str) -> dict[str, int]:
    """``{custom_call_target: instruction count}`` over optimized HLO
    text. A Pallas kernel compiled through Mosaic is a
    ``tpu_custom_call``; in interpret mode it is plain HLO and leaves
    no custom call — so this is how an executable shows that its
    kernels were compiled, not interpreted."""
    return dict(collections.Counter(_CUSTOM_CALL_RE.findall(hlo_text)))


def traffic_matrix(records: list[dict], calls: int = 1) -> dict:
    """Aggregate per-instruction records into the per-(axis, op)
    traffic matrix: ``{(axis, op): {bytes, sites, group_size}}`` where
    ``bytes`` is per-execution payload x ``calls`` dispatches."""
    out: dict = {}
    for r in records:
        key = (r["axis"], r["op"])
        row = out.setdefault(key, {"bytes": 0, "elements": 0,
                                   "sites": 0,
                                   "group_size": r["group_size"]})
        row["bytes"] += r["bytes"] * calls
        row["elements"] += r.get("elements", 0) * calls
        row["sites"] += 1
        row["group_size"] = max(row["group_size"], r["group_size"])
    return out


def bandwidth_bounds(traffic: dict, window_s: float) -> dict:
    """Per-(axis, op) algorithm/bus bandwidth LOWER bounds over a
    measured window: ``{(axis, op): {bytes, group_size, algbw_bytes_
    per_s, busbw_bytes_per_s}}``. Every dispatched byte moved somewhere
    inside the window, so bytes/window is an honest floor; the busbw
    column applies the reference ``get_bw`` op factors. Empty window
    -> empty result (no invented bandwidth). Calibration query for the
    autotuning cost model (ISSUE 7)."""
    if window_s <= 0:
        return {}
    from ..utils.comms_logging import get_bw
    out: dict = {}
    for (axis, op), row in traffic.items():
        if row["bytes"] <= 0:
            continue
        algbw, busbw = get_bw(op, row["bytes"], window_s,
                              max(row["group_size"], 2))
        out[(axis, op)] = {"bytes": row["bytes"],
                           "group_size": row["group_size"],
                           "algbw_bytes_per_s": algbw * 1e9,
                           "busbw_bytes_per_s": busbw * 1e9}
    return out


def axis_bandwidth_bounds(traffic: dict, window_s: float) -> dict:
    """Per-axis fold of :func:`bandwidth_bounds`: total payload bytes
    on the axis over the window — the single-number algbw floor the
    cost model divides candidate traffic by."""
    if window_s <= 0:
        return {}
    out: dict = {}
    for (axis, _op), row in traffic.items():
        if row["bytes"] <= 0:
            continue
        dst = out.setdefault(axis, {"bytes": 0})
        dst["bytes"] += row["bytes"]
    for axis, dst in out.items():
        dst["algbw_bytes_per_s"] = dst["bytes"] / window_s
    return out


def merge_traffic(*matrices: dict) -> dict:
    """Fold several per-executable traffic matrices into one."""
    out: dict = {}
    for mat in matrices:
        for key, row in mat.items():
            dst = out.setdefault(key, {"bytes": 0, "elements": 0,
                                       "sites": 0,
                                       "group_size": row["group_size"]})
            dst["bytes"] += row["bytes"]
            dst["elements"] += row.get("elements", 0)
            dst["sites"] += row["sites"]
            dst["group_size"] = max(dst["group_size"],
                                    row["group_size"])
    return out


def axis_wire_width(traffic: dict) -> dict[str, float]:
    """Per-axis effective wire width (bytes/element) over a traffic
    matrix — the observed number the autotuning calibration records
    (``Calibration.axis_wire_bytes_per_el``): ~4.0 on an fp32 wire,
    ~1.1 once qwZ/qgZ carry int8 payloads + fp32 block scales. Axes
    with no element accounting are omitted."""
    agg: dict[str, list[float]] = {}
    for (axis, _op), row in traffic.items():
        if row.get("elements", 0) > 0:
            a = agg.setdefault(axis, [0.0, 0.0])
            a[0] += row["bytes"]
            a[1] += row["elements"]
    return {axis: b / e for axis, (b, e) in agg.items() if e > 0}

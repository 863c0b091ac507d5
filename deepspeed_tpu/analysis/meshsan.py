"""meshsan — runtime mesh-traffic sanitizer (ISSUE 15 tentpole part 2).

The static SPMD rules (:mod:`.rules.spmd`) check what the *source*
says; this module checks what the *compiler actually emitted*. The
telemetry executable ledger (PR 5) already walks every registered
executable's optimized HLO and decodes each collective's payload bytes,
wire width and mesh axis from its ``replica_groups``
(:mod:`..telemetry.collectives`). :class:`MeshSanitizer` cross-checks
those records against a per-executable **declared traffic contract** —
which axes this jit is allowed to move bytes on, which axes may carry
all-to-all / collective-permute traffic, and what wire width an axis is
configured for — and turns three silent SPMD failure classes into
named findings carrying the executable name, axis, op and bytes:

- **undeclared-axis**: the executable moves bytes on a mesh axis its
  contract never mentions — a sharding-rule regression or an
  unintended GSPMD reshard routed traffic somewhere new;
- **unexpected-op**: ``all-to-all`` / ``collective-permute`` on an
  axis not declared to carry them — the "GSPMD silently resharded"
  signature (a spec mismatch between producer and consumer makes the
  partitioner insert a reshard exchange where none was designed);
- **wire-downgrade**: payload wider than the axis's configured wire
  (fp32 bytes on an axis the ZeRO++ config says runs int8) — the
  quantized wire silently failed to engage and every step pays 4x the
  bandwidth.

Contracts are seeded from the engine/serve-loop call sites (training:
mesh axes >1 plus the ZeRO++ wire flags; inference v2: the tp axis)
and annotatable via the ``meshsan`` config block. Checking happens once
per NEW executable at ledger-registration time (signature-deduped), so
the steady-state dispatch path pays one set lookup.

A per-collective **stall attributor** rides the same records: when the
hang watchdog fires, :meth:`MeshSanitizer.stall_attribution` joins the
flight recorder's last progress event against the registered
executables' collective content, so a wedged multichip run's dump
names the collectives (axis, op, bytes) the stalled dispatch was built
from — not just the host thread stacks
(see :func:`..telemetry.flightrec.dump_state`).

Like blocksan, this module is host-only and stdlib-only (the records
it checks are plain dicts), violations bump
``ds_meshsan_violations_total{kind}`` through the zero-import
telemetry probe, and nothing is imported when the config block is off.
"""

from __future__ import annotations

import os
import threading
from typing import Iterable, Optional

from .blocksan import _count_violation


class MeshSanError(RuntimeError):
    """A declared mesh-traffic contract was violated."""


# collectives.analyze_hlo attributes ops it cannot map to an axis
# combination as "n<group_size>"; those carry no axis NAME to check
def _unattributed(axis: str) -> bool:
    return len(axis) > 1 and axis[0] == "n" and axis[1:].isdigit()


class TrafficContract:
    """What one executable is allowed to put on the wire.

    ``axes``: mesh axes the executable may move bytes on (a combined
    label like ``"fsdp+zps"`` is allowed iff every component is).
    ``all_to_all_axes`` / ``permute_axes``: the subsets that may carry
    all-to-all / collective-permute traffic (a SUBSTANTIAL one showing
    up elsewhere is the GSPMD reshard signature).
    ``wire_bytes_per_el``: ``{axis: {op: max bytes/element}}`` for
    axes with a configured quantized wire (int8 payload + fp32 block
    scales lands ~1.03-1.5 B/el; 2.0 is a safe ceiling). Limits are
    PER OP CLASS because each ZeRO++ flag quantizes one traffic
    direction only: qgZ covers the gradient exchange (all_to_all, and
    the reduce_scatter/all_reduce shapes a disengaged qgZ degrades
    into) while the weight all_gather legitimately stays fp32 unless
    qwZ is also on — an axis-wide ceiling would fail correct
    single-flag configs on their full-precision direction.
    ``min_bytes`` gates the op-class and wire checks: GSPMD routinely
    inserts KILOBYTE-scale reshard shuffles (observed: a 3 KiB
    all-to-all in a plain ZeRO-2 step from a partitioner
    rematerialization) and tiny fp32 control reductions (loss means,
    found-inf flags) are not wire traffic — the findings meshsan hunts
    are the megabyte ones that eat a step's bandwidth. Undeclared-AXIS
    traffic is never size-gated: any byte on an axis the contract
    doesn't mention means the topology assumption itself broke.
    ``allow_world``: whether a full-mesh collective (axis label
    ``"world"``) is expected (training loss reductions are; a serving
    dispatch's usually is not — but mesh-unaware walks also label
    unattributed full-extent groups "world", so default True).
    """

    def __init__(self, axes: Iterable[str] = (),
                 all_to_all_axes: Iterable[str] = (),
                 permute_axes: Iterable[str] = (),
                 wire_bytes_per_el: Optional[dict] = None,
                 min_bytes: int = 65536,
                 allow_world: bool = True):
        self.axes = frozenset(axes)
        self.all_to_all_axes = frozenset(all_to_all_axes)
        self.permute_axes = frozenset(permute_axes)
        # {axis: {op: limit}}; a bare float value means "every op"
        self.wire_bytes_per_el = {
            axis: (dict(v) if isinstance(v, dict) else {"*": float(v)})
            for axis, v in (wire_bytes_per_el or {}).items()}
        self.min_bytes = int(min_bytes)
        self.allow_world = bool(allow_world)

    def _components(self, axis: str) -> list[str]:
        return axis.split("+")

    def axis_declared(self, axis: str) -> bool:
        if axis == "world":
            return self.allow_world
        return all(c in self.axes for c in self._components(axis))

    def op_declared(self, axis: str, op: str) -> bool:
        if op == "all_to_all":
            allowed = self.all_to_all_axes
        elif op == "ppermute":
            allowed = self.permute_axes
        else:
            return True
        return all(c in allowed for c in self._components(axis))

    def wire_limit(self, axis: str, op: str) -> Optional[float]:
        limits = []
        for c in self._components(axis):
            by_op = self.wire_bytes_per_el.get(c)
            if not by_op:
                continue
            lim = by_op.get(op, by_op.get("*"))
            if lim is not None:
                limits.append(float(lim))
        return max(limits) if limits else None

    def to_dict(self) -> dict:
        return {"axes": sorted(self.axes),
                "all_to_all_axes": sorted(self.all_to_all_axes),
                "permute_axes": sorted(self.permute_axes),
                "wire_bytes_per_el": dict(self.wire_bytes_per_el),
                "min_bytes": self.min_bytes,
                "allow_world": self.allow_world}


class MeshSanitizer:
    """See module docstring. One instance audits one engine's
    executables; register per-name contracts with :meth:`declare`, feed
    ledger entries through :meth:`observe_entry` (the engine choke
    points do), or hand synthetic record lists to
    :meth:`check_records` directly (tests, offline HLO audits)."""

    def __init__(self, mode: str = "raise"):
        if mode not in ("raise", "warn"):
            raise ValueError(
                f"meshsan mode must be raise|warn, got {mode!r}")
        self.mode = mode
        self._lock = threading.Lock()
        self.contracts: dict[str, TrafficContract] = {}
        # executables checked already: (name, signature) of each ledger
        # entry — observe_entry is called once per DISPATCH but checks
        # once per executable
        self._seen: set = set()
        # name -> merged per-instruction records, kept for hang-dump
        # stall attribution
        self.records_by_name: dict[str, list[dict]] = {}
        self.counters = {"checked_executables": 0, "violations": 0}
        self.violation_log: list[str] = []

    # -- contracts -----------------------------------------------------
    def declare(self, name: str, contract: TrafficContract) -> None:
        """Register the traffic contract for executables named
        ``name`` (the ledger/span name: ``compiled_step``,
        ``v2/dispatch``, ``v2/fused_dispatch``)."""
        with self._lock:
            self.contracts[name] = contract

    # -- checking ------------------------------------------------------
    def observe_entry(self, entry) -> list[str]:
        """Check one executable-ledger entry (``ExecutableEntry``:
        ``.name``, ``.signature``, ``.collectives``) against its
        contract. Deduped per (name, signature); executables with no
        declared contract are recorded for stall attribution but not
        checked."""
        if entry is None:
            return []
        key = (entry.name, getattr(entry, "signature", None))
        with self._lock:
            if key in self._seen:
                return []
            self._seen.add(key)
        return self.check_records(entry.name,
                                  list(getattr(entry, "collectives", [])))

    def check_records(self, name: str, records: list[dict]) -> list[str]:
        """Core check, synthetic-record friendly: each record is the
        :func:`..telemetry.collectives.analyze_hlo` dict shape
        (``op``, ``axis``, ``bytes``, optional ``wire_bytes_per_el``).
        Returns the finding messages (raised/warned per ``mode``)."""
        with self._lock:
            self.records_by_name.setdefault(name, []).extend(records)
            contract = self.contracts.get(name)
            if contract is not None:
                self.counters["checked_executables"] += 1
        if contract is None:
            return []
        msgs: list[str] = []
        for r in records:
            axis = str(r.get("axis", ""))
            op = str(r.get("op", "?"))
            nbytes = int(r.get("bytes", 0))
            if not axis or _unattributed(axis):
                continue        # no axis name to hold a contract against
            if not contract.axis_declared(axis):
                msgs.append(self._fail(
                    f"executable '{name}': {nbytes} B {op} on "
                    f"UNDECLARED axis '{axis}' (declared: "
                    f"{sorted(contract.axes)}) — a sharding change or "
                    "GSPMD reshard moved traffic onto an axis this "
                    "executable never declared", "undeclared-axis"))
                continue
            # a permute that is the compiler's own form of a gather or
            # scatter (collectives.analyze_hlo ``implements``) is held
            # to that class, which every axis may carry
            if nbytes >= contract.min_bytes \
                    and not (op == "ppermute" and r.get("implements")) \
                    and not contract.op_declared(axis, op):
                owner = (f", op_name '{r['op_name']}'"
                         if r.get("op_name") else "")
                msgs.append(self._fail(
                    f"executable '{name}': unexpected {op} on axis "
                    f"'{axis}' ({nbytes} B{owner}) — the GSPMD "
                    "silent-reshard signature (a producer/consumer "
                    "spec mismatch makes the partitioner insert an "
                    "exchange no call site asked for)",
                    "unexpected-op"))
                continue
            limit = contract.wire_limit(axis, op)
            wpe = float(r.get("wire_bytes_per_el", 0.0) or 0.0)
            if limit is not None and nbytes >= contract.min_bytes \
                    and wpe > limit:
                msgs.append(self._fail(
                    f"executable '{name}': wire downgrade on axis "
                    f"'{axis}' — {nbytes} B {op} at "
                    f"{wpe:.2f} B/element exceeds the configured "
                    f"{limit:.2f} B/element (quantized wire did not "
                    "engage; every step pays the full-precision "
                    "bandwidth)", "wire-downgrade"))
        return msgs

    def _fail(self, msg: str, kind: str) -> str:
        with self._lock:
            self.counters["violations"] += 1
            self.violation_log.append(msg)
        _count_violation("ds_meshsan_violations_total", kind)
        if self.mode == "raise":
            raise MeshSanError(f"meshsan: {msg}")
        from ..utils.logging import logger
        logger.warning(f"meshsan: {msg}")
        return msg

    # -- stall attribution ---------------------------------------------
    # flight-recorder progress keys -> the executable whose dispatch
    # they heartbeat (v2_dispatch carries the span name in its meta)
    _PROGRESS_TO_EXEC = {"train_batch": "compiled_step"}

    def stall_attribution(self, events: list[dict],
                          top: int = 6) -> Optional[dict]:
        """Join the flight recorder's most recent dispatch heartbeat
        against the registered executables' collective content: the
        hang dump names the collectives (axis, op, bytes) the stalled
        dispatch was built from, which on a wedged multichip run is the
        set the program died inside. ``events`` is
        ``FlightRecorder.events()`` (slot-ordered); returns None when
        nothing attributable was recorded."""
        for ev in reversed(events or []):
            name = str(ev.get("name", ""))
            meta = ev.get("meta") or {}
            exec_name = (meta.get("span")
                         or self._PROGRESS_TO_EXEC.get(name)
                         or (name if name in self.records_by_name
                             else None))
            if exec_name is None or exec_name not in self.records_by_name:
                continue
            recs = self.records_by_name[exec_name]
            ranked = sorted(recs, key=lambda r: -int(r.get("bytes", 0)))
            return {
                "last_progress": name,
                "executable": exec_name,
                "n_collectives": len(recs),
                "collectives": [
                    {"axis": r.get("axis"), "op": r.get("op"),
                     "bytes": int(r.get("bytes", 0)),
                     "group_size": r.get("group_size")}
                    for r in ranked[:top]],
                "hint": ("the stalled dispatch contains these "
                         "collectives; on a multi-host hang, one of "
                         "them is the rendezvous some rank never "
                         "reached"),
            }
        return None

    # -- reporting -----------------------------------------------------
    def snapshot(self) -> dict:
        """Hang-dump / forensics view (telemetry/flightrec.py embeds
        this in every watchdog dump while meshsan is active)."""
        with self._lock:
            return {
                "mode": self.mode,
                "counters": dict(self.counters),
                "violations": list(self.violation_log[-16:]),
                "contracts": {n: c.to_dict()
                              for n, c in self.contracts.items()},
                "executables": {
                    n: len(recs)
                    for n, recs in self.records_by_name.items()},
            }


# --- contract seeding (engine / serve-loop call sites) --------------------


# the HLO op classes each ZeRO++ wire flag quantizes: qgZ's gradient
# exchange is an all-to-all (and a DISENGAGED qgZ degrades into the
# plain reduce_scatter/all_reduce it replaced — exactly the fp32 shape
# the ceiling must catch); qwZ covers the weight all-gather
_QGZ_WIRE_OPS = ("all_to_all", "reduce_scatter", "all_reduce")
_QWZ_WIRE_OPS = ("all_gather",)


def seed_training_contract(axis_sizes: dict,
                           quantized_gradients: bool = False,
                           quantized_weights: bool = False,
                           min_bytes: int = 65536,
                           moe_dispatch: bool = False,
                           moe_quantized_dispatch: bool = False
                           ) -> TrafficContract:
    """The compiled train step's contract, derived from the mesh
    topology and the ZeRO++ wire flags exactly as the engine configures
    them: bytes may move on every mesh axis with extent > 1; all-to-all
    is expected on ``sp`` (Ulysses) / ``ep`` (MoE dispatch) and — when
    qgZ is on — on the sharded-DP axes the quantized gradient exchange
    runs over (the hierarchical two-hop variant exchanges over fsdp and
    zps individually, both already in the set); collective-permute on
    ``pp`` (pipeline) and ``sp`` (ring attention). Sharded-DP axes
    carry a <= 2.0 B/element wire ceiling PER QUANTIZED DIRECTION
    (int8 payload + fp32 block scales is ~1.03-1.5): qgZ limits the
    gradient-exchange op class, qwZ the weight all-gather — the other
    direction legitimately stays fp32 when its flag is off.

    ``moe_dispatch`` (ISSUE 16): the engine's ep-sharded MoE dispatcher
    routes the token shuffle through an explicit capacity
    reduce-scatter/all-gather over the TOKEN axes (dp/fsdp/zps), which
    XLA is free to lower as all-to-all + local reduce — those axes join
    the expected-a2a set whenever the dispatcher is engaged, so a
    dispatch landing on any OTHER axis (a mis-sharded table) is still a
    named finding. No wire ceiling rides the MoE a2a op class even for
    an int8/fp8 wire (``moe_quantized_dispatch``): the combine leg and
    the dispatch transpose legitimately stay full-precision and lower
    to all-to-alls on the SAME (axis, op) buckets, so an aggregate
    ceiling there would flag correct programs — the int8 dispatch-byte
    claim is audited per op from the ledger's HLO accounting instead
    (``ds_hlo_collective_bytes_total{axis,op}``; tests/test_moe.py)."""
    live = {a for a, n in (axis_sizes or {}).items() if int(n) > 1}
    a2a = {"sp", "ep"} & live
    if quantized_gradients:
        a2a |= {"fsdp", "zps"} & live
    if moe_dispatch or moe_quantized_dispatch:
        a2a |= {"dp", "fsdp", "zps"} & live
    wire_ops: dict[str, float] = {}
    if quantized_gradients:
        wire_ops.update({op: 2.0 for op in _QGZ_WIRE_OPS})
    if quantized_weights:
        wire_ops.update({op: 2.0 for op in _QWZ_WIRE_OPS})
    wire = ({a: dict(wire_ops) for a in ("fsdp", "zps") if a in live}
            if wire_ops else {})
    if (moe_dispatch or moe_quantized_dispatch) and wire:
        # qgZ's a2a ceiling cannot coexist with an engaged MoE
        # dispatcher: the full-precision combine/transpose legs of the
        # token shuffle share those (axis, op) buckets (see above)
        for by_op in wire.values():
            by_op.pop("all_to_all", None)
    return TrafficContract(
        axes=live,
        all_to_all_axes=a2a,
        permute_axes={"pp", "sp"} & live,
        wire_bytes_per_el=wire,
        min_bytes=min_bytes,
        allow_world=True)


def seed_serving_contract(tp: int = 1,
                          min_bytes: int = 65536) -> TrafficContract:
    """The inference v2 dispatch families' contract: a tp-sharded
    forward moves bytes on ``tp`` only (the output-projection
    all-reduce and kv-head gathers); an all-to-all or permute anywhere
    is the reshard signature, and any OTHER axis carrying traffic means
    the serving params/pools picked up a training-style sharding."""
    return TrafficContract(
        axes={"tp"} if int(tp) > 1 else set(),
        all_to_all_axes=(),
        permute_axes=(),
        min_bytes=min_bytes,
        allow_world=True)


# --- process-wide handle for forensics (hang dumps) -----------------------
# Engines register their sanitizer here so the hang watchdog can embed
# contract state + stall attribution without holding an engine
# reference; last-enabled wins (exact for one-engine processes).

_SAN: Optional[MeshSanitizer] = None


def get_meshsan() -> Optional[MeshSanitizer]:
    return _SAN


def set_meshsan(san: Optional[MeshSanitizer]) -> None:
    global _SAN
    _SAN = san


def env_enabled() -> bool:
    """The ``DS_MESHSAN=1`` env knob (conftest/CI opt-in), mirroring
    ``DS_GRAFTSAN``."""
    return os.environ.get("DS_MESHSAN", "") not in ("", "0")

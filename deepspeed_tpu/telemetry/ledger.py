"""Executable cost/memory ledger (ISSUE 5 tentpole part 1).

Host-side telemetry (PR 2) can time dispatches but knows nothing about
what a compiled step *costs*: FLOPs, HBM traffic, peak device memory.
XLA does — ``Compiled.cost_analysis()`` / ``memory_analysis()`` carry
the compiler's own accounting of the fused, optimized program. The
ledger keeps one entry per ``(jit name, abstract operand signature)``:
call sites hand it the jitted callable plus the operands of a dispatch
(``observe()``), and on FIRST sight of a signature it compiles the same
AOT path the flops profiler uses (``profiler.lower_compiled`` — cached
by jax per signature, so this costs ONE extra backend compile per new
executable during warmup and a dict lookup afterwards), records the
normalized cost/memory analysis, and — when a mesh is given — walks
the optimized HLO for the collective traffic matrix
(:mod:`.collectives`). One more walk of the same text gives every
instruction its device scope, its kind of work and the bytes at its
boundary (:mod:`.scopes`): the join from a device trace's events to what
the program names.

Ledger entry names match the span names of the same call sites
(``compiled_step``, ``v2/dispatch``, ``v2/fused_dispatch``):
``step_seconds_by_name()`` joins dispatch counts against the span
tracer's measured seconds.

Everything here is host-only API (graftlint GL041): nothing may be
called from jit-reachable code.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Optional

from . import collectives as _collectives, scopes as _scopes


def _signature(args, kwargs) -> tuple:
    """Abstract (shape, dtype) tuple over the flattened operands —
    the executable-cache key modulo sharding. Works on donated/deleted
    arrays (avals survive donation) and plain numpy/python leaves."""
    import jax
    sig = []
    for leaf in jax.tree_util.tree_leaves((args, kwargs or {})):
        shape = getattr(leaf, "shape", None)
        if shape is None:
            sig.append((type(leaf).__name__,))
        else:
            sig.append((tuple(int(d) for d in shape),
                        str(getattr(leaf, "dtype", "?"))))
    return tuple(sig)


class ExecutableEntry:
    """Ledger row for one compiled executable."""

    __slots__ = ("name", "signature", "flops", "bytes_accessed",
                 "memory", "collectives", "traffic", "custom_calls",
                 "op_work", "calls", "registered_unix",
                 "register_error")

    def __init__(self, name: str, signature: tuple):
        self.name = name
        self.signature = signature
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.memory: dict = {}
        self.collectives: list[dict] = []
        self.traffic: dict = {}
        # {custom_call_target: count}; "tpu_custom_call" = a Pallas
        # kernel compiled through Mosaic (absent in interpret mode)
        self.custom_calls: dict[str, int] = {}
        # {HLO instruction name: {"scope", "kind", "bytes", "mixed"}}
        # (scopes.op_work): what a trace event named by its instruction
        # is joined through to the program's device scope, the kind of
        # work it is and the bytes at its boundary
        self.op_work: dict[str, dict] = {}
        self.calls = 0
        self.registered_unix = time.time()
        self.register_error = ""

    @property
    def peak_hbm_bytes(self) -> int:
        return int(self.memory.get("peak", 0))

    def signature_str(self) -> str:
        parts = []
        for leaf in self.signature:
            if len(leaf) == 2:
                shape, dtype = leaf
                parts.append(dtype + "[" + ",".join(map(str, shape))
                             + "]")
            else:
                parts.append(str(leaf[0]))
        return " ".join(parts)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "signature": self.signature_str(),
            "n_operands": len(self.signature),
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "arithmetic_intensity": (
                self.flops / self.bytes_accessed
                if self.bytes_accessed else 0.0),
            "memory": dict(self.memory),
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "calls": self.calls,
            "collectives": list(self.collectives),
            "custom_calls": dict(self.custom_calls),
            "register_error": self.register_error,
        }


class ExecutableLedger:
    """Process-wide registry of compiled executables' device-truth
    cost. Thread-safe; ``observe()`` is cheap after first registration
    (a comparison where the caller hands its ``struct``, else the
    signature walk + dict lookup) and NEVER raises — a broken cost
    model must not take down the training step it measures."""

    def __init__(self, hlo_collectives: bool = True):
        self.hlo_collectives = bool(hlo_collectives)
        self._lock = threading.Lock()
        self._entries: dict[tuple, ExecutableEntry] = {}
        # name -> (jitted, struct, entry) of the last observation that
        # came with a ``struct``: observe()'s short path
        self._last: dict[str, tuple] = {}
        # compile-path seconds by phase, fed by the jax.monitoring
        # listener in bridges.py (covers EVERY compile in the process,
        # including ones the ledger never sees an observe() for)
        self.compile_seconds: dict[str, float] = {}
        self.compile_events: dict[str, int] = {}

    # -- registration --------------------------------------------------
    def observe(self, name: str, jitted, args: tuple = (),
                kwargs: Optional[dict] = None, mesh=None,
                n_devices: Optional[int] = None,
                struct=None) -> Optional[ExecutableEntry]:
        """Count one dispatch of ``jitted`` at these operands,
        registering cost/memory/collective analysis on first sight of
        the (name, signature) pair. Call BEFORE the dispatch when any
        operand is donated. Returns the entry (None only if even the
        signature walk failed).

        ``struct`` is the caller's word for the operands' structure
        (anything comparable; None = no word): while ``jitted`` is the
        same object and ``struct`` equals the one it came with the last
        time under this name, the operands are taken to be what they
        were and the entry found then is counted again, with no walk
        over the leaves. The walk runs at first sight and whenever
        either changes, so a new shape still registers."""
        last = self._last.get(name) if struct is not None else None
        if last is not None and last[0] is jitted and last[1] == struct:
            entry = last[2]
            with self._lock:
                entry.calls += 1
            return entry
        try:
            key = (name, _signature(args, kwargs))
        except Exception:
            return None
        with self._lock:
            entry = self._entries.get(key)
            new = entry is None
            if new:
                entry = self._entries[key] = ExecutableEntry(name, key[1])
            entry.calls += 1
            if struct is not None:
                self._last[name] = (jitted, struct, entry)
        if new:
            self._register(entry, jitted, args, kwargs or {}, mesh,
                           n_devices)
        return entry

    def _register(self, entry: ExecutableEntry, jitted, args, kwargs,
                  mesh, n_devices) -> None:
        from ..profiling.flops_profiler.profiler import (
            compiled_cost, compiled_memory, lower_compiled)
        try:
            compiled = lower_compiled(jitted, *args, **kwargs)
        except Exception as e:   # noqa: BLE001 - telemetry never raises
            entry.register_error = f"{type(e).__name__}: {e}"[:200]
            return
        cost = compiled_cost(compiled)
        entry.flops = cost.get("flops", 0.0)
        entry.bytes_accessed = cost.get("bytes accessed", 0.0)
        entry.memory = compiled_memory(compiled)
        try:
            hlo = compiled.as_text()
            entry.op_work = _scopes.op_work(hlo)
            if self.hlo_collectives:
                entry.collectives = _collectives.analyze_hlo(
                    hlo, mesh=mesh, n_devices=n_devices)
                entry.traffic = _collectives.traffic_matrix(
                    entry.collectives)
                entry.custom_calls = _collectives.custom_call_targets(hlo)
        except Exception as e:   # noqa: BLE001
            entry.register_error = (
                f"hlo: {type(e).__name__}: {e}"[:200])

    def on_compile_event(self, phase: str, dur_s: float) -> None:
        with self._lock:
            self.compile_seconds[phase] = (
                self.compile_seconds.get(phase, 0.0) + dur_s)
            self.compile_events[phase] = (
                self.compile_events.get(phase, 0) + 1)

    # -- readers -------------------------------------------------------
    def entries(self) -> list[ExecutableEntry]:
        with self._lock:
            return list(self._entries.values())

    def __len__(self) -> int:
        return len(self._entries)

    def dispatched_flops(self) -> dict[str, float]:
        """{name: flops x calls summed over signatures}."""
        out: dict[str, float] = {}
        for e in self.entries():
            out[e.name] = out.get(e.name, 0.0) + e.flops * e.calls
        return out

    def peak_hbm_by_name(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for e in self.entries():
            out[e.name] = max(out.get(e.name, 0), e.peak_hbm_bytes)
        return out

    def calls_by_name(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for e in self.entries():
            out[e.name] = out.get(e.name, 0) + e.calls
        return out

    def traffic(self) -> dict:
        """Dispatch-weighted per-(axis, op) traffic matrix over every
        registered executable: static bytes per execution x calls."""
        return _collectives.merge_traffic(
            *(_collectives.traffic_matrix(e.collectives, e.calls)
              for e in self.entries()))

    # -- calibration queries (ISSUE 7: consumed by autotuning) ---------
    def step_seconds_by_name(self, span_totals: dict) -> dict:
        """{name: {"seconds_per_call", "calls", "flops_per_call"}}
        joining ledger dispatch counts against measured span seconds
        (pass ``SpanTracer.totals_trimmed()`` so the warmup span's XLA
        compile doesn't pollute the rate). Names with no measured
        window are omitted."""
        calls = self.calls_by_name()
        flops = self.dispatched_flops()
        out: dict = {}
        for name, n in calls.items():
            tot = span_totals.get(name)
            if not tot or tot[0] <= 0 or tot[1] <= 0:
                continue
            seconds, count = float(tot[0]), int(tot[1])
            out[name] = {
                "seconds_per_call": seconds / count,
                "calls": n,
                "flops_per_call": flops.get(name, 0.0) / max(n, 1),
            }
        return out

    def effective_flops_per_s(self, span_totals: dict) -> dict:
        """{name: measured FLOPs/s} — the autotuner's calibration rate:
        per-dispatch executable FLOPs over per-dispatch measured span
        seconds. A lower bound on device throughput (span time includes
        host overhead around the device work)."""
        out: dict = {}
        for name, row in self.step_seconds_by_name(span_totals).items():
            if row["flops_per_call"] > 0 and row["seconds_per_call"] > 0:
                out[name] = row["flops_per_call"] / row["seconds_per_call"]
        return out

    def axis_algbw_bounds(self, window_s: float) -> dict:
        """{axis: {"bytes", "algbw_bytes_per_s"}} lower bounds from the
        dispatch-weighted HLO traffic matrix over a measured window:
        every dispatched byte moved somewhere inside the window, so
        bytes/window is an honest floor on per-axis achieved algorithm
        bandwidth (see :func:`.collectives.bandwidth_bounds`)."""
        return _collectives.axis_bandwidth_bounds(self.traffic(),
                                                  window_s)

    def axis_wire_bytes_per_el(self) -> dict:
        """{axis: observed wire bytes/element} over every registered
        executable's collective traffic — 4.0 on an fp32 wire, ~1.1
        once the ZeRO++ quantized collectives carry int8 payloads +
        fp32 block scales. Recorded into autotuning calibrations
        (``Calibration.axis_wire_bytes_per_el``) so plan artifacts
        show which wire the bandwidth floors were measured at."""
        return _collectives.axis_wire_width(self.traffic())

    def collective_bytes_by_axis(self, name: str) -> dict:
        """{axis: per-DISPATCH collective payload bytes} for one jit
        name, call-weighted across its live signatures — the comm
        baseline a calibration fitted on this executable's measured
        rate already contains (the cost model charges only excess)."""
        totals: dict[str, float] = {}
        calls = 0
        for e in self.entries():
            if e.name != name or e.calls <= 0:
                continue
            calls += e.calls
            for (axis, _op), row in _collectives.traffic_matrix(
                    e.collectives, e.calls).items():
                totals[axis] = totals.get(axis, 0.0) + row["bytes"]
        if calls <= 0:
            return {}
        return {axis: b / calls for axis, b in totals.items()}

    def op_work_by_name(self) -> dict[str, dict[str, dict]]:
        """{entry name: {instruction name: {"scope", "kind", "bytes",
        "mixed"}}}; the signatures of one name are merged, the most
        dispatched last (it wins where two executables share an
        instruction name)."""
        out: dict[str, dict[str, dict]] = {}
        for e in sorted(self.entries(), key=lambda e: e.calls):
            if e.op_work:
                out.setdefault(e.name, {}).update(e.op_work)
        return out

    def op_scopes_by_name(self) -> dict[str, dict[str, str]]:
        """{entry name: {instruction name: scope path}}, of
        ``op_work_by_name``."""
        return {name: {op: w["scope"] for op, w in rows.items()}
                for name, rows in self.op_work_by_name().items()}

    def snapshot(self) -> dict:
        rows = sorted((e.to_dict() for e in self.entries()),
                      key=lambda r: (-r["flops"] * r["calls"],
                                     r["name"]))
        traffic = {f"{axis}/{op}": dict(row) for (axis, op), row
                   in sorted(self.traffic().items())}
        return {"executables": rows,
                "n_executables": len(rows),
                "traffic": traffic,
                "compile_seconds": dict(self.compile_seconds),
                "compile_events": dict(self.compile_events)}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._last.clear()
            self.compile_seconds.clear()
            self.compile_events.clear()


# --- module-level current ledger (wired by telemetry.configure) ---------

_LEDGER: Optional[ExecutableLedger] = None


def get_ledger() -> Optional[ExecutableLedger]:
    return _LEDGER


def set_ledger(ledger: Optional[ExecutableLedger]) -> None:
    global _LEDGER
    _LEDGER = ledger

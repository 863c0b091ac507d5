"""Unified telemetry (ISSUE 2 tentpole): span tracing + metrics registry
+ Perfetto/Prometheus export across training and serving.

Three parts:

- :mod:`.spans` — host-side span tracer (context manager + decorator,
  nested, per-rank ring buffer) that mirrors each span into a
  ``jax.profiler.TraceAnnotation`` (XPlane) and exports
  Chrome-trace-event JSON loadable in Perfetto.
- :mod:`.registry` — process-wide Counter/Gauge/Histogram registry with
  ``snapshot()``, JSON dump, and Prometheus text exposition.
- :mod:`.bridges` — collectors from existing sources (jax compile
  events, ThroughputTimer, CommsLogger, serving_metrics, memory) and a
  registry -> MonitorMaster flush.

Activation::

    from deepspeed_tpu import telemetry
    telemetry.configure()                  # or via the engine's
                                           # {"telemetry": {"enabled": true}}
    ... run training / serving ...
    telemetry.export_artifacts("/tmp/tel", prefix="run1")

Overhead contract: nothing in this package is imported by the framework
until telemetry is activated; instrumented call sites probe
``sys.modules`` for this module instead of importing it, so a
telemetry-disabled run allocates no tracer/registry state and pays one
dict lookup per *dispatch* (never per token). See docs/observability.md.
"""

from __future__ import annotations

import os
from typing import Optional

from . import (bridges, collectives, flightrec as _flightrec_mod,  # noqa: F401
               fleet as _fleet_mod, health as _health_mod,
               ledger as _ledger_mod, registry as _registry_mod,
               reqtrace as _reqtrace_mod, scopes, spans as _spans_mod,
               steptrace as _steptrace_mod, timeseries as _timeseries_mod)
from .fleet import FleetScope, get_fleet  # noqa: F401
from .flightrec import (FlightRecorder, HangWatchdog,  # noqa: F401
                        get_flight_recorder, get_watchdog)
flightrec = _flightrec_mod   # public alias for instrumented call sites
from .health import HealthMonitor, get_health_monitor  # noqa: F401
from .ledger import ExecutableLedger, get_ledger  # noqa: F401
from .registry import (Counter, Gauge, Histogram,  # noqa: F401
                       MetricsRegistry, get_registry)
from .reqtrace import (RequestTraceRecorder,  # noqa: F401
                       get_request_recorder)
from .spans import NULL_CONTEXT, SpanTracer, get_tracer  # noqa: F401
from .steptrace import (StepTraceRecorder,  # noqa: F401
                        get_step_recorder)
from .timeseries import TimeSeriesRing, get_timeseries  # noqa: F401

_ACTIVE = False
_ARTIFACT_DIR = "telemetry_hangdump"
_BURN_WINDOWS_S = _timeseries_mod.DEFAULT_BURN_WINDOWS_S


def is_active() -> bool:
    """True iff ``configure()`` ran (and ``shutdown()`` has not)."""
    return _ACTIVE


def configure(config=None, *, span_buffer_size: Optional[int] = None,
              profiler_annotations: Optional[bool] = None,
              jax_compile_events: Optional[bool] = None,
              executable_ledger: Optional[bool] = None,
              hlo_collectives: Optional[bool] = None,
              flight_recorder: Optional[bool] = None,
              flight_recorder_size: Optional[int] = None,
              watchdog_deadline_s: Optional[float] = None,
              watchdog_artifact_dir: Optional[str] = None,
              watchdog_abort: Optional[bool] = None,
              request_traces: Optional[bool] = None,
              request_trace_size: Optional[int] = None,
              steptrace: Optional[bool] = None,
              steptrace_size: Optional[int] = None,
              steptrace_regression_window: Optional[int] = None,
              steptrace_regression_threshold: Optional[float] = None,
              fleet: Optional[bool] = None,
              fleet_replica: Optional[str] = None,
              timeseries_capacity: Optional[int] = None,
              timeseries_interval_s: Optional[float] = None,
              burn_windows_s=None) -> None:
    """Activate telemetry for this process. ``config`` may be the
    engine's ``TelemetryConfig`` block; keyword overrides win.
    Idempotent: re-configuring while active keeps the existing
    tracer/registry (so engine init cannot wipe a bench harness's
    already-collected spans).

    The device-truth layer (ISSUE 5) is opt-in on top: the executable
    ledger + HLO collective accounting (``executable_ledger``), and
    the flight recorder + hang watchdog (``flight_recorder`` /
    ``watchdog_deadline_s``)."""
    global _ACTIVE
    if _ACTIVE:
        return

    def pick(kw, attr, default):
        if kw is not None:
            return kw
        return getattr(config, attr, default) if config is not None \
            else default

    capacity = pick(span_buffer_size, "span_buffer_size", 8192)
    annotations = pick(profiler_annotations, "profiler_annotations", True)
    compile_events = pick(jax_compile_events, "jax_compile_events", True)
    ledger_on = pick(executable_ledger, "executable_ledger", False)
    hlo_coll = pick(hlo_collectives, "hlo_collectives", True)
    flight_on = pick(flight_recorder, "flight_recorder", False)
    flight_cap = pick(flight_recorder_size, "flight_recorder_size", 2048)
    deadline = pick(watchdog_deadline_s, "watchdog_deadline_s", 0.0)
    artifact_dir = pick(watchdog_artifact_dir, "watchdog_artifact_dir",
                        "telemetry_hangdump")
    abort = pick(watchdog_abort, "watchdog_abort", False)
    global _ARTIFACT_DIR
    _ARTIFACT_DIR = artifact_dir
    req_on = pick(request_traces, "request_traces", True)
    req_cap = pick(request_trace_size, "request_trace_size", 1024)
    _spans_mod.set_tracer(SpanTracer(
        capacity=capacity, profiler_annotations=annotations))
    _registry_mod.set_registry(MetricsRegistry())
    if req_on:
        # per-request serving traces (ISSUE 10): host-only ring; the
        # serving loops resolve it through the probe and guard every
        # call, so nothing is recorded until requests actually flow
        _reqtrace_mod.set_request_recorder(RequestTraceRecorder(
            capacity=req_cap, registry=_registry_mod.get_registry()))
    if ledger_on:
        _ledger_mod.set_ledger(ExecutableLedger(
            hlo_collectives=hlo_coll))
    if pick(steptrace, "steptrace", True):
        # per-step training traces (ISSUE 20): host-only ring like
        # reqtrace; the engine resolves it through the probe and guards
        # every call, so nothing is recorded until train_batch runs.
        # The ledger/timeseries hooks are zero-arg accessors — wiring
        # stays correct whether those layers are on, off, or re-wired.
        _steptrace_mod.set_step_recorder(StepTraceRecorder(
            capacity=pick(steptrace_size, "steptrace_size", 2048),
            registry=_registry_mod.get_registry(),
            ledger=_ledger_mod.get_ledger,
            timeseries=_timeseries_mod.get_timeseries,
            regression_window=pick(steptrace_regression_window,
                                   "steptrace_regression_window", 32),
            regression_threshold=pick(
                steptrace_regression_threshold,
                "steptrace_regression_threshold", 0.5)))
    if flight_on:
        rec = FlightRecorder(capacity=flight_cap)
        _flightrec_mod.set_flight_recorder(rec)
        if deadline and deadline > 0:
            dog = HangWatchdog(rec, deadline_s=deadline,
                               artifact_dir=artifact_dir, abort=abort)
            _flightrec_mod.set_watchdog(dog)
            dog.start()
    if compile_events:
        bridges.install_jax_compile_listener()
    _ACTIVE = True
    # fleet health plane (ISSUE 17): opt-in like the device-truth layer
    if pick(fleet, "fleet", False):
        configure_fleet(
            replica=pick(fleet_replica, "fleet_replica", ""),
            timeseries_capacity=pick(timeseries_capacity,
                                     "timeseries_capacity", 512),
            timeseries_interval_s=pick(timeseries_interval_s,
                                       "timeseries_interval_s", 0.25),
            burn_windows_s=pick(burn_windows_s, "burn_windows_s", None))


def configure_fleet(*, replica: str = "",
                    timeseries_capacity: int = 512,
                    timeseries_interval_s: float = 0.25,
                    burn_windows_s=None, **health_kw) -> None:
    """Install the fleet health plane (ISSUE 17): the time-series ring,
    the health monitor, and a :class:`FleetScope` with this process's
    registry registered as the local replica. Idempotent (a second
    caller — router after bench, say — keeps the existing components;
    its kwargs are ignored). Requires an active ``configure()`` —
    no-ops otherwise so disabled runs stay allocation-free.

    ``health_kw`` passes through to :class:`HealthMonitor`
    (``phi_suspect``, ``phi_dead``, ``heartbeat_window``, ...), which is
    how the router's ``RouterConfig.health`` block lands here."""
    if not _ACTIVE:
        return
    if _timeseries_mod.get_timeseries() is None:
        _timeseries_mod.set_timeseries(TimeSeriesRing(
            capacity=timeseries_capacity,
            interval_s=timeseries_interval_s))
    if burn_windows_s:
        global _BURN_WINDOWS_S
        _BURN_WINDOWS_S = tuple(float(w) for w in burn_windows_s)
    if _health_mod.get_health_monitor() is None:
        _health_mod.set_health_monitor(HealthMonitor(**health_kw))
    if _fleet_mod.get_fleet() is None:
        scope = FleetScope()
        reg = get_registry()
        if reg is not None:
            scope.add_replica(replica or f"proc{os.getpid()}", reg)
        _fleet_mod.set_fleet(scope)


def burn_windows() -> tuple:
    """The configured multi-window burn lookbacks (seconds)."""
    return _BURN_WINDOWS_S


def shutdown() -> None:
    """Deactivate and drop all telemetry state. The jax.monitoring
    listener stays registered (jax has no per-listener removal) but
    no-ops once the registry is gone."""
    global _ACTIVE, _BURN_WINDOWS_S
    _ACTIVE = False
    _fleet_mod.set_fleet(None)
    _health_mod.set_health_monitor(None)
    _timeseries_mod.set_timeseries(None)
    _BURN_WINDOWS_S = _timeseries_mod.DEFAULT_BURN_WINDOWS_S
    _flightrec_mod.set_watchdog(None)
    _flightrec_mod.set_flight_recorder(None)
    _flightrec_mod.reset_straggler_gate()
    _ledger_mod.set_ledger(None)
    _steptrace_mod.set_step_recorder(None)
    _reqtrace_mod.set_request_recorder(None)
    _spans_mod.set_tracer(None)
    _registry_mod.set_registry(None)


def clear() -> None:
    """Reset spans + metrics + device-truth state in place (e.g.
    between bench stages)."""
    t = get_tracer()
    if t is not None:
        t.clear()
    r = get_registry()
    if r is not None:
        r.clear()
    led = get_ledger()
    if led is not None:
        led.clear()
    fr = get_flight_recorder()
    if fr is not None:
        fr.clear()
    _flightrec_mod.reset_straggler_gate()
    rt = get_request_recorder()
    if rt is not None:
        rt.clear()
    st = get_step_recorder()
    if st is not None:
        st.clear()
    ts = get_timeseries()
    if ts is not None:
        ts.clear()
    hm = get_health_monitor()
    if hm is not None:
        hm.clear()


def span(name: str, **tags):
    """Module-level span helper; shared no-op context when inactive."""
    return _spans_mod.span(name, **tags)


def trace(func=None, *, name: Optional[str] = None):
    """Decorator recording a span per call; pass-through when inactive
    at call time (the check happens per call, not at decoration)."""
    import functools

    def wrap(f):
        label = name or f.__qualname__

        @functools.wraps(f)
        def inner(*a, **kw):
            with _spans_mod.span(label):
                return f(*a, **kw)
        return inner
    return wrap(func) if func is not None else wrap


def export_artifacts(out_dir: str, prefix: str = "telemetry",
                     serving_metrics: Optional[dict] = None) -> dict:
    """Write ``<prefix>.trace.json`` (Perfetto), ``<prefix>.prom``
    (Prometheus text) and ``<prefix>.metrics.json`` (snapshot) into
    ``out_dir``, refreshing the memory/comms collectors first. Returns
    the written paths (empty when telemetry is inactive)."""
    tracer, reg = get_tracer(), get_registry()
    if tracer is None or reg is None:
        return {}
    os.makedirs(out_dir, exist_ok=True)
    bridges.collect_memory(reg)
    bridges.collect_comms(reg)
    bridges.collect_ledger(reg)
    if serving_metrics is not None:
        bridges.collect_serving(reg, serving_metrics)
    rt = get_request_recorder()
    if rt is not None:
        rt.collect(reg)     # component p50/p99 gauges
    st = get_step_recorder()
    if st is not None:
        st.collect(reg)     # goodput/badput + step-component gauges
    hm = get_health_monitor()
    if hm is not None:
        hm.collect(reg)     # ds_fleet_replica_{phi,score,state} gauges
    out = {}
    # per-request async tracks (ISSUE 10) ride the same Chrome-trace
    # document as the host spans — one named tid per request — so
    # `telemetry_report --merge` composes them per rank unchanged
    doc = tracer.chrome_trace()
    pid = doc["traceEvents"][0].get("pid", 0) \
        if doc["traceEvents"] else 0
    if rt is not None:
        doc["traceEvents"].extend(
            rt.chrome_events(pid, tracer.epoch_ns))
    if st is not None:
        # per-step training tracks (ISSUE 20) share the document too,
        # so --merge composes steps + components alongside host spans
        doc["traceEvents"].extend(
            st.chrome_events(pid, tracer.epoch_ns))
    trace_path = os.path.join(out_dir, f"{prefix}.trace.json")
    import json as _json
    with open(trace_path, "w") as f:
        _json.dump(doc, f)
    out["trace"] = trace_path
    out["prometheus"] = reg.dump_prometheus(
        os.path.join(out_dir, f"{prefix}.prom"))
    out["metrics_json"] = reg.dump_json(
        os.path.join(out_dir, f"{prefix}.metrics.json"))
    if rt is not None:
        # structured access log: one JSONL line per completed request
        log_path = rt.write_access_log(
            os.path.join(out_dir, f"{prefix}.access.jsonl"))
        if log_path:
            out["access_log"] = log_path
    if st is not None:
        # step log: one STEP_LOG_KEYS JSONL line per training step;
        # telemetry_report --diff accepts it as a numeric source
        log_path = st.write_step_log(
            os.path.join(out_dir, f"{prefix}.steps.jsonl"))
        if log_path:
            out["step_log"] = log_path
    led = get_ledger()
    if led is not None:
        import json as _json
        path = os.path.join(out_dir, f"{prefix}.ledger.json")
        with open(path, "w") as f:
            _json.dump(led.snapshot(), f, indent=1, default=str)
        out["ledger"] = path
        scope_maps = led.op_scopes_by_name()
        if scope_maps:
            # the join from a device trace's events (named by HLO
            # instruction) to the program's ds. scopes (telemetry/scopes.py)
            path = os.path.join(out_dir, f"{prefix}.op_scopes.json")
            with open(path, "w") as f:
                _json.dump(scope_maps, f)
            out["op_scopes"] = path
        work_maps = led.op_work_by_name()
        if work_maps:
            # beside each instruction's scope, what kind of work it is
            # and the bytes at its boundary (scopes.op_work)
            path = os.path.join(out_dir, f"{prefix}.op_work.json")
            with open(path, "w") as f:
                _json.dump(work_maps, f)
            out["op_work"] = path
    scope = get_fleet()
    if scope is not None:
        # versioned fleet rollup (ISSUE 17); embeds the health snapshot
        # so telemetry_report --fleet renders from this file alone
        out["fleet"] = scope.write(
            os.path.join(out_dir, f"{prefix}.fleet.json"),
            health=hm.snapshot() if hm is not None else None)
    return out


def dump_flight_record(reason: str,
                       out_dir: Optional[str] = None) -> str:
    """Write a hang-dump artifact NOW (flight-recorder events, open
    spans, ledger, memory, thread stacks) — the entry external
    watchdogs (bench's ``--total-budget-s``) route through. Returns
    the artifact path, or '' when the flight recorder is off."""
    dog = get_watchdog()
    if dog is not None:
        return dog.fire(reason)
    rec = get_flight_recorder()
    if rec is None:
        return ""
    return _flightrec_mod.dump_state(
        reason, out_dir or _ARTIFACT_DIR, recorder=rec,
        tracer=get_tracer(), ledger=get_ledger(),
        registry=get_registry(), reqtrace=get_request_recorder(),
        steptrace=get_step_recorder())

"""Bridges from existing signal sources into the telemetry registry
(ISSUE 2 tentpole part 2b).

Each collector reads one legacy/framework surface and mirrors it into
Prometheus-style metrics:

- ``install_jax_compile_listener`` — ``jax.monitoring`` duration events
  (jit trace / lowering / backend compile / persistent-cache load) ->
  compile count + seconds by phase.
- ``collect_memory`` — /proc/self/status VmRSS+VmHWM and PJRT device
  ``memory_stats()`` -> host/device memory gauges.
- ``collect_comms`` — ``CommsLogger`` per-op call/byte tallies ->
  ``ds_comm_*_total`` counters.
- ``collect_serving`` — ``InferenceEngineV2.serving_metrics()`` ->
  serving counters + efficiency gauges.
- ``collect_throughput`` — ``ThroughputTimer`` -> samples/s + TFLOPS.
- ``flush_to_monitor`` — registry snapshot -> ``MonitorMaster`` events,
  so CSV/TensorBoard/W&B see everything the registry holds.

All collectors are cheap, idempotent, and safe to call at flush
boundaries only — never per token.
"""

from __future__ import annotations

from typing import Optional

from . import ledger as _ledger_mod, registry as _registry_mod
from .registry import MetricsRegistry

_JAX_LISTENER_INSTALLED = False

# plain process-wide compile-event tallies, independent of the registry
# lifecycle: the analysis/sentinels.py recompile sentinel reads these, so
# it works with telemetry configured OR shut down (the registry mirror
# below additionally feeds ds_jax_compile_total when active)
_COMPILE_EVENTS: dict[str, int] = {}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def compile_event_count(phase: str = "backend_compile") -> int:
    """Monotonic count of jax compile-path events seen by this process's
    listener. ``backend_compile`` fires exactly once per executable
    built (trace/lowering phases can fire more) — the signal the
    recompile sentinel watches. Returns 0 until the listener is
    installed."""
    return _COMPILE_EVENTS.get(phase, 0)


def install_jax_compile_listener() -> None:
    """Capture jit compile count/time via ``jax.monitoring``. Installed
    once per process; the registry half reads the live registry on each
    event, so it no-ops after ``telemetry.shutdown()`` (jax offers no
    per-listener removal) while the plain tallies keep counting for the
    sentinels."""
    global _JAX_LISTENER_INSTALLED
    if _JAX_LISTENER_INSTALLED:
        return
    import jax

    def _on_duration(name: str, dur_s: float, **kw) -> None:
        if name == _CACHE_LOAD_EVENT:
            # reading an executable back from the persistent cache: part
            # of (inside) that executable's backend_compile event, so a
            # warm set-up shows as cache loads and a cold one as compiles
            phase = "cache_load"
        elif "/compile/" in name:
            phase = name.rsplit("/", 1)[-1]
            if phase.endswith("_duration"):
                phase = phase[: -len("_duration")]
        else:
            return
        _COMPILE_EVENTS[phase] = _COMPILE_EVENTS.get(phase, 0) + 1
        # the executable ledger tracks process-wide compile time per
        # phase (every newly compiled executable announces itself
        # here, whether or not a call site ever observe()s it)
        led = _ledger_mod.get_ledger()
        if led is not None:
            led.on_compile_event(phase, dur_s)
        reg = _registry_mod.get_registry()
        if reg is None:
            return
        reg.counter("ds_jax_compile_total",
                    "jax compile-path events by phase").inc(phase=phase)
        reg.counter("ds_compile_seconds_total",
                    "cumulative seconds in jax compile phases "
                    "(jaxpr_trace, jaxpr_to_mlir_module, backend_compile, "
                    "and cache_load, which lies inside backend_compile)"
                    ).inc(dur_s, phase=phase)

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _JAX_LISTENER_INSTALLED = True


def collect_memory(reg: MetricsRegistry) -> None:
    """Host VmRSS/VmHWM + device memory stats as gauges."""
    host = reg.gauge("ds_host_memory_bytes",
                     "host process memory from /proc/self/status")
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    host.set(int(line.split()[1]) * 1024, kind="rss")
                elif line.startswith("VmHWM:"):
                    host.set(int(line.split()[1]) * 1024, kind="hwm")
    except OSError:
        pass  # no procfs (VmHWM is also absent on some sandboxed kernels)
    from ..utils.memory import device_memory_stats
    stats = device_memory_stats()
    if stats:
        dev = reg.gauge("ds_device_memory_bytes",
                        "PJRT device memory stats (device 0)")
        for key, kind in (("bytes_in_use", "in_use"),
                          ("peak_bytes_in_use", "peak"),
                          ("bytes_limit", "limit")):
            if key in stats:
                dev.set(float(stats[key]), kind=kind)


def collect_comms(reg: MetricsRegistry, comms_logger=None) -> None:
    """CommsLogger per-op tallies -> counters (absolute mirror)."""
    if comms_logger is None:
        from .. import comm as dist
        comms_logger = dist.get_comms_logger()
    if comms_logger is None:
        return
    calls = reg.counter("ds_comm_calls_total",
                        "collective calls recorded at trace time")
    byts = reg.counter("ds_comm_bytes_total",
                       "collective payload bytes recorded at trace time")
    for op, sizes in comms_logger.comms_dict.items():
        n = sum(sizes.values())
        b = sum(cnt * sz for sz, cnt in sizes.items())
        calls.set_total(n, op=op)
        byts.set_total(b, op=op)


# serving counters mirrored 1:1 from InferenceEngineV2.serving_stats,
# plus the prefix-cache counters (schema shared with ragged.py's
# PREFIX_STAT_KEYS so the key set cannot drift from what
# serving_metrics() emits). Resolved lazily: importing the inference
# package here would pull jax + the model zoo into every telemetry
# process, serving or not.
_SERVING_COUNTERS_BASE = ("decoded_tokens", "host_dispatches",
                          "fused_dispatches", "fused_steps",
                          "spec_proposed_tokens",
                          "spec_accepted_tokens", "spec_hit_slots")
_SERVING_GAUGES = ("dispatches_per_token", "fused_occupancy",
                   "max_inflight_dispatches",
                   "tokens_per_dispatch", "spec_acceptance_rate",
                   "prefix_hit_rate", "prefix_cached_blocks",
                   "prefix_evictable_blocks")


def _serving_counter_keys() -> tuple:
    import sys
    ragged = sys.modules.get("deepspeed_tpu.inference.v2.ragged")
    if ragged is None:
        # no engine loaded -> nothing beyond the base counters can be
        # present in the metrics dict anyway
        return _SERVING_COUNTERS_BASE
    return _SERVING_COUNTERS_BASE + ragged.PREFIX_STAT_KEYS


def collect_serving(reg: MetricsRegistry, serving_metrics: dict,
                    engine_label: str = "v2") -> None:
    """``InferenceEngineV2.serving_metrics()`` -> registry."""
    for key in _serving_counter_keys():
        if key in serving_metrics:
            reg.counter(f"ds_serving_{key}_total",
                        f"serving counter {key}").set_total(
                serving_metrics[key], engine=engine_label)
    for key in _SERVING_GAUGES:
        if key in serving_metrics:
            reg.gauge(f"ds_serving_{key}",
                      f"decode-loop efficiency ratio {key}").set(
                serving_metrics[key], engine=engine_label)
    # quantized KV cache (ISSUE 12): pool footprint gauges carry the
    # storage format as a label so fp16/int8/fp8 pools chart as
    # distinct series at one glance
    if "kv_pool_bytes" in serving_metrics:
        kv_dtype = str(serving_metrics.get("kv_dtype", "unknown"))
        reg.gauge("ds_kv_pool_bytes",
                  "HBM bytes of the paged KV pools (payload + scale "
                  "slabs)").set(serving_metrics["kv_pool_bytes"],
                                dtype=kv_dtype, engine=engine_label)
        reg.gauge("ds_kv_bytes_per_token",
                  "KV bytes one cached token costs across all layers "
                  "(k+v, scales included)").set(
            serving_metrics.get("kv_bytes_per_token", 0.0),
            dtype=kv_dtype, engine=engine_label)
        reg.gauge("ds_kv_num_blocks",
                  "blocks in the paged KV pool (grown past "
                  "num_kv_blocks when the quantized pool fills the "
                  "full-precision HBM budget)").set(
            serving_metrics.get("kv_num_blocks", 0),
            dtype=kv_dtype, engine=engine_label)


def collect_ledger(reg: MetricsRegistry) -> None:
    """Executable-ledger state -> registry (ISSUE 5): FLOPs dispatched
    per jit name, peak HBM per executable name,
    HBM headroom against the device limit, and the per-(mesh axis, op)
    HLO collective traffic counters. No-op (zero allocations) when the
    ledger is off."""
    led = _ledger_mod.get_ledger()
    if led is None:
        return
    reg.gauge("ds_ledger_executables",
              "compiled executables registered in the cost ledger"
              ).set(len(led))
    flops_total = reg.counter(
        "ds_ledger_dispatched_flops_total",
        "FLOPs dispatched per jit name (executable FLOPs x calls)")
    for name, flops in led.dispatched_flops().items():
        flops_total.set_total(flops, name=name)
    hbm = reg.gauge("ds_ledger_peak_hbm_bytes",
                    "compiler-reported peak HBM per executable name "
                    "(max over live shape signatures)")
    max_peak = 0
    for name, peak_bytes in led.peak_hbm_by_name().items():
        hbm.set(peak_bytes, name=name)
        max_peak = max(max_peak, peak_bytes)
    from ..utils.memory import device_memory_stats
    limit = float(device_memory_stats().get("bytes_limit", 0) or 0)
    if limit > 0 and max_peak > 0:
        reg.gauge("ds_hbm_headroom_bytes",
                  "device memory limit minus the largest registered "
                  "executable's peak HBM").set(limit - max_peak)
    traffic = led.traffic()
    if traffic:
        byts = reg.counter(
            "ds_hlo_collective_bytes_total",
            "collective payload bytes from HLO accounting, dispatch-"
            "weighted, attributed to mesh axes")
        sites = reg.counter(
            "ds_hlo_collective_sites_total",
            "collective instruction sites in registered executables")
        for (axis, op), row in traffic.items():
            byts.set_total(row["bytes"], axis=axis, op=op)
            sites.set_total(row["sites"], axis=axis, op=op)
        wire = reg.gauge(
            "ds_hlo_wire_bytes_per_el",
            "observed collective wire width per mesh axis "
            "(bytes/element; ~1.1 when ZeRO++ qwZ/qgZ int8 payloads "
            "+ fp32 block scales carry the traffic, 4.0 at fp32)")
        from .collectives import axis_wire_width
        for axis, width in axis_wire_width(traffic).items():
            wire.set(round(width, 4), axis=axis)


def collect_throughput(reg: MetricsRegistry, tput_timer) -> None:
    """``ThroughputTimer`` -> samples/s (+ TFLOPS when configured)."""
    sps = tput_timer.avg_samples_per_sec()
    reg.gauge("ds_train_samples_per_second",
              "training throughput (ThroughputTimer)").set(sps)
    if getattr(tput_timer, "flops_per_sample", None):
        reg.gauge("ds_train_tflops",
                  "estimated training TFLOPS").set(tput_timer.tflops())


def record_train_step(reg: MetricsRegistry, engine, metrics) -> None:
    """Engine step-boundary metrics (called at steps_per_print
    boundaries, where the device sync is already paid)."""
    reg.counter("ds_train_steps_total",
                "engine steps taken").set_total(engine.global_steps)
    reg.counter("ds_train_samples_total",
                "samples consumed").set_total(engine.global_samples)
    reg.counter("ds_train_skipped_steps_total",
                "overflow-skipped optimizer steps").set_total(
        engine.skipped_steps)
    # device-truth overflow count (ISSUE 18): global_steps minus the
    # on-device applied-step counter — covers the compiled path, which
    # never tallies skipped_steps on the host
    ov = getattr(engine, "overflow_steps", None)
    if ov is not None:
        reg.counter("ds_overflow_steps_total",
                    "fp16 overflow steps (optimizer update skipped, "
                    "loss scale backed off) — derived from the "
                    "on-device applied-step counter").set_total(int(ov))
    if metrics:
        if "loss" in metrics:
            reg.gauge("ds_train_loss", "last reported loss").set(
                float(metrics["loss"]))
        if "grad_norm" in metrics:
            reg.gauge("ds_train_grad_norm",
                      "last reported global gradient norm").set(
                float(metrics["grad_norm"]))
        if "loss_scale" in metrics:
            reg.gauge("ds_train_loss_scale", "live fp16 loss scale").set(
                float(metrics["loss_scale"]))
    tput = getattr(engine, "tput_timer", None)
    if tput is not None:
        collect_throughput(reg, tput)
    collect_memory(reg)
    collect_comms(reg)
    collect_ledger(reg)


def flush_to_monitor(monitor, step: int,
                     reg: Optional[MetricsRegistry] = None,
                     prefix: str = "Telemetry") -> int:
    """Write the registry's scalar view through MonitorMaster so the
    CSV/TensorBoard/W&B backends chart it. Returns event count."""
    reg = reg if reg is not None else _registry_mod.get_registry()
    if reg is None or monitor is None or not getattr(monitor, "enabled",
                                                     False):
        return 0
    events = reg.events_for_monitor(step, prefix=prefix)
    if events:
        monitor.write_events(events)
    return len(events)

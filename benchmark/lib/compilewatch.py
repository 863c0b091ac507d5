"""Count executables built or loaded, through ``jax.monitoring`` - the
harness's own listener, so untraced runs keep the program's telemetry
off. ``backend_compile`` fires once per NEW executable, whether XLA
compiled it or the persistent cache supplied it; ``cache_hits`` counts
the latter. A new executable inside the measured window is a stall the
warm-up should have taken, so both are reported."""

from __future__ import annotations

import time

_COUNTS = {"executables": 0, "cache_hits": 0, "compile_s": 0.0}
_LOG: list[tuple[float, float]] = []    # (when, seconds) of each build
_installed = False


def install() -> None:
    global _installed
    if _installed:
        return
    import jax.monitoring as mon

    def on_duration(name: str, dur_s: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            _COUNTS["executables"] += 1
            _COUNTS["compile_s"] += dur_s
            _LOG.append((time.perf_counter(), dur_s))

    def on_event(name: str, **kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            _COUNTS["cache_hits"] += 1

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)
    _installed = True


def snapshot() -> dict:
    return dict(_COUNTS)


def executables() -> int:
    return _COUNTS["executables"]


def slow_builds(min_s: float = 1.0) -> list[float]:
    return [round(d, 1) for _, d in _LOG if d >= min_s]

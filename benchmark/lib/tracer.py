"""Start and stop ``jax.profiler`` around a few seconds of the steady
window, into a fixed directory inside the checkout (git-ignored)."""

from __future__ import annotations

import shutil
import threading
import time

from .files import CHECKOUT

TRACE_ROOT = CHECKOUT / ".bench_trace"


class Tracer:
    def __init__(self, workload: str, max_seconds: float):
        self.dir = TRACE_ROOT / workload
        self.max_seconds = float(max_seconds)
        self.started_at = None
        self.stopped = False
        self._lock = threading.Lock()
        self._timer = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the program's spans, not frames
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.started_at = time.perf_counter()
        # stop from a timer thread so neither the event loop nor the
        # training loop waits for the trace to be written
        self._timer = threading.Timer(self.max_seconds, self.stop)
        self._timer.daemon = True
        self._timer.start()

    def stop(self) -> None:
        with self._lock:
            if self.stopped or self.started_at is None:
                return
            self.stopped = True
            import jax
            jax.profiler.stop_trace()
        if self._timer is not None:
            self._timer.cancel()

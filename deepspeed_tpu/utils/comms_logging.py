"""Comms logging (reference: deepspeed/utils/comms_logging.py CommsLogger).

Records per-op call counts and message sizes at trace time. Because XLA
compiles collectives into the step graph, eager per-call latency is not
measurable; algbw/busbw columns are therefore filled from profiler-measured
step time when available, else left as totals. ``get_bw`` keeps the
reference's bus-bandwidth formulas (comms_logging.py:32).
"""

from __future__ import annotations

import time
from collections import defaultdict

from .logging import log_dist, logger
from .telemetry_probe import active_telemetry


def _telemetry_window_s(started_unix: float) -> float:
    """Measured wall-time window (seconds) from the telemetry span
    tracer's top-level spans, when telemetry is active; 0.0 otherwise.

    The window is only trusted when the tracer started recording no
    later than this logger did (``started_unix``): a tracer configured
    — or ``clear()``ed — after collectives were already tallied would
    pair a short window with a long run's bytes and OVERSTATE
    bandwidth, breaking the lower-bound claim. In that case the caller
    gets 0.0 and the bandwidth columns render ``-`` (call
    ``CommsLogger.reset()`` alongside ``telemetry.clear()`` to re-pair
    them)."""
    mod = active_telemetry()
    if mod is None:
        return 0.0
    tracer = mod.get_tracer()
    if tracer is None or tracer.epoch_unix > started_unix + 1.0:
        return 0.0
    return tracer.window_seconds()


def _human_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024.0:
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0
    return f"{n:.1f} PB"


def get_bw(comm_op: str, size_bytes: int, duration_s: float, n: int) -> tuple[float, float]:
    """(algbw, busbw) in GB/s; formulas follow the reference comms_logging.get_bw."""
    if duration_s <= 0:
        return 0.0, 0.0
    tput = size_bytes / duration_s
    if comm_op in ("all_to_all_single", "all_to_all"):
        busbw = tput * ((n - 1) / n)
    elif comm_op in ("all_gather", "all_gather_into_tensor", "reduce_scatter",
                     "reduce_scatter_tensor"):
        busbw = tput * ((n - 1) / n)
    elif comm_op in ("all_reduce",):
        busbw = tput * (2 * (n - 1) / n)
    else:  # send/recv/broadcast/ppermute
        busbw = tput
    return tput / 1e9, busbw / 1e9


class CommsLogger:
    def __init__(self, config=None):
        self.enabled = getattr(config, "enabled", True)
        self.verbose = getattr(config, "verbose", False)
        self.prof_all = getattr(config, "prof_all", True)
        self.prof_ops = list(getattr(config, "prof_ops", []) or [])
        # op_name -> msg_size -> call count (total bytes = count * msg_size)
        self.comms_dict: dict[str, dict[int, int]] = defaultdict(
            lambda: defaultdict(int))
        # when this tally window opened (paired against the telemetry
        # tracer's epoch in log_summary's bandwidth accounting)
        self.started_unix = time.time()

    def reset(self) -> None:
        """Drop all tallies and reopen the window (pair with
        ``telemetry.clear()`` so bytes and measured duration keep
        covering the same interval)."""
        self.comms_dict.clear()
        self.started_unix = time.time()

    def append(self, op_name: str, msg_size: int, group=None) -> None:
        if not self.enabled:
            return
        if not self.prof_all and op_name not in self.prof_ops:
            return
        self.comms_dict[op_name][msg_size] += 1
        if self.verbose:
            logger.info(
                f"comm op: {op_name} | msg size: {msg_size} B | group: {group}")

    def log_all(self, print_log: bool = True):
        lines = [f"{'Comm. Op':<25}{'Message Size':>15}{'Count':>10}{'Total (MB)':>14}"]
        for op_name, sizes in sorted(self.comms_dict.items()):
            for msg_size, count in sorted(sizes.items()):
                lines.append(
                    f"{op_name:<25}{msg_size:>15}{count:>10}"
                    f"{count * msg_size / 1e6:>14.2f}")
        text = "\n".join(lines)
        if print_log:
            log_dist("\n" + text)
        return text

    def log_summary(self, duration_s: float | None = None,
                    world_size: int | None = None,
                    print_log: bool = True) -> str:
        """Reference-format per-op summary table (comms_logging.py
        log_summary) with the latency/bandwidth columns filled from a
        MEASURED duration instead of per-call timing (which XLA's fused
        collectives make unobservable eagerly).

        ``duration_s`` defaults to the telemetry span tracer's top-level
        window (sum of train_batch / dispatch span durations). Every op
        ran somewhere inside that window, so ``bytes / window`` is an
        honest LOWER BOUND on each op's achieved algorithm bandwidth —
        collectives overlap compute inside the window, so true bandwidth
        is at least this. The bound only holds when the window and the
        tallies cover the same interval, so a tracer that started (or
        was cleared) AFTER this logger began recording is rejected; the
        bandwidth columns then print ``-``, as they do with telemetry
        off and no explicit duration.

        Zero-call ops / zero sizes / zero duration never divide by zero;
        such rows render ``-`` in the derived columns.
        """
        if duration_s is None:
            duration_s = _telemetry_window_s(self.started_unix)
        if world_size is None:
            import jax
            world_size = max(jax.device_count(), 1)
        header = (f"{'Comm. Op':<28}{'Message Size':>14}{'Count':>8}"
                  f"{'Total Bytes':>14}{'Window(ms)':>12}"
                  f"{'algbw(GB/s)':>13}{'busbw(GB/s)':>13}")
        lines = [header]
        for op_name, sizes in sorted(self.comms_dict.items()):
            n_calls = sum(sizes.values())
            total_bytes = sum(cnt * sz for sz, cnt in sizes.items())
            if n_calls == 0:
                # defensive: an op key with no recorded calls renders a
                # placeholder row instead of dividing by zero
                lines.append(f"{op_name:<28}{'-':>14}{0:>8}{'-':>14}"
                             f"{'-':>12}{'-':>13}{'-':>13}")
                continue
            if duration_s > 0 and total_bytes > 0:
                algbw, busbw = get_bw(op_name, total_bytes, duration_s,
                                      world_size)
                win = f"{duration_s * 1e3:.2f}"
                alg, bus = f"{algbw:.3f}", f"{busbw:.3f}"
            else:
                win = alg = bus = "-"
            for msg_size, count in sorted(sizes.items()):
                lines.append(
                    f"{op_name:<28}{_human_bytes(msg_size):>14}"
                    f"{count:>8}{_human_bytes(count * msg_size):>14}"
                    f"{'':>12}{'':>13}{'':>13}")
            lines.append(
                f"{op_name + ' (total)':<28}{'':>14}{n_calls:>8}"
                f"{_human_bytes(total_bytes):>14}{win:>12}"
                f"{alg:>13}{bus:>13}")
        if len(lines) == 1:
            lines.append("(no collectives recorded)")
        lines += self._hlo_traffic_lines(duration_s)
        text = "\n".join(lines)
        if print_log:
            log_dist("\n" + text)
        return text

    def _hlo_traffic_lines(self, duration_s: float) -> list[str]:
        """Device-truth section (ISSUE 5): the executable ledger's HLO
        collective traffic matrix, attributed to mesh axes and
        dispatch-weighted. Unlike the trace-time tallies above, these
        are the collectives XLA actually EMITTED after fusion —
        including ones the comm facade never saw (sharding-induced
        resharding, grad psums inside shard_map). Bandwidth columns
        are the same window-based lower bounds. Empty when the ledger
        is off."""
        mod = active_telemetry()
        led = mod.get_ledger() if mod is not None else None
        if led is None:
            return []
        traffic = led.traffic()
        if not traffic:
            return []
        out = ["", "HLO collective accounting (compiled-executable "
                   "ground truth, per mesh axis):",
               f"{'Axis':<14}{'Op':<16}{'Sites':>7}{'Total Bytes':>14}"
               f"{'Window(ms)':>12}{'algbw(GB/s)':>13}{'busbw(GB/s)':>13}"]
        for (axis, op), row in sorted(traffic.items()):
            if duration_s > 0 and row["bytes"] > 0:
                algbw, busbw = get_bw(op, row["bytes"], duration_s,
                                      max(row["group_size"], 2))
                win, alg, bus = (f"{duration_s * 1e3:.2f}",
                                 f"{algbw:.3f}", f"{busbw:.3f}")
            else:
                win = alg = bus = "-"
            out.append(
                f"{axis:<14}{op:<16}{row['sites']:>7}"
                f"{_human_bytes(row['bytes']):>14}{win:>12}"
                f"{alg:>13}{bus:>13}")
        return out

"""From a profiler trace (``.xplane.pb``) to numbers. The only code that
turns a trace into metrics; it reads the file with
``jax.profiler.ProfileData`` and nothing else.

What a TPU trace looks like (looked at by hand, PR 23, v5e, jax 0.9.0):
one plane per chip named ``/device:TPU:<n>``. Its line ``XLA Modules``
has one event per executable run, named ``jit_<fn>(<fingerprint>)``
(the train step is ``jit_train_step(...)``, whatever span the program
wraps round the call). Its line ``XLA Ops`` has one event per HLO op the
TensorCore ran; an event's NAME IS THE WHOLE HLO INSTRUCTION TEXT
(``%closed_call.35 = (...) custom-call(...),
custom_call_target="tpu_custom_call", ...``), so patterns are searched in
that text: ``^%all-gather`` finds an op by its name, and
``custom_call_target="tpu_custom_call"`` finds every Mosaic kernel
without a stable name. Ops nested in a ``while`` appear inside their
parent's interval, so intervals are merged, never summed, where "busy" is
meant, and "leaf" ops are those that contain no other. The line
``Async XLA Ops`` has one event per asynchronous copy or collective,
from its ``-start`` to its ``-done``. Host threads are lines of the plane
``/host:CPU``; the program's spans (``TraceAnnotation``) are on the line
``python`` (main thread) or the worker thread's line. The device's clock
runs about 1 ms ahead of the host's in these traces (a module starts
"before" the host call that launched it), so gaps under a few ms cannot
be attributed to a host span with certainty.

Times are in seconds from the earliest event of the trace.

    python benchmark/lib/trace.py <file.xplane.pb>      # dump what is there
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def short_name(text: str) -> str:
    """``%closed_call.35 = ... custom_call_target="tpu_custom_call"`` ->
    ``closed_call.35 [tpu_custom_call]``; other names unchanged."""
    m = re.match(r"^%?([^\s=]+)", text)
    name = m.group(1) if m else text
    t = re.search(r'custom_call_target="([^"]+)"', text)
    return f"{name} [{t.group(1)}]" if t else name


class Trace:
    """devices: {chip index: {line name: [(name, start_s, end_s)]}};
    host: {thread line name: [(name, start_s, end_s)]}."""

    def __init__(self, devices: dict, host: dict):
        self.devices = devices
        self.host = host

    # -- loading ---------------------------------------------------------
    @classmethod
    def from_file(cls, path: str, device_plane=DEVICE_PLANE) -> "Trace":
        import jax
        pd = jax.profiler.ProfileData.from_file(path)
        devices, host = {}, {}
        raw = []
        for plane in pd.planes:
            m = device_plane.match(plane.name)
            if m is None and plane.name != HOST_PLANE:
                continue
            for line in plane.lines:
                evs = [(e.name, float(e.start_ns),
                        float(e.start_ns) + float(e.duration_ns))
                       for e in line.events]
                if evs:
                    raw.append((m, line.name, evs))
        if not raw:
            return cls({}, {})
        t0 = min(e[1] for _, _, evs in raw for e in evs)
        for m, line_name, evs in raw:
            evs = sorted(((n, (a - t0) * 1e-9, (b - t0) * 1e-9)
                          for n, a, b in evs), key=lambda e: e[1])
            if m is None:
                host.setdefault(line_name, []).extend(evs)
            else:
                devices.setdefault(int(m.group(1)), {}).setdefault(
                    line_name, []).extend(evs)
        return cls(devices, host)

    @classmethod
    def newest_under(cls, trace_dir: str) -> "Trace":
        files = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        return cls.from_file(files[-1])

    # -- selections ------------------------------------------------------
    def ops(self, chip: int, pattern: str | None = None,
            exclude: str | None = None, lines=(OPS_LINE,),
            leaves: bool = False) -> list:
        evs = [e for ln in lines
               for e in self.devices.get(chip, {}).get(ln, [])]
        if leaves:
            evs = leaf_events(evs)
        if pattern is not None:
            rx = re.compile(pattern)
            evs = [e for e in evs if rx.search(e[0])]
        if exclude is not None:
            rx = re.compile(exclude)
            evs = [e for e in evs if not rx.search(e[0])]
        return evs

    def modules(self, chip: int, pattern: str | None = None) -> list:
        evs = self.devices.get(chip, {}).get(MODULES_LINE, [])
        if pattern is not None:
            rx = re.compile(pattern)
            evs = [e for e in evs if rx.search(e[0])]
        return evs

    def chips(self) -> list[int]:
        return sorted(self.devices)

    def host_spans(self, pattern: str) -> list:
        rx = re.compile(pattern)
        out = [e for evs in self.host.values() for e in evs
               if rx.search(e[0])]
        return sorted(out, key=lambda e: e[1])


def leaf_events(events) -> list:
    """Events that contain no other event of the list (the bodies of
    ``while``/``call`` ops, not the ops that hold them)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []     # stack of [event, has_child]
    for e in evs:
        while stack and stack[-1][0][2] <= e[1]:
            top, has_child = stack.pop()
            if not has_child:
                out.append(top)
        if stack:
            stack[-1][1] = True
        stack.append([e, False])
    out.extend(top for top, has_child in stack if not has_child)
    return sorted(out, key=lambda e: e[1])


# -- interval arithmetic -------------------------------------------------
def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals as a sorted disjoint list."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a_iv, b_iv) -> list[tuple[float, float]]:
    """Parts of the disjoint sorted list a_iv not covered by b_iv."""
    out = []
    b_iv = list(b_iv)
    j = 0
    for a0, a1 in a_iv:
        cur = a0
        while j < len(b_iv) and b_iv[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_iv) and b_iv[k][0] < a1:
            if b_iv[k][0] > cur:
                out.append((cur, b_iv[k][0]))
            cur = max(cur, b_iv[k][1])
            k += 1
        if cur < a1:
            out.append((cur, a1))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _iv(events):
    return [(a, b) for _, a, b in events]


# -- reductions ----------------------------------------------------------
def window(trace: Trace) -> tuple[float, float]:
    """The traced window: from the first to the last device-op event over
    all chips (the profiler runs a little longer on both sides; those
    margins hold no device work and belong to no step)."""
    starts = [e[1] for c in trace.chips() for e in trace.ops(c)]
    ends = [e[2] for c in trace.chips() for e in trace.ops(c)]
    if not starts:
        return (0.0, 0.0)
    return (min(starts), max(ends))


def busy_seconds(trace: Trace, lo=None, hi=None) -> float:
    """Seconds in which an op ran on the device: union of the op
    intervals, averaged over the chips in the trace."""
    chips = trace.chips()
    if not chips:
        return 0.0
    if lo is None:
        lo, hi = window(trace)
    return sum(total(clip(merge(_iv(trace.ops(c))), lo, hi))
               for c in chips) / len(chips)


def complete_steps(trace: Trace, module_pattern: str, chip: int) -> list:
    """(start, end) of each run of the step executable on ``chip``; the
    last one is dropped, because the trace may have stopped inside it."""
    return [(a, b) for _, a, b in trace.modules(chip, module_pattern)][:-1]


def step_count(trace: Trace, module_pattern: str) -> int:
    chips = trace.chips()
    return len(complete_steps(trace, module_pattern, chips[0])) if chips \
        else 0


def per_step_seconds(trace: Trace, module_pattern: str, intervals_of) -> list:
    """For every complete step and chip: the seconds of
    ``intervals_of(chip)`` (a merged interval list) inside the step.
    Returns one number per step, averaged over chips."""
    chips = trace.chips()
    if not chips:
        return []
    per_chip = []
    for c in chips:
        iv = intervals_of(c)
        per_chip.append([total(clip(iv, a, b))
                         for a, b in complete_steps(trace, module_pattern, c)])
    n = min(len(x) for x in per_chip)
    return [sum(x[i] for x in per_chip) / len(chips) for i in range(n)]


def step_gaps(trace: Trace, module_pattern: str) -> list[float]:
    """Device idle between one run of the step executable and the next:
    from the last op inside run i to the first op inside run i+1."""
    chips = trace.chips()
    if not chips:
        return []
    c = chips[0]
    mods = trace.modules(c, module_pattern)
    ops = merge(_iv(trace.ops(c)))
    spans = []
    for _, a, b in mods:
        inside = clip(ops, a, b)
        if inside:
            spans.append((inside[0][0], inside[-1][1]))
    return [spans[i + 1][0] - spans[i][1] for i in range(len(spans) - 1)]


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The device ops that took most time on chip 0: [[name, seconds]].
    Parents of nested ops (``while``, ``conditional``, ``call``) are left
    out so a loop is not counted once for itself and again for its body."""
    chips = trace.chips()
    if not chips:
        return []
    acc: dict[str, float] = defaultdict(float)
    for text, a, b in trace.ops(chips[0], leaves=True):
        acc[short_name(text)] += b - a
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in top]


def label_timeline(spans) -> list[tuple[float, float, str]]:
    """Disjoint (start, end, name) pieces in which ``name`` is the
    innermost (shortest) span covering the piece."""
    pieces: list[tuple[float, float, str]] = []
    for name, a, b in sorted(spans, key=lambda s: -(s[2] - s[1])):
        if b <= a:
            continue
        kept = []
        for pa, pb, pn in pieces:
            if pb <= a or pa >= b:
                kept.append((pa, pb, pn))
                continue
            if pa < a:
                kept.append((pa, a, pn))
            if pb > b:
                kept.append((b, pb, pn))
        kept.append((a, b, name))
        pieces = kept
    return sorted(pieces)


def idle_gaps_by_span(trace: Trace, span_pattern: str,
                      n: int = 10) -> list[list]:
    """Idle seconds of chip 0 inside the window, attributed to the
    program span (host ``TraceAnnotation``) that covered them - the
    innermost one; "no span" where none did. Returns [[span name,
    seconds]] by decreasing seconds."""
    chips = trace.chips()
    if not chips:
        return []
    lo, hi = window(trace)
    busy = clip(merge(_iv(trace.ops(chips[0]))), lo, hi)
    gaps = subtract([(lo, hi)], busy)
    acc: dict[str, float] = defaultdict(float)
    pieces = label_timeline(
        [s for s in trace.host_spans(span_pattern) if s[2] > lo and s[1] < hi])
    starts = [p[0] for p in pieces]
    import bisect
    for a, b in gaps:
        covered = 0.0
        k = max(0, bisect.bisect_right(starts, a) - 1)
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, pn = pieces[k]
            ov = min(b, pb) - max(a, pa)
            if ov > 0:
                acc[pn] += ov
                covered += ov
            k += 1
        if b - a - covered > 0:
            acc["no span"] += b - a - covered
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in top]


def dump(path: str) -> None:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            by: dict[str, list] = defaultdict(lambda: [0, 0.0])
            for e in evs:
                k = re.sub(r"[.\d]+$", "", e.name)
                by[k][0] += 1
                by[k][1] += e.duration_ns * 1e-9
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for k, (cnt, sec) in sorted(by.items(),
                                        key=lambda kv: -kv[1][1])[:25]:
                print(f"      {sec:10.6f}s x{cnt:<6d} {k[:100]}")


if __name__ == "__main__":
    import sys
    dump(sys.argv[1])

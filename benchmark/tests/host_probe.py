"""By hand: the host's side of every step of an UNTRACED run, on the host's
own clock, and a detector of the machine's freezes to run beside it. The
benchmark's own runs never run it. It placed the one-chip cells' two
noises (``PERF.md`` section 6, PR 56): the whole machine stops for about
110 ms every 15 s or so and now and then for 1 to 3.6 s (a process that
touches neither JAX nor the chip sees the same stops at the same instants),
and the runtime's transfer and dispatch inside ``train_batch`` go from 2.2
to 6 ms at some step of a process and stay there (no fall back to Python).

    chiprun -- bash -c '
      python3 benchmark/tests/host_probe.py beside spin  chiprun_out/spin.json  600 &
      python3 benchmark/tests/host_probe.py beside sleep chiprun_out/sleep.json 600 &
      python3 benchmark/tests/host_probe.py run chiprun_out/probe.json \
          --workload <cell> --seed <n> --seconds 45 --trace 0; kill %1 %2'

``run`` is ``benchmark/run.py`` with the engine's ``train_batch`` wrapped
(two clock readings a step; the result line is the harness's own) and
prints one more line, ``PROBE {...}``: over the window's steps the
percentiles of ``step_ms``, of ``call_ms`` (inside ``train_batch``: the
transfer and the dispatch), ``wait_ms`` (until the loss is ready) and
``gap_ms`` (the caller's loop); ``put_ms`` / ``dispatch_ms`` medians over
the calls under and over ``SLOW_CALL_MS``; ``slow_from`` (the first step
from which ``call_ms`` stays over it); ``python_dispatches`` (calls that
left jaxlib's fast path); ``stalls`` (every step 20 ms over the median,
with its start on ``time.perf_counter``, which two processes of one
machine share). The file holds every step's row. ``beside spin`` reads the
clock in a loop (user space alone), ``beside sleep`` sleeps 1 ms between
readings (a system call each): both write every gap over 20 ms.
"""

import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

SLOW_CALL_MS = 4.2      # between the two states of ``call_ms`` (2.2 / 6)
STALL_MS = 20.0


def beside(mode: str, out: str, seconds: float) -> None:
    gaps, n = [], 0
    last = time.perf_counter()
    end = last + seconds

    def write(done):
        with open(out, "w") as f:
            json.dump({"mode": mode, "gaps": gaps, "readings": n,
                       "done": done}, f)

    while last < end:
        if mode == "sleep":
            time.sleep(0.001)
        t = time.perf_counter()
        if 1e3 * (t - last) > STALL_MS:
            gaps.append((last, round(1e3 * (t - last), 1)))
            write(False)
        last = t
        n += 1
    write(True)


def _pcts(xs) -> dict:
    xs = sorted(xs)
    at = lambda q: round(1e3 * xs[min(len(xs) - 1, int(q * len(xs)))], 3)  # noqa: E731
    return {"p5": at(0.05), "p50": at(0.5), "p95": at(0.95),
            "max": at(1.0), "mean": round(1e3 * statistics.fmean(xs), 3)}


def _median_ms(xs):
    return round(1e3 * statistics.median(xs), 3) if xs else None


def summary(rows: list, warmup: int) -> dict:
    """``rows``: a call's [enter, exit, ready, put_s, dispatch_s,
    python_dispatches]; the window is what follows the warm-up's calls."""
    rows = rows[warmup:]
    steps = [b[0] - a[0] for a, b in zip(rows, rows[1:])]
    call = [r[1] - r[0] for r in rows]
    p50 = statistics.median(steps)
    slow = [1e3 * c > SLOW_CALL_MS for c in call]
    slow_from = next((i for i in range(len(slow)) if all(slow[i:])), None)
    parts = {}
    for name, keep in (("fast", False), ("slow", True)):
        rs = [r for r, s in zip(rows, slow) if s is keep]
        parts[name] = {"calls": len(rs),
                       "put_ms": _median_ms([r[3] for r in rs]),
                       "dispatch_ms": _median_ms([r[4] for r in rs])}
    return {
        "steps": len(rows), "step_ms": _pcts(steps), "call_ms": _pcts(call),
        "wait_ms": _pcts([r[2] - r[1] for r in rows]),
        "gap_ms": _pcts([b[0] - a[2] for a, b in zip(rows, rows[1:])]),
        "calls": parts, "slow_from": slow_from,
        "python_dispatches": sum(r[5] for r in rows),
        "window": [rows[0][0], rows[-1][2]],
        "stalls": [{"step": i, "at": round(rows[i][0], 3),
                    "over_ms": round(1e3 * (s - p50), 1),
                    "call_ms": round(1e3 * call[i], 1),
                    "wait_ms": round(1e3 * (rows[i][2] - rows[i][1]), 1)}
                   for i, s in enumerate(steps)
                   if 1e3 * (s - p50) > STALL_MS]}


class _Loss:
    """What the loop of ``kinds/train_job.py`` does with a loss, with the
    clock read when it is ready."""

    def __init__(self, loss, row):
        self.loss, self.row = loss, row

    def block_until_ready(self):
        self.loss.block_until_ready()
        self.row[2] = time.perf_counter()
        return self

    def __float__(self):
        return float(self.loss)


def probed_run(out: str, argv: list, rig=None) -> int:
    import run as bench_run
    from kinds import train_job
    import jax._src.pjit as pjit
    rows: list = []
    now: dict = {}
    build = train_job.build_engine

    def timed(name, fn):
        def call(*a, **k):
            t0 = time.perf_counter()
            try:
                return fn(*a, **k)
            finally:
                now[name] = time.perf_counter() - t0
        return call

    # jaxlib's fast path calls this (a private name of jax 0.9) only when
    # it cannot dispatch a call itself
    python_path = pjit._run_python_pjit

    def counted(*a, **k):
        now["python"] = now.get("python", 0) + 1
        return python_path(*a, **k)

    pjit._run_python_pjit = counted

    def build_engine(*a, **k):
        engine, model = build(*a, **k)
        inner = engine.train_batch
        engine._put_batch = timed("put", engine._put_batch)
        engine._train_step = timed("dispatch", engine._train_step)

        def train_batch(batch=None, data_iter=None):
            now.clear()
            t0 = time.perf_counter()
            loss = inner(batch, data_iter)
            row = [t0, time.perf_counter(), None, now.get("put", 0.0),
                   now.get("dispatch", 0.0), now.get("python", 0)]
            rows.append(row)
            return _Loss(loss, row)

        engine.train_batch = train_batch
        return engine, model

    train_job.build_engine = build_engine
    rc = bench_run.main(argv, rig=rig)
    workload = argv[argv.index("--workload") + 1]
    from lib import files
    warmup = int(files.load_cell(workload)["traffic_file"]["warmup_steps"])
    rows = [r for r in rows if r[2] is not None]
    line = summary(rows, warmup)
    with open(out, "w") as f:
        json.dump({"argv": argv, "rows": rows, "summary": line}, f)
    print("PROBE " + json.dumps(line), flush=True)
    return rc


if __name__ == "__main__":
    if sys.argv[1] == "beside":
        beside(sys.argv[2], sys.argv[3], float(sys.argv[4]))
    else:
        sys.exit(probed_run(sys.argv[2], sys.argv[3:]))

"""``host_probe.py`` (PR 56): its reading of a window's rows on rows made
by hand, and the probed run end to end on the CPU at the tiny preset (the
harness's own result line is untouched). By hand, with the rest:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import host_probe                                   # noqa: E402

CELL = "train-s8k-1chip"


def _rows(calls_ms, step_ms=350.0, freeze_at=None, freeze_ms=0.0):
    """Two warm-up calls, then one row a window step: ``call_ms`` inside
    ``train_batch`` (a third of it the transfer), the rest of the step
    waiting; ``freeze_ms`` more of waiting in step ``freeze_at``."""
    rows, t = [], 100.0
    for i, c in enumerate([2.0, 2.0] + list(calls_ms)):
        wait = step_ms - c + (freeze_ms if i - 2 == freeze_at else 0.0)
        rows.append([t, t + c / 1e3, t + (c + wait) / 1e3, c / 3e3,
                     c / 2e3, 0])
        t += (c + wait) / 1e3 + 0.0005
    return rows


def test_summary_reads_the_two_states_and_a_freeze():
    calls = [2.2] * 30 + [6.1] * 50
    s = host_probe.summary(_rows(calls, freeze_at=10, freeze_ms=110.0), 2)
    assert s["steps"] == 80 and s["slow_from"] == 30
    assert s["calls"]["fast"]["calls"] == 30
    assert s["calls"]["slow"] == {"calls": 50, "put_ms": 2.033,
                                  "dispatch_ms": 3.05}
    assert s["call_ms"]["p50"] == 6.1 and s["python_dispatches"] == 0
    assert [(x["step"], x["over_ms"]) for x in s["stalls"]] == [(10, 110.0)]
    assert s["stalls"][0]["wait_ms"] == 457.8
    assert abs(s["gap_ms"]["p50"] - 0.5) < 1e-6
    # a run that never leaves the fast state has no ``slow_from``
    assert host_probe.summary(_rows([2.3] * 20), 2)["slow_from"] is None


def test_beside_writes_the_gaps_it_sees(tmp_path):
    out = tmp_path / "spin.json"
    host_probe.beside("sleep", str(out), 0.05)
    got = json.loads(out.read_text())
    assert got["done"] is True and got["readings"] > 5
    assert all(ms > host_probe.STALL_MS for _, ms in got["gaps"])


def test_a_probed_run_is_the_harness_run(tmp_path):
    out = tmp_path / "probe.json"
    code = ("import sys, cpu_rig, host_probe; sys.exit(host_probe.probed_run("
            f"{str(out)!r}, ['--workload', {CELL!r}, '--seed', '3000000019', "
            "'--seconds', '2', '--trace', '0'], rig=cpu_rig.RIG))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=HERE,
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   CHECKOUT, ".bench_trace", "test_jax_cache"))
    p = subprocess.run([sys.executable, "-c", code], cwd=CHECKOUT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-2])
    assert lines[-1].startswith("PROBE ")
    probe = json.loads(lines[-1][6:])
    assert line["correct"] is True
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert probe["steps"] == line["attempted"]
    assert probe["python_dispatches"] == 0
    assert probe["calls"]["fast"]["calls"] + probe["calls"]["slow"][
        "calls"] == probe["steps"]
    saved = json.loads(out.read_text())
    assert len(saved["rows"]) == probe["steps"] + 2

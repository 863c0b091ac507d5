"""The benchmark's contract with the program (PERF.md section 7, asked
for in PR 26; written in PR 29). Tier-1, host-only, no run and no edit
under ``benchmark/``: ``BENCHMARK.json`` against the files it names, and
the names the harness takes from the program, which a refactor of the
program must keep: nothing else the driver's test command runs looks at
``benchmark/``."""

import inspect
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmark"
CONTRACT = json.loads((ROOT / "BENCHMARK.json").read_text())


def _load(path):
    assert path.is_file(), f"{path.relative_to(ROOT)} is missing"
    return json.loads(path.read_text())


@pytest.mark.parametrize("workload", CONTRACT["workloads"],
                         ids=lambda w: w["name"])
def test_every_cell_has_its_files(workload):
    """A cell of BENCHMARK.json is a file under cells/ that names the
    same configuration, traffic and chips; both of those are files whose
    architecture and kind are modules; the cell reports exactly the
    metrics the contract declares for it."""
    cell = _load(BENCH / "cells" / f"{workload['name']}.json")
    for key in ("config", "traffic", "chips", "why"):
        assert cell[key] == workload[key], key
    declared = {c["name"]: c for c in CONTRACT["configs"]}[cell["config"]]
    assert declared["file"] == f"benchmark/configs/{cell['config']}.json"
    config = _load(ROOT / declared["file"])
    assert config["source"] == declared["source"]
    assert sorted(config["reduced"]) == sorted(declared["reduced"])
    traffic = _load(BENCH / "traffic" / f"{cell['traffic']}.json")
    assert (BENCH / "architectures"
            / f"{config['architecture']}.py").is_file()
    assert (BENCH / "kinds" / f"{traffic['kind']}.py").is_file()
    cells = [w["name"] for w in CONTRACT["workloads"]]
    for key in ("end_to_end", "per_layer"):
        declared = {m["name"] for m in CONTRACT[key]
                    if workload["name"] in m.get("workloads", cells)}
        assert set(cell[key]) == declared, key


_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
_UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
_SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
_KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}


def _is_a_line(text):
    """1 to 200 printable characters on one line: what the driver asks
    of a ``why``, a ``layer``, a ``source`` and each word of ``command``
    (PR 38 was refused for a ``why`` of 201)."""
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and text.isprintable() and text.isascii())


@pytest.mark.parametrize("group", sorted(_KEYS))
def test_the_contract_keeps_the_drivers_form(group):
    """The rules of form the driver holds ``BENCHMARK.json`` to before
    any run, so that a fault of form fails here and not there."""
    needed, allowed = _KEYS[group]
    names = [entry["name"] for entry in CONTRACT[group]]
    assert len(names) == len(set(names))
    assert 1 <= len(names) <= (128 if group == "per_layer" else 24)
    cells = {w["name"] for w in CONTRACT["workloads"]}
    for entry in CONTRACT[group]:
        where = (group, entry["name"])
        assert needed <= set(entry) <= needed | allowed, where
        assert _NAME.fullmatch(entry["name"]), where
        for key in ("why", "layer"):
            assert key not in entry or _is_a_line(entry[key]), where + (key,)
        if "unit" in entry:
            assert _UNIT.fullmatch(entry["unit"]), where
            assert entry["better"] in ("lower", "higher"), where
            assert entry["source"] in _SOURCES, where
            assert set(entry.get("workloads", cells)) <= cells, where
    if group == "configs":
        for entry in CONTRACT[group]:
            assert _is_a_line(entry["source"]), entry["name"]
            assert len(entry["reduced"]) <= 16
            assert all(_NAME.fullmatch(k) for k in entry["reduced"])
            assert entry["file"].startswith(
                tuple(p + "/" for p in CONTRACT["paths"]))
        files = [entry["file"] for entry in CONTRACT[group]]
        assert len(files) == len(set(files))
    if group == "workloads":
        configs = {c["name"] for c in CONTRACT["configs"]}
        pairs = [(w["config"], w["traffic"]) for w in CONTRACT[group]]
        assert len(pairs) == len(set(pairs))
        for w in CONTRACT[group]:
            assert w["config"] in configs and w["chips"] in (1, 4)
            assert _NAME.fullmatch(w["traffic"]), w["name"]
        four = sum(w["chips"] == 4 for w in CONTRACT[group])
        assert four <= max(1, len(pairs) // 4)
    if group == "end_to_end":
        assert all(m["source"] in ("host_clock", "device_trace")
                   for m in CONTRACT[group])
    if group == "per_layer":
        metrics = [m["name"] for key in ("end_to_end", "per_layer")
                   for m in CONTRACT[key]]
        assert len(metrics) == len(set(metrics))
        assert all(_is_a_line(word) for word in CONTRACT["command"])
        assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
        runs = 2 + 14 * len(cells)
        seconds = CONTRACT["run_seconds"]
        assert runs * (seconds + 60) + 180 * len(cells) + 1200 <= 43200


def test_every_per_layer_metric_has_its_file():
    assert len(CONTRACT["per_layer"]) >= 21
    for m in CONTRACT["per_layer"]:
        f = _load(BENCH / "layer_metrics" / f"{m['name']}.json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert f[key] == m[key], (m["name"], key)
        assert sorted(f["cells"]) == sorted(m["workloads"]), m["name"]
        assert f["reducer"]["name"]
        assert m["moves"] in {e["name"] for e in CONTRACT["end_to_end"]}


def test_the_names_the_harness_takes_from_the_program_exist():
    """lib/telemetry.py, run.py and kinds/train_job.py import these by
    name; each is checked to be still in use there, then to exist."""
    src = "\n".join(p.read_text() for p in BENCH.rglob("*.py")
                    if "tests" not in p.parts)
    import deepspeed_tpu as ds
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.utils import telemetry_probe

    assert "IMPORT_SECONDS" in src
    assert isinstance(ds.IMPORT_SECONDS, float)
    assert "active_telemetry" in src
    assert callable(telemetry_probe.active_telemetry)
    assert "export_artifacts" in src
    assert callable(telemetry.export_artifacts)
    assert "profiler_annotations" in src and "executable_ledger" in src
    params = inspect.signature(telemetry.configure).parameters
    assert {"profiler_annotations", "executable_ledger"} <= set(params)
    assert "ds.initialize" in src and callable(ds.initialize)
    for attr in re.findall(r"\btelemetry\.(get_\w+)\(", src):
        assert callable(getattr(telemetry, attr)), attr


def test_the_step_keeps_the_names_the_reducers_find(devices8):
    """The trace reducers find the step by its module name, the program
    spans by name and the layers by scope: the jitted callable is
    ``train_step``, the ledger entry ``compiled_step``, and the scopes the
    metric files name are scopes the program opens."""
    import deepspeed_tpu as ds
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.models import Llama
    from deepspeed_tpu.telemetry import scopes

    metric_files = [_load(p) for p in
                    sorted((BENCH / "layer_metrics").glob("*.json"))]
    text = json.dumps(metric_files)
    modules = set(re.findall(r'"module": "([^"]+)"', text))
    assert modules == {"^jit_train_step"}
    named = set(re.findall(r"ds\.[a-z_]+", text))
    assert named and named <= (set(scopes.DEVICE_SCOPES)
                               | set(scopes.KIND_SCOPES)
                               | set(scopes.SSM_SCOPES)
                               | set(scopes.MIXER_SCOPES)
                               | set(scopes.WINDOW_SCOPES)
                               | set(scopes.LOOP_SCOPES)
                               | set(scopes.GDN_SCOPES))

    telemetry.shutdown()
    try:
        engine, *_ = ds.initialize(model=Llama(size="tiny"), config={
            "train_batch_size": 8, "bf16": {"enabled": True},
            "zero_optimization": {"stage": 3},
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "steps_per_print": 10 ** 9,
            "telemetry": {"enabled": True, "executable_ledger": True}})
        assert engine._train_step.__name__ == "train_step"
        import numpy as np
        tok = np.zeros((8, 16), np.int32)
        engine.train_batch((tok, tok)).block_until_ready()
        spans = set(telemetry.get_tracer().totals())
        kind = (BENCH / "kinds" / "train_job.py").read_text()
        traffic = [_load(p) for p in (BENCH / "traffic").glob("*.json")]
        wanted = {"train_batch", "batch_to_device", "compiled_step",
                  "first_step", "init/topology", "init/state",
                  "init/build_step"}
        assert wanted <= spans, wanted - spans
        for t in traffic:       # the spans idle gaps are attributed to
            assert any(re.search(t["span_pattern"], s) for s in spans)
        assert 'LEDGER_ENTRY = "compiled_step"' in kind
        assert "compiled_step" in {
            e.name for e in telemetry.get_ledger().entries()}
    finally:
        telemetry.shutdown()


# ---- the kind of work of each device op (ISSUE 36) -------------------------
# reducers/work.py and the twenty metric definitions that read it. They are
# data beside a by-hand reader (benchmark/tests/work_split.py) and not yet
# files under layer_metrics/: run.py takes a cell's metrics from the
# ``per_layer`` list of cells/<cell>.json, which only a ``benchmark`` PR may
# edit (PERF.md section 7).
WORK_METRICS = json.loads((BENCH / "tests" / "work_metrics.json").read_text())


def _bench_on_path():
    import sys
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))


@pytest.mark.parametrize("name", sorted(WORK_METRICS))
def test_a_work_metric_is_a_metric_file_in_all_but_place(name):
    """The keys of a metric file, a unit and a layer its neighbours use,
    cells the contract has, a reducer of ``reducers/work.py``, and no
    ``ds.`` name that is not a scope the program opens; a name the
    contract does not have yet."""
    _bench_on_path()
    from deepspeed_tpu.telemetry import scopes
    from lib import reducers
    spec = WORK_METRICS[name]
    assert set(spec) == {"layer", "unit", "better", "source", "moves",
                         "cells", "what", "reducer"}
    assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", name)
    assert name not in {m["name"] for m in CONTRACT["per_layer"]}
    assert spec["layer"] in {m["layer"] for m in CONTRACT["per_layer"]}
    assert spec["unit"] == ("GiB" if "_gib." in name else "ms")
    assert (spec["better"], spec["source"], spec["moves"]) == (
        "lower", "device_trace", "train_tokens_per_s")
    cells = {w["name"] for w in CONTRACT["workloads"]}
    assert spec["cells"] and set(spec["cells"]) <= cells
    args = spec["reducer"]["args"]
    assert spec["reducer"]["name"] == (
        "work_gib_per_step" if spec["unit"] == "GiB" else "work_ms_per_step")
    assert callable(reducers.find(spec["reducer"]["name"]))
    assert args["module"] == "^jit_train_step"
    assert set(args) <= {"pattern", "exclude", "kinds", "module"}
    assert set(args.get("kinds", ())) <= set(scopes.KINDS)
    opened = (set(scopes.DEVICE_SCOPES) | set(scopes.KIND_SCOPES)
              | set(scopes.SSM_SCOPES) | set(scopes.MIXER_SCOPES))
    for text in (spec["what"], args["pattern"], args.get("exclude", "")):
        plain = text.replace("\\b", "").replace("\\", "")   # a regex's
        assert set(re.findall(r"ds\.[a-z_]+", plain)) <= opened, text


def _synthetic_work(tmp_path, with_file=True):
    """Two steps of one chip: a ``while`` that holds a matmul fusion and a
    copy (twice a step), a loop fusion after it, a Mosaic kernel of
    another scope; and the map the program would have exported."""
    _bench_on_path()
    from lib import trace as tr
    ops, modules = [], []
    for step in (0.0, 1.0, 2.0):        # the last run is cut off
        modules.append(("jit_train_step(1)", step, step + 0.9))
        ops += [("%while.1 = (s32[]) while(...)", step + 0.1, step + 0.5),
                ("%fusion.1 = f32[8] fusion(...)", step + 0.10, step + 0.20),
                ("%copy.1 = f32[8] copy(...)", step + 0.20, step + 0.25),
                ("%fusion.1 = f32[8] fusion(...)", step + 0.30, step + 0.40),
                ("%copy.1 = f32[8] copy(...)", step + 0.40, step + 0.45),
                ("%fusion.2 = f32[8] fusion(...)", step + 0.5, step + 0.7),
                ("%closed_call.1 = f32[8] custom-call(...)", step + 0.7,
                 step + 0.8)]
    trace = tr.Trace({0: {tr.OPS_LINE: ops, tr.MODULES_LINE: modules}}, {})
    kda = "fwd:ds.layers/ds.kda"
    rows = {"while.1": {"scope": kda, "kind": "control",
                        "bytes": 10 ** 12, "mixed": False},
            "fusion.1": {"scope": kda, "kind": "matmul",
                         "bytes": 2 ** 30, "mixed": True},
            "copy.1": {"scope": kda, "kind": "move",
                       "bytes": 2 ** 29, "mixed": False},
            "fusion.2": {"scope": kda + "/ds.mix_pre", "kind": "elementwise",
                         "bytes": 2 ** 28, "mixed": False},
            "closed_call.1": {"scope": kda + "/ds.kda_scan/ds.kda_fwd",
                              "kind": "kernel", "bytes": 2 ** 27,
                              "mixed": False}}
    scopes_path = tmp_path / "t.op_scopes.json"
    scopes_path.write_text(json.dumps(
        {"compiled_step": {k: v["scope"] for k, v in rows.items()}}))
    if with_file:
        (tmp_path / "t.op_work.json").write_text(
            json.dumps({"compiled_step": rows}))
    return {"trace": trace, "ledger_entry": "compiled_step",
            "op_scopes_path": str(scopes_path)}


@pytest.mark.parametrize("args,ms,gib", [
    # every kind: the while lends nothing of its own (its gaps, 0.05 s
    # twice a step, belong to no leaf), and its 10**12 bytes are not read
    ({"pattern": r"ds\.kda\b"}, 600.0, 2 * 1.0 + 2 * 0.5 + 0.25 + 0.125),
    ({"pattern": r"ds\.kda\b", "exclude": r"ds\.kda_scan\b"},
     500.0, 2 * 1.0 + 2 * 0.5 + 0.25),
    ({"pattern": r"ds\.kda\b", "exclude": r"ds\.kda_scan\b",
      "kinds": ["matmul"]}, 200.0, 2.0),    # a leaf in a loop: per event
    ({"pattern": r"ds\.kda\b", "kinds": ["move"]}, 100.0, 1.0),
    ({"pattern": r"ds\.kda\b", "kinds": ["elementwise", "move"]},
     300.0, 1.25),
    ({"pattern": r"ds\.kda\b.*ds\.mix_pre\b"}, 200.0, 0.25),
    ({"pattern": "", "kinds": ["kernel"]}, 100.0, 0.125),
    ({"pattern": r"ds\.mamba\b"}, 0.0, 0.0),
])
def test_work_reducers_on_a_synthetic_trace(tmp_path, args, ms, gib):
    _bench_on_path()
    from lib import reducers
    ctx = _synthetic_work(tmp_path)
    args = dict(args, module="^jit_train_step")
    assert reducers.find("work_ms_per_step")(ctx, args) == pytest.approx(ms)
    assert reducers.find("work_gib_per_step")(ctx, args) == gib


def test_work_reducers_read_nothing_where_the_program_wrote_no_file(
        tmp_path):
    """The parent: ``op_scopes.json`` is there, ``op_work.json`` is not;
    the metric is left out, nothing raises."""
    _bench_on_path()
    from lib import reducers
    args = {"pattern": "", "module": "^jit_train_step"}
    for ctx in (_synthetic_work(tmp_path, with_file=False),
                {"trace": None}, {"trace": None, "op_scopes_path": None}):
        assert reducers.find("work_ms_per_step")(ctx, args) is None
        assert reducers.find("work_gib_per_step")(ctx, args) is None

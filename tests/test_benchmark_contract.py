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
    assert named and named <= scopes.KNOWN_SCOPES

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


# ---- the host's side of a step (ISSUE 52) ----------------------------------
# reducers/hostgap.py and the six definitions that read it: data beside a
# by-hand reader (benchmark/tests/gap_split.py), as the twenty above are and
# for the same reason.
GAP_METRICS = json.loads((BENCH / "tests" / "gap_metrics.json").read_text())
GAP_PARTS = ("caller", "prepare", "h2d", "observe", "dispatch", "launch")
GAP_ARGS = {"module": "^jit_train_step",
            "launch": "^TpuLoadedExecutable::ExecuteLaunch$"}
SCOPED_TRACE = BENCH / "tests" / "data" / "train_1chip_3steps_scoped.xplane.pb"


def test_the_gap_metrics_are_the_six_parts():
    assert sorted(GAP_METRICS) == sorted(
        f"gap_{part}_ms.train" for part in GAP_PARTS)


@pytest.mark.parametrize("name", sorted(GAP_METRICS))
def test_a_gap_metric_is_a_metric_file_in_all_but_place(name):
    """The keys of a metric file, the train entry's layer, the cells of
    ``clock_bracket_us.train`` (the seven the contract had when the parts
    came: a cell a later PR adds is none of the file's until a ``benchmark``
    PR appends it, PR 54), the reducer of ``reducers/hostgap.py`` with the
    module and the launch event ``clock_bracket_us.train`` reads; a name
    the contract does not have yet."""
    _bench_on_path()
    from lib import reducers
    from reducers import hostgap
    spec = GAP_METRICS[name]
    assert set(spec) == {"layer", "unit", "better", "source", "moves",
                         "cells", "what", "reducer"}
    assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", name)
    assert name not in {m["name"] for m in CONTRACT["per_layer"]}
    assert (spec["layer"], spec["unit"], spec["better"], spec["source"],
            spec["moves"]) == ("train entry", "ms", "lower", "device_trace",
                               "train_tokens_per_s")
    assert spec["layer"] in {m["layer"] for m in CONTRACT["per_layer"]}
    bracket = _load(BENCH / "layer_metrics" / "clock_bracket_us.train.json")
    assert sorted(spec["cells"]) == sorted(bracket["cells"])
    assert set(spec["cells"]) <= {w["name"] for w in CONTRACT["workloads"]}
    assert spec["reducer"]["name"] == "gap_part_ms"
    assert reducers.find("gap_part_ms") is hostgap.gap_part_ms
    args = dict(spec["reducer"]["args"])
    assert name == f"gap_{args.pop('part')}_ms.train"
    assert args == bracket["reducer"]["args"] == GAP_ARGS
    assert spec["what"] and "\n" not in spec["what"]


def _synthetic_gaps(new_spans=True, offset=0.0):
    """Four runs of the step on one chip, 100 ms apart with 3 ms between
    them, and the host's path of each on a clock ``offset`` seconds behind
    the device's; the caller blocks, so the bracket has both limits."""
    _bench_on_path()
    from lib import trace as tr
    ops, modules, py, rt = [], [], [], []
    for i in range(4):
        d0 = 0.1 * i
        modules.append(("jit_train_step(1)", d0 - 1e-5, d0 + 0.0971))
        ops += [("%fusion.1 = f32[8] fusion(...)", d0, d0 + 0.05),
                ("%fusion.2 = f32[8] fusion(...)", d0 + 0.05, d0 + 0.097)]
        h = d0 - 0.003 - offset                # the last step's end, host's
        tb = h + 0.0004 + 1e-5 * i             # a caller a little later
        py += [("train_batch", tb, tb + 0.0030),
               ("batch_to_device", tb + 0.0002, tb + 0.0012),
               ("compiled_step", tb + 0.00152, tb + 0.0022),
               ("PjitFunction(train_step)", tb + 0.00153, tb + 0.0021),
               ("step_boundary", tb + 0.0031, tb + 0.0032)]
        if new_spans:
            py += [("train_batch/prepare", tb, tb + 0.00019),
                   ("train_batch/observe", tb + 0.0012, tb + 0.0015),
                   ("train_batch/account", tb + 0.0022, tb + 0.0030)]
        rt.append(("TpuLoadedExecutable::ExecuteLaunch",
                   tb + 0.0019, tb + 0.002))
    return tr.Trace({0: {tr.OPS_LINE: ops, tr.MODULES_LINE: modules}},
                    {"python3": sorted(py, key=lambda e: e[1]),
                     "main/1": rt})


def test_gap_parts_sum_to_the_gap_every_step():
    _bench_on_path()
    from lib import reducers, trace as tr
    from reducers import hostgap
    t = _synthetic_gaps()
    rows = hostgap.gap_parts(t, GAP_ARGS["module"], GAP_ARGS["launch"], 0.0)
    gaps = tr.step_gaps(t, GAP_ARGS["module"])
    assert len(rows) == len(gaps) == 3
    for i, (row, gap) in enumerate(zip(rows, gaps), start=1):
        assert row["gap"] == gap == pytest.approx(0.003)
        parts = [row[k] for k in GAP_PARTS + ("other",)]
        assert None not in parts
        assert sum(parts) == pytest.approx(gap, abs=1e-6)
        assert row["caller"] == pytest.approx(0.0004 + 1e-5 * i)
        assert (row["prepare"], row["h2d"], row["observe"],
                row["dispatch"]) == pytest.approx(
                    (0.0002, 0.0010, 0.0003, 0.00038))
        assert row["other"] == pytest.approx(0.00002)   # observe's end to
        assert row["launch"] == pytest.approx(          # compiled_step
            0.003 - 0.0019 - 0.0004 - 1e-5 * i)
    # the reducer: the bracket's own midpoint, the median over the steps
    ctx = {"trace": t}
    read = {part: reducers.find("gap_part_ms")(ctx, dict(GAP_ARGS, part=part))
            for part in GAP_PARTS + ("other",)}
    mid = ctx["clock_bracket"]["midpoint"]
    assert read["h2d"] == pytest.approx(1.0)
    assert read["caller"] == pytest.approx(1e3 * (0.00042 + mid))
    assert read["launch"] == pytest.approx(1e3 * (0.00068 - mid))
    assert sum(read.values()) == pytest.approx(3.0, abs=1e-3)


def test_an_offset_error_moves_only_the_two_ends():
    """Four parts are differences of host events; ``caller`` and ``launch``
    hold one device event each, so 0.5 ms of offset moves them by +0.5 and
    -0.5 and their sum not at all."""
    _bench_on_path()
    from reducers import hostgap
    t = _synthetic_gaps()
    a = hostgap.gap_parts(t, GAP_ARGS["module"], GAP_ARGS["launch"], 0.0)
    b = hostgap.gap_parts(t, GAP_ARGS["module"], GAP_ARGS["launch"], 0.0005)
    for x, y in zip(a, b):
        assert y["caller"] - x["caller"] == pytest.approx(0.0005)
        assert y["launch"] - x["launch"] == pytest.approx(-0.0005)
        for k in ("prepare", "h2d", "observe", "dispatch", "other", "gap"):
            assert y[k] == x[k]
    # and the same trace read on a host clock 1.2 ms behind the device's
    # gives the same parts, because the bracket finds the offset
    from lib import reducers
    for part in GAP_PARTS:
        args = dict(GAP_ARGS, part=part)
        assert reducers.find("gap_part_ms")(
            {"trace": _synthetic_gaps(offset=0.0012)}, args) == pytest.approx(
                reducers.find("gap_part_ms")({"trace": t}, args), abs=1e-6)


@pytest.mark.parametrize("part", GAP_PARTS + ("other",))
def test_gap_parts_of_a_program_without_the_new_spans(part):
    """A parent from before PR 52: ``prepare`` and ``observe`` read nothing
    and their time lies in ``other``; without a trace, or with a caller
    that does not block (no midpoint), every part reads nothing."""
    _bench_on_path()
    from lib import reducers
    read = reducers.find("gap_part_ms")
    args = dict(GAP_ARGS, part=part)
    old = read({"trace": _synthetic_gaps(new_spans=False)}, args)
    new = read({"trace": _synthetic_gaps()}, args)
    if part in ("prepare", "observe"):
        assert old is None and new is not None
    elif part == "other":
        assert old == pytest.approx(new + 0.2 + 0.3)
    else:
        assert old == pytest.approx(new)
    assert read({"trace": None}, args) is None
    assert read({"trace": _synthetic_gaps(),
                 "clock_bracket": {"upper": 0.0, "lower": None, "steps": 3,
                                   "midpoint": None}}, args) is None


@pytest.mark.parametrize("part", GAP_PARTS + ("other",))
def test_gap_parts_on_a_trace_recorded_on_the_chip(part):
    """``train_1chip_3steps_scoped.xplane.pb`` (PR 24's program: the
    launch event is there, the new spans are not): four parts read, two
    do not, and with ``other`` they sum to every step's gap."""
    _bench_on_path()
    from lib import reducers, trace as tr
    from reducers import hostgap
    ctx = {"trace": tr.Trace.from_file(str(SCOPED_TRACE))}
    value = reducers.find("gap_part_ms")(ctx, dict(GAP_ARGS, part=part))
    if part in ("prepare", "observe"):
        assert value is None
    else:
        assert 0.2 < value < 2.0
    rows = hostgap.parts_of(ctx, GAP_ARGS)
    gaps = tr.step_gaps(ctx["trace"], GAP_ARGS["module"])
    assert [r["gap"] for r in rows] == gaps and len(gaps) == 3
    for r in rows:
        assert sum(r[k] or 0.0 for k in GAP_PARTS + ("other",)) == \
            pytest.approx(r["gap"], abs=1e-6)


def test_gap_split_reads_a_traced_runs_directory(tmp_path, monkeypatch):
    """The by-hand reader on the recorded trace laid out as a traced run
    leaves it: the rows sum, the twice-opened ``PjitFunction(train_step)``
    lies inside ``compiled_step``, an untraced rate gives its gap."""
    _bench_on_path()
    import shutil
    import sys
    from lib import tracer
    run = tmp_path / "train-s8k-1chip" / "plugins" / "profile" / "t"
    run.mkdir(parents=True)
    shutil.copy(SCOPED_TRACE, run)
    monkeypatch.setattr(tracer, "TRACE_ROOT", tmp_path)
    sys.path.insert(0, str(BENCH / "tests"))
    try:
        import gap_split
    finally:
        sys.path.pop(0)
    out = gap_split.gap_split("train-s8k-1chip", "31000")
    assert set(out["metrics"]) == set(GAP_METRICS)
    assert out["metrics"]["gap_observe_ms.train"] is None
    assert out["host_gap_ms"] == pytest.approx(3.556, abs=1e-3)
    assert all(s["sum"] == pytest.approx(s["gap"], abs=1e-3)
               for s in out["steps"])
    assert out["bracket_us"]["lower"] < out["bracket_us"]["upper"]
    (pjit,) = [e for e in out["in_compiled_step"]
               if e["name"] == "PjitFunction(train_step)"]
    assert pjit["events_per_step"] == 2.0 and pjit["thread"] == "python3"
    assert out["untraced"]["implied_gap_ms"] == pytest.approx(
        1e3 * 8192 / 31000 - out["untraced"]["device_step_ms"])
    json.dumps(out)


# ---- the held sweep counted where it runs (ISSUE 68) ------------------------
# reducers/sweep.py and the six definitions that read it: data beside a
# by-hand reader (benchmark/tests/sweep_split.py), as the twenty-six above are
# and for the same reason.
SWEEP_METRICS = json.loads(
    (BENCH / "tests" / "sweep_metrics.json").read_text())
# name -> (unit, source, reducer, the registry names or the scope its
# ``what`` has to name)
SWEEP_FORMS = {
    "moe_sweep_trips.routed": (
        "trips/call", "program_counter", "sweep_trips_per_call",
        ("ds_moe_sweep_trips_total", "ds_moe_held_calls_total")),
    "moe_extra_trip_steps.routed": (
        "%", "program_counter", "sweep_extra_trip_steps_pct",
        ("ds_moe_sweep_extra_trip_steps_total", "ds_moe_held_steps_total")),
    "moe_tile_pad_share.routed": (
        "%", "program_counter", "sweep_tile_pad_share_pct",
        ("ds_moe_held_rows_total", "ds_moe_sweep_tiles_total{state=live}",
         "ds_moe_sweep_tile_rows")),
    "moe_dead_tile_share.routed": (
        "%", "program_counter", "sweep_dead_tile_share_pct",
        ("ds_moe_sweep_tiles_total{state=live}",
         "ds_moe_sweep_tiles_total{state=swept}")),
    "moe_extra_trip_cost_ms.routed": (
        "ms", "device_trace", "extra_trip_cost_ms", ("moe_extra_trip",)),
    "router_ms.routed": (
        "ms", "device_trace", "scope_ms_per_step", ("ds.moe_router",)),
}


def _sweep_snapshot(reg_names=None):
    """A registry's snapshot after three finished steps of five routed
    layers, the second with a call of two trips (the recorder's own
    output on hand-made scalars); ``reg_names`` keeps only those."""
    import numpy as np
    from deepspeed_tpu.moe.dispatch import record_held_expert_counts
    from deepspeed_tpu.telemetry.registry import MetricsRegistry
    reg = MetricsRegistry()
    for trips, swept, most in ((5, 95, 1), (6, 114, 2), (5, 95, 1)):
        record_held_expert_counts(reg, {k: np.int32(v) for k, v in dict(
            moe_held_rows=14080, moe_held_done=14080, moe_held_calls=5,
            moe_held_experts=8, moe_sweep_trips=trips, moe_sweep_tiles=80,
            moe_sweep_swept=swept, moe_sweep_tile=256,
            moe_sweep_trips_max=most).items()})
    snap = reg.snapshot()
    return snap if reg_names is None else {
        k: v for k, v in snap.items() if k in reg_names}


def _sweep_trace(events=(2,), from_step=0):
    """``_synthetic_gaps``' four runs with a fifth, the run of step 1 six
    ms longer, and a ``moe_extra_trip`` event inside the ``step_boundary``
    behind ``train_batch`` of each step of ``events``: one step behind, so
    the event of step 2's boundary speaks of step 1. The host's events of
    the steps before ``from_step`` are left out: a profiler started while
    that step's run was under way."""
    _bench_on_path()
    from lib import trace as tr
    ops, modules, py, rt = [], [], [], []
    d0 = 0.0
    for i in range(5):
        busy = 0.103 if i == 1 else 0.097
        modules.append(("jit_train_step(1)", d0 - 1e-5, d0 + busy + 1e-4))
        ops += [("%fusion.1 = f32[8] fusion(...)", d0, d0 + 0.05),
                ("%fusion.2 = f32[8] fusion(...)", d0 + 0.05, d0 + busy)]
        tb = d0 - 0.0026
        if i >= from_step:
            py += [("train_batch", tb, tb + 0.0030),
                   ("batch_to_device", tb + 0.0002, tb + 0.0012),
                   ("compiled_step", tb + 0.00152, tb + 0.0022),
                   ("step_boundary", tb + 0.0031, tb + 0.0033)]
            if i in events:
                py.append(("moe_extra_trip", tb + 0.00315, tb + 0.00316))
            rt.append(("TpuLoadedExecutable::ExecuteLaunch",
                       tb + 0.0019, tb + 0.002))
        d0 += busy + 0.003
    return tr.Trace({0: {tr.OPS_LINE: ops, tr.MODULES_LINE: modules}},
                    {"python3": sorted(py, key=lambda e: e[1]),
                     "main/1": rt})


def test_the_sweep_metrics_are_the_six():
    assert sorted(SWEEP_METRICS) == sorted(SWEEP_FORMS)


@pytest.mark.parametrize("name", sorted(SWEEP_FORMS))
def test_a_sweep_metric_is_a_metric_file_in_all_but_place(name):
    """The keys of a metric file, the expert dispatch's layer, the eight
    routed cells (those whose cell file reports ``held_expert_tokens.*``),
    a reducer of ``reducers/sweep.py`` (the router's: the accepted
    ``scope_ms_per_step`` on a scope the program opens), a ``what`` that
    names what it reads; a name the contract does not have yet."""
    _bench_on_path()
    from deepspeed_tpu.telemetry import scopes
    from lib import reducers
    from reducers import sweep
    spec = SWEEP_METRICS[name]
    unit, source, reducer, reads = SWEEP_FORMS[name]
    assert set(spec) == {"layer", "unit", "better", "source", "moves",
                         "cells", "what", "reducer"}
    assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", name)
    assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", spec["unit"])
    assert name not in {m["name"] for m in CONTRACT["per_layer"]}
    assert (spec["layer"], spec["unit"], spec["better"], spec["source"],
            spec["moves"]) == ("expert dispatch", unit, "lower", source,
                               "train_tokens_per_s")
    assert spec["layer"] in {m["layer"] for m in CONTRACT["per_layer"]}
    routed = sorted(
        w["name"] for w in CONTRACT["workloads"]
        if any(m.startswith("held_expert_tokens.") for m in _load(
            BENCH / "cells" / f"{w['name']}.json")["per_layer"]))
    assert sorted(spec["cells"]) == routed and len(routed) == 8
    assert spec["reducer"]["name"] == reducer
    found = reducers.find(reducer)
    args = spec["reducer"]["args"]
    if name == "router_ms.routed":
        assert args == {"pattern": r"ds\.moe_router\b",
                        "module": "^jit_train_step"}
        assert "ds.moe_router" in scopes.KIND_SCOPES
    else:
        assert found is getattr(sweep, reducer)
        assert args == (GAP_ARGS if source == "device_trace" else {})
    assert spec["what"] and "\n" not in spec["what"]
    for text in reads:
        assert text in spec["what"], text
    counted = {n for n, _ in sweep.COUNTERS.values()}
    assert set(re.findall(r"ds_moe_\w+", spec["what"])) <= counted


def test_the_sweep_counters_read_a_snapshot_and_a_live_registry():
    """The four counted readings from the recorder's own output: three
    steps of five calls, one of them with six trips."""
    _bench_on_path()
    from deepspeed_tpu import telemetry
    from lib import reducers
    read = lambda ctx: {  # noqa: E731
        name: reducers.find(form[2])(ctx, {})
        for name, form in SWEEP_FORMS.items() if form[1] == "program_counter"}
    want = {"moe_sweep_trips.routed": pytest.approx(16 / 15),
            "moe_extra_trip_steps.routed": pytest.approx(100 / 3),
            "moe_tile_pad_share.routed": pytest.approx(
                100 * (1 - 14080 / (80 * 256))),
            "moe_dead_tile_share.routed": pytest.approx(
                100 * (1 - 240 / 304))}
    assert read({"registry_snapshot": _sweep_snapshot()}) == want
    # a parent from before the counters, a registry without them, none
    old = {"ds_moe_held_rows_total", "ds_moe_held_calls_total"}
    for ctx in ({"registry_snapshot": _sweep_snapshot(old)},
                {"registry_snapshot": {}}, {"registry_snapshot": None}):
        assert set(read(ctx).values()) == {None}
    telemetry.shutdown()
    assert set(read({}).values()) == {None}       # no live registry
    telemetry.configure()
    try:
        live = telemetry.get_registry()
        for name, metric in _sweep_snapshot().items():
            for v in metric["values"]:
                getattr(live, metric["type"])(name).inc(
                    v["value"], **v["labels"])
        assert read({}) == want
    finally:
        telemetry.shutdown()


@pytest.mark.parametrize("events, want", [
    ((2,), 6.0), ((), None), ((0,), None), ((2, 4), 3.0),
    ((1, 2, 3, 4), None)],
    ids=["one_long_step", "no_event", "speaks_of_a_run_before_the_trace",
         "a_long_and_a_plain_step", "every_step"])
def test_an_extra_trip_event_is_paired_with_the_run_before(events, want):
    """The event lies one step behind: the one in step 2's boundary speaks
    of step 1's run, the long one (with step 3's the median of the two).
    No event, an event that speaks of a run the trace does not hold, or
    one for every step (nothing to compare with) read nothing."""
    _bench_on_path()
    from lib import reducers
    from reducers import sweep
    ctx = {"trace": _sweep_trace(events)}
    got = reducers.find("extra_trip_cost_ms")(ctx, GAP_ARGS)
    assert got == (want if want is None else pytest.approx(want, abs=1e-6))
    rows = sweep.traced_steps(ctx, GAP_ARGS)
    assert len(rows) == 4
    spoken = {i - 1 for i in events if 1 <= i <= 4}
    assert [r["extra_trip"] for r in rows] == [i in spoken for i in range(4)]
    assert [r["device_ms"] for r in rows] == pytest.approx(
        [97.0, 103.0, 97.0, 97.0])
    assert reducers.find("extra_trip_cost_ms")({"trace": None},
                                               GAP_ARGS) is None


def test_a_run_under_way_when_the_trace_began_is_no_traced_step():
    """A profiler started by hand inside the window catches the tail of a
    run (here step 0's, whose host events it did not see): that run is
    left out, the event of step 2's boundary still finds step 1's."""
    _bench_on_path()
    from lib import reducers
    from reducers import sweep
    ctx = {"trace": _sweep_trace((2,), from_step=1)}
    assert reducers.find("extra_trip_cost_ms")(
        ctx, GAP_ARGS) == pytest.approx(6.0, abs=1e-6)
    rows = sweep.traced_steps(ctx, GAP_ARGS)
    assert [(round(r["device_ms"]), r["extra_trip"]) for r in rows] == [
        (103, True), (97, False), (97, False)]


def test_sweep_split_reads_a_traced_runs_directory(tmp_path, monkeypatch):
    """The by-hand reader on a directory laid out as a traced run leaves
    it (the recorded trace of a program from before the counters and the
    snapshot of one that has them): the six names, the counted four from
    the snapshot, the block padding of a cell that has it beside the
    tiles', the program's own extra-trip events with the step each speaks
    of; the recorded trace holds no such event, so no cost."""
    _bench_on_path()
    import shutil
    import sys
    from lib import tracer
    cell = "train-conv-s8k-1chip"
    run = tmp_path / cell / "plugins" / "profile" / "t"
    run.mkdir(parents=True)
    shutil.copy(SCOPED_TRACE, run)
    snap = _sweep_snapshot()
    snap["ds_moe_held_blocks_total"] = {"values": [
        {"labels": {}, "value": 120.0}]}
    snap["ds_moe_held_block_rows"] = {"values": [
        {"labels": {}, "value": 640.0}]}
    (tmp_path / cell / f"{cell}.metrics.json").write_text(json.dumps(snap))
    (tmp_path / cell / f"{cell}.op_scopes.json").write_text("{}")
    span = lambda name, ts, **args: {  # noqa: E731
        "name": name, "ph": "X", "ts": ts, "dur": 1.0, "args": args}
    (tmp_path / cell / f"{cell}.trace.json").write_text(json.dumps({
        "traceEvents": [span("train_batch", 10.0, step=7),
                        span("moe_extra_trip", 20.0, trips=6, calls=5),
                        span("train_batch", 30.0, step=8)]}))
    monkeypatch.setattr(tracer, "TRACE_ROOT", tmp_path)
    sys.path.insert(0, str(BENCH / "tests"))
    try:
        import sweep_split
    finally:
        sys.path.pop(0)
    out = sweep_split.sweep_split(cell)
    assert set(out["metrics"]) == set(SWEEP_METRICS)
    assert out["metrics"]["moe_sweep_trips.routed"] == pytest.approx(16 / 15)
    assert out["metrics"]["moe_extra_trip_cost_ms.routed"] is None
    assert out["metrics"]["router_ms.routed"] is None
    assert out["counters"]["extra"] == 1 and out["counters"]["steps"] == 3
    assert out["block_pad_share"] == {"moe_pad_share.routed": pytest.approx(
        100 * (1 - 42240 / (120 * 640)))}
    assert out["extra_trip_steps"] == [{"step": 6, "trips": 6, "calls": 5}]
    assert [r["extra_trip"] for r in out["steps"]] == [False] * len(
        out["steps"]) and out["steps"]
    json.dumps(out)

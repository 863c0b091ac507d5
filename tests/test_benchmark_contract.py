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
                               | set(scopes.SSM_SCOPES))

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

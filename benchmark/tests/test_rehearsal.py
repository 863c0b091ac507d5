"""A second architecture arrives as files: in a temporary copy of
``benchmark/`` the files under ``tests/rehearsal/`` are ADDED (an
architecture module for the program's ``mixtral`` class whose reference
returns a mask, a configuration of its ``tiny`` preset and one at OLMoE's
router shape, a cell each, a traffic mix, per-layer metrics, a reducer in
a new file, the script that reads where routing flips), nothing that was
there is edited, and the new cells run through ``cpu_rig``. A new train
cell cannot join the seventeen step readers every accepted train cell
reports (``*.train``): a metric's file lists its cells, ``BENCHMARK.json``
the same list, and an adding PR edits no file that is there. So it brings
COPIES of them under a suffix of its own (``*.moe``: the file's
definition, its own cells) beside the nine readings that are its own (one
through a reducer it brings, eight by scope): the 26 entries a routed
cell of PRs 54 and 56 cost, which ``per_layer`` has room for since PR 63
folded it to 67 of the driver's 128, and the next ``benchmark`` PR folds
the copies. Tier-1's
``tests/test_benchmark_contract.py`` and the suite here both pass on
that tree, unedited. Then what must fail does, naming what is
known. By hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH)
ADDED = os.path.join(HERE, "rehearsal")
CELL = "train-moe-tiny-1chip"
OWN_METRIC = "expert_flops_share.moe"
ENTRIES_A_CELL = 26         # seventeen copies and nine readings


def _known(root, directory):
    """What ``lib/files.py`` lists as known in an error: the modules of
    the copy, the rehearsal's among them."""
    return sorted(p[:-3] for p in os.listdir(root / "benchmark" / directory)
                  if p.endswith(".py") and not p.startswith("_"))


def _hashes(root):
    out = {}
    for d, dirs, names in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for n in names:
            path = os.path.join(d, n)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


@pytest.fixture()
def copy(tmp_path):
    """A checkout that holds only ``BENCHMARK.json`` and ``benchmark/``
    (without the rehearsal's own files), then the rehearsal's files added."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        "__pycache__", "rehearsal"))
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), root)
    before = _hashes(root)
    shutil.copytree(ADDED, root / "benchmark", dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    after = _hashes(root)
    assert {k: after[k] for k in before} == before      # added, not edited
    assert len(after) == len(before) + len(_hashes(ADDED))
    return root


def _rig(root, cell=CELL, trace="0", seconds="2"):
    """``cpu_rig.py`` of the copy; the program is the real checkout's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=CHECKOUT,
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   CHECKOUT, ".bench_trace", "test_jax_cache"))
    return subprocess.run(
        [sys.executable, str(root / "benchmark" / "tests" / "cpu_rig.py"),
         cell, trace, seconds], cwd=root, env=env, capture_output=True,
        text=True, timeout=900)


def _edit(path, drop=(), **changes):
    with open(path) as f:
        d = json.load(f)
    d.update(changes)
    for key in drop:
        del d[key]
    with open(path, "w") as f:
        json.dump(d, f)


def test_a_second_architecture_runs_from_added_files_only(copy):
    before = _hashes(copy)
    p = _rig(copy, trace="1", seconds="3")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, p.stdout[-3000:]
    # the new cell reports a copy of each step reader every accepted train
    # cell reports (on the CPU those that need no device plane) and its
    # one new name
    def cell_file(root, name=f"{CELL}.json"):
        with open(os.path.join(root, "cells", name)) as f:
            return json.load(f)["per_layer"]
    listed = cell_file(copy / "benchmark")
    accepted = [cell_file(BENCH, n)
                for n in os.listdir(os.path.join(BENCH, "cells"))]
    every_cells = set(accepted[0]).intersection(*accepted[1:])
    assert len(every_cells) == 17
    assert all(n.endswith(".train") for n in every_cells)
    copies = {n[:-len("train")] + "moe" for n in every_cells}
    assert copies | {OWN_METRIC} <= set(listed)
    assert len(listed) == len(set(listed)) == ENTRIES_A_CELL
    for n in copies:            # a copy says what the accepted file says
        theirs, ours = (json.load(open(os.path.join(
            root, "layer_metrics", name + ".json"))) for root, name in (
            (BENCH, n[:-len("moe")] + "train"), (copy / "benchmark", n)))
        for key in ("layer", "unit", "better", "source", "moves", "reducer"):
            assert ours[key] == theirs[key], (n, key)
    assert {"mfu.moe", "setup_import_s.moe", "setup_init_s.moe",
            "setup_compile_s.moe", OWN_METRIC} <= set(line["metrics"])
    assert set(line["metrics"]) <= set(listed)
    # 2 of 4 experts a token: 2 layers x 2 x 3 x 64 x 128 x 2 FLOPs of
    # 2 x (that + projections 24576 + router 512 + attention) + head 65536
    share = line["metrics"][OWN_METRIC]["value"]
    assert 50.0 < share < 65.0
    agree = [ln for ln in p.stdout.splitlines() if ln.startswith("agreement")]
    assert len(agree) == 1 and "ok=True" in agree[0]
    # an untraced run reports the end-to-end metrics of the new cell
    p = _rig(copy)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    after = _hashes(copy)
    assert {k: after[k] for k in before
            if not k.startswith(".bench_trace")} == {
        k: v for k, v in before.items() if not k.startswith(".bench_trace")}


def test_the_accepted_benchmark_has_one_entry_a_definition():
    """What a fold leaves (PR 49, PR 63): in the ACCEPTED tree no two
    entries say the same thing (layer, unit, better, source, moves,
    reducer). Copies live only between an adding PR and the next fold; the
    rehearsal's are added to a copy, which is why this is held here and
    not in ``test_benchmark.py``, which runs inside that copy too."""
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    seen = {}
    for name in names:
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        key = json.dumps([spec[k] for k in (
            "layer", "unit", "better", "source", "moves", "reducer")],
            sort_keys=True)
        assert key not in seen, (name, seen[key])
        seen[key] = name
    assert len(names) == len(os.listdir(os.path.join(BENCH, "layer_metrics")))


def _write_benchmark_json_entries(root):
    """``BENCHMARK.json`` of the copy gains what a PR adding these files
    writes there (README, "Adding things", step 5), read off the files:
    an entry for each file it adds, and its cells' names in ONE list that
    was there, ``train_tokens_per_s``'s ``workloads``."""
    def added(kind):
        for name in sorted(os.listdir(os.path.join(ADDED, kind))):
            with open(os.path.join(ADDED, kind, name)) as f:
                yield name[:-5], json.load(f)
    with open(root / "BENCHMARK.json") as f:
        b = json.load(f)
    for name, c in added("configs"):
        b["configs"].append({
            "name": name, "source": c["source"][:200],
            "file": f"benchmark/configs/{name}.json",
            "reduced": sorted(c["reduced"]), "why": "rehearsal"})
    for name, c in added("cells"):
        b["workloads"].append({"name": name, **{k: c[k] for k in (
            "config", "traffic", "chips", "why")}})
        for m in b["end_to_end"]:
            if "workloads" in m and m["name"] in c["end_to_end"]:
                m["workloads"].append(name)
    for name, m in added("layer_metrics"):
        b["per_layer"].append({"name": name, **{k: m[k] for k in (
            "unit", "better", "source", "layer", "moves")},
            "workloads": m["cells"]})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(b, f, indent=1)


def test_the_suite_stays_green_with_the_added_files(copy):
    """The benchmark's own tests, run IN the copy with the added files and
    their ``BENCHMARK.json`` entries: no test there counts what the
    harness holds, so a files-only addition edits no test either. The new
    cell runs end to end, and ``tests/control.py``, which knows no
    architecture, comes out not correct on it."""
    _write_benchmark_json_entries(copy)
    contract = os.path.join(CHECKOUT, "tests", "test_benchmark_contract.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=CHECKOUT)
    if os.path.isfile(contract):
        # tier-1's reading of the same tree: ``BENCHMARK.json`` against the
        # files and the driver's rules of form (its two tests that build
        # an engine hold the program, which the copy does not change)
        os.mkdir(copy / "tests")
        shutil.copy(contract, copy / "tests")
        p = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             "tests/test_benchmark_contract.py", "-k",
             "every_cell_has_its_files or keeps_the_drivers_form"
             " or every_per_layer_metric_has_its_file"],
            cwd=copy, env=env, capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
        with open(copy / "BENCHMARK.json") as f:
            b = json.load(f)
        cells = b["workloads"]
        # the room: the accepted entries and this cell's 26, and as many
        # again would still fit the driver's 128
        with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
            accepted = len(json.load(f)["per_layer"])
        assert len(b["per_layer"]) == accepted + ENTRIES_A_CELL
        assert accepted + 2 * ENTRIES_A_CELL <= 128
        # a case a cell, one a group of the contract, one for the files
        assert f"{len(cells) + 4 + 1} passed" in p.stdout, p.stdout[-500:]
    before = _hashes(copy)
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "benchmark/tests/test_benchmark.py",
         "benchmark/tests/test_program_reducers.py"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=1800)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    for must in (f"test_cell_end_to_end_on_cpu[{CELL}]",
                 f"test_the_control_comes_out_not_correct_on_cpu[{CELL}]"):
        assert f"{must} PASSED" in p.stdout, (must, p.stdout[-3000:])
    after = _hashes(copy)
    assert {k: after.get(k) for k in before
            if not k.startswith(".bench_trace")} == {
        k: v for k, v in before.items() if not k.startswith(".bench_trace")}


@pytest.mark.parametrize("stand_in,correct", [("bfloat16", True),
                                              ("float8_e4m3fn", False)])
def test_the_routed_check_passes_bf16_and_fails_fp8(copy, stand_in, correct):
    """``control.py`` on the second rehearsal cell (the ``mixtral`` class
    at the tiny widths with the file's 64 experts top-8, the engine's own
    weights): the reference in the program's place in bfloat16 is what a
    right program looks like and comes out correct; in fp8 it does not.
    On the chip at OLMoE's widths: ``PERF.md`` section 2."""
    tests = copy / "benchmark" / "tests"
    code = ("import sys, json; sys.path[:0] = [%r, %r]; "
            "import cpu_rig, control; print(json.dumps(control.control("
            "'check-moe-64x8-1chip', 3000000019, cpu_rig.RIG, %r)))"
            % (str(tests), str(copy / "benchmark"), stand_in))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=CHECKOUT,
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   CHECKOUT, ".bench_trace", "test_jax_cache"))
    p = subprocess.run([sys.executable, "-c", code], cwd=copy, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["control"] == stand_in and got["correct"] is correct, got
    assert got["rounded_matmuls"] > 0
    assert set(got["got"]) == {"logits_err_max", "logits_err_rms",
                               "excluded_share", "positions_counted",
                               "loss_err"}
    assert set(got["limits"]) == {"logits_err_max", "logits_err_rms",
                                  "excluded_share", "loss_err"}
    assert got["got"]["excluded_share"] <= got["limits"]["excluded_share"]
    assert set(got["all_positions"]) == {"logits_err_max", "logits_err_rms"}


def _unknown_architecture(root):
    _edit(root / "benchmark/configs/mixtral-tiny-zero3-1chip.json",
          architecture="mamba")
    return "0", ["architecture 'mamba'",
                 f"known: {_known(root, 'architectures')}"]


def _no_architecture_key(root):
    _edit(root / "benchmark/configs/mixtral-tiny-zero3-1chip.json",
          drop=["architecture"])
    return "0", ["architecture None",
                 f"known: {_known(root, 'architectures')}"]


def _unknown_kind(root):
    _edit(root / "benchmark/traffic/pretrain-s8k.json", kind="serve_open")
    return "0", ["kind 'serve_open'", f"known: {_known(root, 'kinds')}"]


def _unknown_reducer(root):
    _edit(root / f"benchmark/layer_metrics/{OWN_METRIC}.json",
          reducer={"name": "mfu_of_nothing", "args": {}})
    return "1", ["no reducer named 'mfu_of_nothing'", "'mfu_pct'",
                 "'expert_flops_share_pct'", "'scope_ms_per_step'"]


def _duplicate_reducer(root):
    with open(root / "benchmark/reducers/again.py", "w") as f:
        f.write("from lib.reducers import reducer\n\n\n@reducer\n"
                "def mfu_pct(ctx, args):\n    return 1.0\n")
    return "1", ["reducer 'mfu_pct' is defined twice", "lib.reducers",
                 "reducers.again"]


def _drifted_expert_count(root):
    _edit(root / "benchmark/configs/mixtral-tiny-zero3-1chip.json",
          num_local_experts=8)
    return "0", ["num_local_experts=8", "num_experts=4"]


def _missing_check_key(root):
    path = root / "benchmark/configs/mixtral-tiny-zero3-1chip.json"
    with open(path) as f:
        check = json.load(f)["check"]
    del check["routing_margin"]
    _edit(path, check=check)
    return "0", ["demands ['routing_margin'] of the 'check' of "
                 "configuration mixtral-tiny-zero3-1chip",
                 "['routing_margin', 'excluded_share_max']"]


def _missing_demanded_width(root):
    _edit(root / "benchmark/configs/mixtral-tiny-zero3-1chip.json",
          drop=["num_experts_per_tok"])
    return "0", ["demands 'num_experts_per_tok'"]


@pytest.mark.parametrize("breakage", [
    _unknown_architecture, _no_architecture_key, _unknown_kind,
    _unknown_reducer, _duplicate_reducer, _missing_check_key,
    _drifted_expert_count, _missing_demanded_width], ids=lambda f: f.__name__.strip("_"))
def test_what_is_not_there_fails_and_names_what_is(copy, breakage):
    trace, said = breakage(copy)
    p = _rig(copy, trace=trace)
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
    for text in said:
        assert text in p.stderr, (text, p.stderr[-2000:])
    if breakage not in (_drifted_expert_count, _missing_demanded_width):
        # found by name before any device work: no cell line was printed
        assert "cell train-moe" not in p.stdout

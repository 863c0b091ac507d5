"""chip_smoke.py on the CPU rig (ISSUE 21 satellites).

The smoke itself has no CPU mode: ``python chip_smoke.py`` must fail at
its device gate here. Its phases are driven by import at a tiny size —
Pallas kernels run in interpret mode, so this covers control flow, the
sharding/ledger/leak checks and the plumbing, never Mosaic or a time.
"""

import ast
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _run(code_or_path, env_extra, *, as_code=False):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra)
    cmd = [sys.executable] + (["-c", code_or_path] if as_code
                              else [code_or_path])
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=300)


def test_chip_smoke_fails_at_device_gate_on_cpu():
    proc = _run(os.path.join(REPO, "chip_smoke.py"),
                {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    # no result line: the contract's JSON is printed only on success
    assert '"ok"' not in proc.stdout


@pytest.fixture
def smoke_telemetry():
    from deepspeed_tpu import telemetry
    telemetry.shutdown()
    telemetry.configure(executable_ledger=True)
    yield
    telemetry.shutdown()


def test_chip_smoke_phases_tiny(smoke_telemetry, devices8):
    import chip_smoke
    tiny = chip_smoke.Sizes(
        size="tiny", overrides={"max_seq_len": 512},
        train_layers=2, serve_layers=2, agree_layers=2,
        seq=128, loss_chunk=64, train_steps=5,
        # one prompt past max_chunk_size (256) and past the window (32)
        prompts=(12, 300, 40, 70, 20, 9), new_tokens=8,
        large_leaf=1 << 12)

    train = chip_smoke.train_phase(tiny)
    assert train["devices"] == 8 and train["batch"] == 8
    assert train["losses"][-1] < train["losses"][0]
    assert train["mosaic_calls"] == 0      # interpret mode: no Mosaic

    serve = chip_smoke.serve_phase(tiny)
    assert serve["requests"] == 6
    assert 256 in serve["prefill_dispatches_by_chunk"]
    assert len(serve["params_and_pools_on"]) == 1   # one engine, one device

    agree = chip_smoke.agreement_phase(tiny)
    for name in ("flash/causal/o", "flash/window/dq", "flash/window/dk",
                 "paged/prefill/logits", "paged/decode/logits",
                 "paged/decode/k"):
        assert 0 <= agree[name]["max"] < 2e-2, (name, agree[name])


def test_chip_smoke_phase_failure_is_loud(smoke_telemetry, monkeypatch):
    """A check that does not hold raises; nothing in a phase swallows it
    (main() has no except, so the process then exits non-zero)."""
    import chip_smoke
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check(False, "forced")
    # a kernel that disagrees with its reference fails the phase
    monkeypatch.setattr(chip_smoke, "TOL_KERNEL", (0.0, 0.0))
    monkeypatch.setattr(chip_smoke, "_paged_agreement", lambda sz, cfg: {})
    tiny = chip_smoke.Sizes(size="tiny", seq=128)
    with pytest.raises(chip_smoke.SmokeFailure, match="flash/causal/o"):
        chip_smoke.agreement_phase(tiny)


# ---- compile-cache helper --------------------------------------------------
_PRINT_CACHE = (
    "import jax\n"
    "from deepspeed_tpu.utils.compile_cache import enable_compile_cache\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "used = enable_compile_cache()\n"
    "print(repr((before, used, jax.config.jax_compilation_cache_dir)))\n")


def test_compile_cache_env_set_configures_nothing(tmp_path):
    proc = _run(_PRINT_CACHE, {"JAX_PLATFORMS": "cpu",
                               "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
                as_code=True)
    assert proc.returncode == 0, proc.stderr
    before, used, after = ast.literal_eval(
        proc.stdout.strip().splitlines()[-1])
    # JAX read the variable itself; the helper changed nothing
    assert before == used == after == str(tmp_path)


def test_compile_cache_default_is_fixed_in_checkout_path():
    outs = []
    for _ in range(2):      # two processes, one path
        proc = _run(_PRINT_CACHE, {"JAX_PLATFORMS": "cpu"}, as_code=True)
        assert proc.returncode == 0, proc.stderr
        outs.append(ast.literal_eval(
            proc.stdout.strip().splitlines()[-1]))
    assert outs[0] == outs[1]
    before, used, after = outs[0]
    assert before is None
    assert used == after == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ---- peaks table -------------------------------------------------------------
def test_unknown_device_kind_raises_in_peak_flops(monkeypatch):
    from deepspeed_tpu.accelerator.tpu_accelerator import TPU_Accelerator

    class FakeDevice:
        device_kind = "TPU v99 hyper"

    acc = TPU_Accelerator()
    monkeypatch.setattr(acc, "device", lambda index=None: FakeDevice())
    with pytest.raises(ValueError, match="TPU v99 hyper"):
        acc.peak_flops()
    FakeDevice.device_kind = "TPU v5 lite"
    assert acc.peak_flops() == 197e12


# ---- the ledger's HLO walk on TPU-shaped text --------------------------------
# recorded from the v5e:2x2 AOT compile of a ZeRO-3 step (PR 21), trimmed
_TPU_HLO = '''
%all-reduce-scatter.1.clone (input.23: bf16[14336,4096]) -> bf16[3584,4096] {
  %input.23 = bf16[14336,4096]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %all-reduce.86 = bf16[14336,4096]{1,0:T(8,128)(2,1)} all-reduce(%input.23), channel_id=128, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add.7.clone
  ROOT %dynamic-slice.277 = bf16[3584,4096]{1,0:T(8,128)(2,1)} dynamic-slice(%all-reduce.86, %multiply.195, %constant.1818), dynamic_slice_sizes={3584,4096}
}

ENTRY %main.1 (p0: bf16[32,8192,128]) -> f32[32,8192,128] {
  %all-reduce.9 = f32[] all-reduce(%x), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = bf16[4096,4096]{1,0} all-gather(%w), channel_id=5, replica_groups={{0,1,2,3}}, dimensions={0}
  %shard_map.266 = (f32[32,8192,128]{2,1,0}, f32[32,1,8192]{2,1,0}) custom-call(%a, %b, %c), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[32,8192,128]{2,1,0}}
  %fusion.447 = bf16[3584,4096]{1,0} fusion(%g), kind=kCustom, calls=%all-reduce-scatter.1.clone
}
'''


def test_hlo_walk_reads_tpu_reduce_scatter_fusion_and_custom_calls():
    from deepspeed_tpu.telemetry.collectives import (analyze_hlo,
                                                     custom_call_targets)
    recs = analyze_hlo(_TPU_HLO, n_devices=4)
    by_op = {r["op"]: r for r in recs}
    assert set(by_op) == {"reduce_scatter", "all_reduce", "all_gather"}
    # payload is the full (pre-scatter) input, the reduce-scatter convention
    assert by_op["reduce_scatter"]["bytes"] == 14336 * 4096 * 2
    assert by_op["all_reduce"]["bytes"] == 4      # the entry's scalar one
    assert custom_call_targets(_TPU_HLO) == {"tpu_custom_call": 1}

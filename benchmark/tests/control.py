"""The control of ``correct``: the architecture's reference put in the
program's place and computed one precision below the configurations'
bfloat16 (``CONTROL``: both operands of every matmul against a weight
rounded to fp8 e4m3, per-tensor scaled), compared with the float32
reference exactly as ``kinds/train_job.py`` compares the program. It has to
come out NOT correct. The benchmark's own runs never run it, and the
reference knows nothing of it: ``weight_matmuls_in`` rounds from outside,
so it serves every architecture module as it is.

    chiprun -- python3 benchmark/tests/control.py <cell> <seed> [<seed> ...]

prints one JSON line a seed (a new process each: the engine fills the chip).
On the CPU ``tests/test_benchmark.py`` runs it at the tiny preset.
"""

import contextlib
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

CONTROL = "float8_e4m3fn"      # the precision below bfloat16


def _rounded(t, dtype):
    """``t`` rounded to ``dtype``, scaled so that its largest magnitude is
    the type's largest (per-tensor scaling, as fp8 recipes do)."""
    import jax.numpy as jnp
    scale = float(jnp.finfo(dtype).max) / jnp.max(jnp.abs(t))
    return (t * scale).astype(dtype).astype(jnp.float32) / scale


@contextlib.contextmanager
def weight_matmuls_in(dtype):
    """Inside, every ``dot_general`` without batch dimensions (``a @ w``: a
    matmul against a weight; attention's einsums have batch dimensions and
    stay float32) rounds both operands to ``dtype``. ``jnp.matmul`` looks
    ``dot_general`` up in ``jax._src.lax.lax`` at each trace, so the
    jitted pieces of a reference are traced again under it (the caches
    are dropped going in and coming out). Yields the list of rounded
    matmuls traced, which the caller checks is not empty."""
    import jax
    from jax._src.lax import lax as lax_module
    plain, traced = lax_module.dot_general, []

    def rounding(lhs, rhs, dimension_numbers, *args, **kwargs):
        if not dimension_numbers[1][0]:
            traced.append((lhs.shape, rhs.shape))
            lhs, rhs = _rounded(lhs, dtype), _rounded(rhs, dtype)
        return plain(lhs, rhs, dimension_numbers, *args, **kwargs)

    lax_module.dot_general = rounding
    jax.clear_caches()
    try:
        yield traced
    finally:
        lax_module.dot_general = plain
        jax.clear_caches()


def control(cell_name: str, seed: int, rig: dict) -> dict:
    import jax
    import run
    from kinds import train_job
    from lib import files, modelspec, traffic
    cell = files.load_cell(cell_name)
    cell["traffic_file"].update(rig.get("traffic_overrides", {}))
    run.enable_cache()
    run.device_gate(int(cell["chips"]), rig)
    arch, cfg = cell["arch"], cell["config_file"]
    engine, model = train_job.build_engine(cfg, arch, int(cell["chips"]),
                                           seed, rig)
    batch = traffic.train_batches(cell["traffic_file"], seed,
                                  int(cell["chips"]),
                                  model.config.vocab_size)[0]
    m = modelspec.reference_model(arch, model)
    master = engine.state["master"] or engine.state["params"]
    toks = train_job._put(engine, batch[:, :-1])
    tgts = train_job._put(engine, batch[:, 1:])
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_tail = arch.reference(master, toks, tgts, m,
                                            train_job.TAIL)
        with weight_matmuls_in(CONTROL) as traced:
            got_loss, got_tail = arch.reference(master, toks, tgts, m,
                                                train_job.TAIL)
    assert traced, "the control rounded no matmul: it is the reference"
    err_max, err_rms = train_job.errors(got_tail, ref_tail)
    got = {"logits_err_max": err_max, "logits_err_rms": err_rms,
           "loss_err": abs(got_loss - ref_loss) / abs(ref_loss)}
    tol = {k: cfg["check"][k] for k in got}
    return {"cell": cell_name, "seed": seed, "control": CONTROL,
            "rounded_matmuls": len(traced), "got": got, "limits": tol,
            "correct": all(got[k] <= tol[k] for k in got),
            "device": jax.devices()[0].device_kind}


if __name__ == "__main__":
    if len(sys.argv) == 3:
        print(json.dumps(control(sys.argv[1], int(sys.argv[2]), {})),
              flush=True)
    else:
        for seed in sys.argv[2:]:
            subprocess.run([sys.executable, __file__, sys.argv[1], seed],
                           check=True)

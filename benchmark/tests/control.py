"""The control of ``correct``: the architecture's reference put in the
program's place with both operands of every matmul against a weight
rounded to a lower precision, compared with the float32 reference through
the functions ``kinds/train_job.py`` compares the program with
(``tail_numbers``, ``decide``). The reference knows nothing of it:
``weight_matmuls_in`` rounds from outside, so it serves every architecture
module as it is. The benchmark's own runs never run it.

    chiprun -- python3 benchmark/tests/control.py <cell> [--as <dtype>] <seed> [<seed> ...]

``--as float8_e4m3fn`` (the default, ``CONTROL``: one precision below the
configurations' bfloat16, per-tensor scaled) has to come out NOT correct.
``--as bfloat16`` is what a right program looks like through the check,
and has to come out correct: it shows that a check passes a right program
before that program exists. Where the architecture returns a mask, the
line also carries the errors over ALL positions (``all_positions``: the
reading without the mask). One JSON line a seed (a new process each: the
engine fills the chip). On the CPU ``tests/test_benchmark.py`` and
``tests/test_routed_check.py`` run it at the tiny widths.
"""

import argparse
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
    if jnp.finfo(dtype).maxexp == jnp.finfo(jnp.float32).maxexp:
        # bfloat16 has float32's range: rounded as a cast rounds it
        return t.astype(dtype).astype(jnp.float32)
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


def staged(cell_name: str, seed: int, rig: dict):
    """The engine of the cell built from the seed (never stepped), and
    what a reference is called with: (cell, its ``check`` keys in ``m``,
    the float32 master weights, tokens, targets)."""
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
    m = modelspec.reference_model(arch, model, cfg["check"])
    master = engine.state["master"] or engine.state["params"]
    return (cell, m, master, train_job._put(engine, batch[:, :-1]),
            train_job._put(engine, batch[:, 1:]))


def control(cell_name: str, seed: int, rig: dict,
            stand_in: str = CONTROL) -> dict:
    import time

    import jax
    from kinds import train_job
    cell, m, master, toks, tgts = staged(cell_name, seed, rig)
    arch, cfg = cell["arch"], cell["config_file"]
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        ref_loss, ref_tail, counted = train_job.reference_of(
            arch, master, toks, tgts, m)
        jax.block_until_ready(ref_tail)
        reference_s = time.perf_counter() - t0
        with weight_matmuls_in(stand_in) as traced:
            # the mask is the float32 reference's: the stand-in's own is
            # dropped, as the program's choices never reach the check
            got_loss, got_tail, _ = train_job.reference_of(
                arch, master, toks, tgts, m)
    assert traced, "the control rounded no matmul: it is the reference"
    got = train_job.tail_numbers(got_tail, ref_tail, counted)
    correct = train_job.decide(got, ref_loss, got_loss, cfg["check"])
    out = {"cell": cell_name, "seed": seed, "control": stand_in,
           "rounded_matmuls": len(traced), "got": got,
           "limits": {k: cfg["check"][limit]
                      for k, limit in train_job.LIMITS.items() if k in got},
           "correct": correct, "positions": list(tgts.shape),
           "reference_first_call_s": reference_s,
           "device": jax.devices()[0].device_kind}
    if counted is not None:
        out["all_positions"] = train_job.tail_numbers(got_tail, ref_tail,
                                                      None)
    return out


def cli(one, script: str, doc: str, default: str) -> None:
    """``<script> <cell> [--as <dtype>] <seed> [<seed> ...]``: one seed
    prints ``one(cell, seed, {}, dtype)`` as a JSON line; several run a new
    process each (the engine fills the chip)."""
    ap = argparse.ArgumentParser(description=doc.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--as", dest="stand_in", default=default,
                    choices=("bfloat16", CONTROL))
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args()
    if len(args.seeds) == 1:
        print(json.dumps(one(args.cell, args.seeds[0], {}, args.stand_in)),
              flush=True)
    else:
        for seed in args.seeds:
            subprocess.run([sys.executable, script, args.cell, "--as",
                            args.stand_in, str(seed)], check=True)


if __name__ == "__main__":
    cli(control, __file__, __doc__, CONTROL)

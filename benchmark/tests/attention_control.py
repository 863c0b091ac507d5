"""The control of ``correct`` for a layer the seeded weights hide: the
cell's model with its attention weights drawn larger, so that the softmax
is peaked and the layer counts in the tail logits, compared with the
architecture's float32 reference through the functions
``kinds/train_job.py`` compares the program with (``tail_numbers``,
``decide``) and held to the configuration's own ``check``; then the same
weights through the program with a PLANTED FAULT (the softmax scale
``head_dim ** -0.5`` in place of ``attention_multiplier``), which has to
come out NOT correct. The benchmark's own runs never run it.

    chiprun -- python3 benchmark/tests/attention_control.py <cell> <seed> [<seed> ...]

Why it exists (PR 34): at N(0, 0.02) weights q . k / 64 has a standard
deviation of 0.1, the softmax over 8192 keys is near uniform and
granite-4.0-h-micro's one attention layer adds 0.2% to the final hidden
state, so the cell's ``correct`` cannot see the flash kernels at a head of
64 with 4 : 1 grouped queries, nor the ``q * 0.125`` that carries the
1/64 scale. ``BOOST`` (powers of two, exact in bfloat16) draws ``wq`` and
``wk`` at 0.08 (scores of standard deviation 1.6 at the right scale, 13 at
the planted one) and ``wo`` at four times its seeded scale. No engine is
built: the weights are the model's own ``init`` from the seed, raised to
float32 (the reference's) and rounded back (the program's). One JSON line
a seed (a new process each); a line that is not ``ok`` exits 1.
"""

import dataclasses
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

BOOST = {"wq": 4.0, "wk": 4.0, "wo": 4.0}


def attention_control(cell_name: str, seed: int, rig: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import run
    from kinds import train_job
    from lib import files, modelspec, traffic
    cell = files.load_cell(cell_name)
    cell["traffic_file"].update(rig.get("traffic_overrides", {}))
    run.enable_cache()
    run.device_gate(int(cell["chips"]), rig)
    arch, cfg = cell["arch"], cell["config_file"]
    model = modelspec.build_model(cfg, arch, rig)
    c = model.config
    if c.attention_multiplier in (None, c.head_dim ** -0.5):
        raise SystemExit(f"{cell_name}: the model's softmax scale is the "
                         f"kernels' own; there is no fault to plant")
    m = modelspec.reference_model(arch, model, cfg["check"])
    batch = traffic.train_batches(cell["traffic_file"], seed,
                                  int(cell["chips"]), c.vocab_size)[0]
    tokens, targets = jnp.asarray(batch[:, :-1]), jnp.asarray(batch[:, 1:])
    bf16 = cfg["program"]["ds_config"].get("bf16", {}).get("enabled")
    compute = jnp.bfloat16 if bf16 else jnp.float32     # as the engine casts
    boost = rig.get("attention_boost", BOOST)   # the tiny widths need more

    @jax.jit
    def weights(key):
        boosted = jax.tree_util.tree_map_with_path(
            lambda path, w: w.astype(jnp.float32)
            * boost.get(getattr(path[-1], "key", None), 1.0),
            model.init(key))
        return boosted, jax.tree_util.tree_map(
            lambda w: w.astype(compute), boosted)

    master, params = weights(jax.random.PRNGKey(seed % (2 ** 31 - 1)))
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_tail, counted = train_job.reference_of(
            arch, master, tokens, targets, m)
    del master
    out = {"cell": cell_name, "seed": seed, "boost": boost,
           "limits": {k: cfg["check"][limit]
                      for k, limit in train_job.LIMITS.items()
                      if limit in cfg["check"]},
           "device": jax.devices()[0].device_kind}
    planted = type(model)(config=dataclasses.replace(
        c, attention_multiplier=c.head_dim ** -0.5))
    for name, program in (("program", model), ("planted_scale", planted)):
        tail = jax.jit(lambda p, t, f=program: f.apply(p, t)[
            :, -train_job.TAIL:])(params, tokens)
        loss = float(jax.jit(program.loss)(params, (tokens, targets)))
        got = train_job.tail_numbers(tail, ref_tail, counted)
        correct = train_job.decide(got, ref_loss, loss, cfg["check"])
        out[name] = {"got": got, "correct": correct}
    # a right program is seen as right and the planted fault as a fault
    out["ok"] = out["program"]["correct"] and not out["planted_scale"][
        "correct"]
    return out


if __name__ == "__main__":
    cell, *seeds = sys.argv[1:]
    if len(seeds) == 1:
        line = attention_control(cell, int(seeds[0]), {})
        print(json.dumps(line), flush=True)
        sys.exit(0 if line["ok"] else 1)
    for seed in seeds:
        subprocess.run([sys.executable, __file__, cell, seed], check=True)

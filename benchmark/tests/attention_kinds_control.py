"""The control of ``correct`` for the two kinds of attention layer of a
window-and-full stack (``architectures/mellum.py``): the cell's model at
weights drawn from the seed, compared with the architecture's float32
reference through the functions ``kinds/train_job.py`` compares the
program with (``tail_numbers``, ``decide``) and held to the configuration's
own ``check``; then the same weights through the program with each of
``planted`` (a window layer without its window, the full layer's
``attention_factor`` left out, the two kinds' rotary tables swapped), every
one of which has to come out NOT correct. The benchmark's own runs never
run it; ``tests/attention_control.py`` is the same for Granite's one kind.

    chiprun -- python3 benchmark/tests/attention_kinds_control.py <cell> <seed> [<seed> ...]

Why it exists (PR 38): ``correct`` compares a right program with the
reference and never shows that it WOULD see a wrong one. At this cell's
own size it does, with no help: the seeded query and key projections are
normal(0, 0.06) (the configuration's ``assumed``), a score has a standard
deviation of 8 and attention selects, so the layers make 60% of the
logits and ``BOOST`` is empty (read on the chip, one seed: the program
0.058 / 0.039 of limits 0.12 / 0.055, a missing ``attention_factor``
0.079 / 0.070, which the rms limit alone refuses, swapped tables 0.70 /
0.72, no window 0.84 / 0.77). The tiny widths of the CPU rig hide the
layers as Granite's did and are given a boost (powers of two, exact in
bfloat16) through the rig. No engine is built: the weights are the model's own ``init`` from the
seed, raised to float32 (the reference's) and rounded back (the
program's). A planted program is judged by its tail logits alone (its loss
is taken as the right program's, so that one loss is compiled). One JSON
line a seed (a new process each); a line that is not ``ok`` exits 1.
"""

import dataclasses
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

BOOST: dict = {}


def planted(c) -> dict:
    """{name: the configuration with one departure from the published
    attention} of a model whose ``layer_types`` hold both kinds."""
    rope = c.rope_parameters
    full, swa = rope["full_attention"], rope["sliding_attention"]
    return {
        "no_window": dataclasses.replace(c, sliding_window=c.max_seq_len),
        "no_attention_factor": dataclasses.replace(c, rope_parameters={
            **rope, "full_attention": {**full, "attention_factor": 1.0}}),
        "tables_swapped": dataclasses.replace(c, rope_parameters={
            "full_attention": swa, "sliding_attention": full}),
    }


def attention_kinds_control(cell_name: str, seed: int, rig: dict) -> dict:
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
    if not {"sliding_attention", "full_attention"} <= set(c.layer_types):
        raise SystemExit(f"{cell_name}: the model has not both kinds of "
                         f"attention layer; there is no fault to plant")
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
    loss = float(jax.jit(model.loss)(params, (tokens, targets)))
    programs = {"program": model,
                **{name: type(model)(config=faulty)
                   for name, faulty in planted(c).items()}}
    for name, program in programs.items():
        tail = jax.jit(lambda p, t, f=program: f.apply(p, t)[
            :, -train_job.TAIL:])(params, tokens)
        got = train_job.tail_numbers(tail, ref_tail, counted)
        correct = train_job.decide(got, ref_loss, loss, cfg["check"])
        out[name] = {"got": got, "correct": correct}
    # a right program is seen as right and every planted fault as a fault
    out["ok"] = out["program"]["correct"] and not any(
        out[name]["correct"] for name in programs if name != "program")
    return out


if __name__ == "__main__":
    cell, *seeds = sys.argv[1:]
    if len(seeds) == 1:
        line = attention_kinds_control(cell, int(seeds[0]), {})
        print(json.dumps(line), flush=True)
        sys.exit(0 if line["ok"] else 1)
    for seed in seeds:
        subprocess.run([sys.executable, __file__, cell, seed], check=True)

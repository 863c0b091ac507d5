"""The control of ``correct`` for what a Gated DeltaNet and gated attention
stack adds (``architectures/qwen3_next.py``): the cell's model at weights
drawn from the seed, compared with the architecture's float32 reference
through the functions ``kinds/train_job.py`` compares the program with
(``tail_numbers``, ``decide``) and held to the configuration's own
``check``; then the same weights through the program with each of
``FAULTS`` planted, every one of which has to come out NOT correct. The
benchmark's own runs never run it; ``tests/attention_kinds_control.py`` is
the same for Mellum's two kinds, whose frame this is.

    chiprun -- python3 benchmark/tests/gdn_control.py <cell> <seed> [<seed> ...]

Why it exists (PR 46): the tail logits cannot see a missing output gate, a
missing QK-norm or a wrong rotation if the seeded weights make the
attention layer's scores near level, nor a missing decay if every head
forgets at once. ``plant(model, fault)`` returns the model with ONE
departure from the published layer, made where the model calls out (its
``_attention``, its ``_mixers``, ``moe_ffn_held``), so the tier-1 test
(``tests/test_qwen3_next.py``) plants the same faults at the tiny widths.
No engine is built: the weights are the model's own ``init`` from the
seed, raised to float32 (the reference's) and rounded back (the
program's). A planted program is judged by its tail logits alone (its loss
is taken as the right program's, so that one loss is compiled). One JSON
line a seed (a new process each); a line that is not ``ok`` exits 1.
"""

import contextlib
import dataclasses
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

FAULTS = ("no_sigmoid_on_the_attention_gate", "no_qk_norm",
          "rotary_over_the_whole_head", "gate_a_head_is_zero",
          "shared_experts_gate_is_one")


@contextlib.contextmanager
def _patched(owner, name, value):
    real = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield real
    finally:
        setattr(owner, name, real)


def plant(model, fault: str):
    """A model of ``model``'s class and configuration with ``fault`` (one
    of ``FAULTS``) planted; the patches act while its layers are traced."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.moe import sharded_moe
    c = model.config
    if fault == "rotary_over_the_whole_head":
        return type(model)(config=dataclasses.replace(c, rotary_pct=1.0))
    faulty = type(model)(config=dataclasses.replace(c))
    if fault == "no_sigmoid_on_the_attention_gate":
        real = faulty._attention

        def attention(*a, **kw):
            with _patched(jax.nn, "sigmoid", lambda x: x):
                return real(*a, **kw)
        faulty._attention = attention
    elif fault == "no_qk_norm":
        real = faulty._attention

        def attention(*a, **kw):        # noqa: F811
            with _patched(faulty, "_norm", lambda x, scale, bias=None: x):
                return real(*a, **kw)
        faulty._attention = attention
    elif fault == "gate_a_head_is_zero":
        real = faulty._mixers

        def mixers(*a, **kw):
            attn, scan, conv = real(*a, **kw)
            return attn, (lambda q, k, v, g, beta, **kw: scan(
                q, k, v, jnp.zeros_like(g), beta, **kw)), conv
        faulty._mixers = mixers
    elif fault == "shared_experts_gate_is_one":
        real = faulty._one_layer
        held = sharded_moe.moe_ffn_held

        def one_layer(*a, **kw):
            with _patched(sharded_moe, "moe_ffn_held",
                          lambda *x, shared_gate=None, **y: held(*x, **y)):
                return real(*a, **kw)
        faulty._one_layer = one_layer
    else:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")
    return faulty


def gdn_control(cell_name: str, seed: int, rig: dict) -> dict:
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
    m = modelspec.reference_model(arch, model, cfg["check"])
    batch = traffic.train_batches(cell["traffic_file"], seed,
                                  int(cell["chips"]), c.vocab_size)[0]
    tokens, targets = jnp.asarray(batch[:, :-1]), jnp.asarray(batch[:, 1:])
    bf16 = cfg["program"]["ds_config"].get("bf16", {}).get("enabled")
    compute = jnp.bfloat16 if bf16 else jnp.float32     # as the engine casts

    @jax.jit
    def weights(key):
        master = jax.tree_util.tree_map(
            lambda w: w.astype(jnp.float32), model.init(key))
        return master, jax.tree_util.tree_map(
            lambda w: w.astype(compute), master)

    master, params = weights(jax.random.PRNGKey(seed % (2 ** 31 - 1)))
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_tail, counted = train_job.reference_of(
            arch, master, tokens, targets, m)
    del master
    out = {"cell": cell_name, "seed": seed,
           "limits": {k: cfg["check"][limit]
                      for k, limit in train_job.LIMITS.items()
                      if limit in cfg["check"]},
           "device": jax.devices()[0].device_kind}
    loss = float(jax.jit(model.loss)(params, (tokens, targets)))
    programs = {"program": model,
                **{fault: plant(model, fault) for fault in FAULTS}}
    for name, program in programs.items():
        tail = jax.jit(lambda p, t, f=program: f.apply(p, t)[
            :, -train_job.TAIL:])(params, tokens)
        got = train_job.tail_numbers(tail, ref_tail, counted)
        correct = train_job.decide(got, ref_loss, loss, cfg["check"])
        out[name] = {"got": got, "correct": correct}
    # a right program is seen as right and every planted fault as a fault
    out["ok"] = out["program"]["correct"] and not any(
        out[name]["correct"] for name in FAULTS)
    return out


if __name__ == "__main__":
    cell, *seeds = sys.argv[1:]
    if len(seeds) == 1:
        line = gdn_control(cell, int(seeds[0]), {})
        print(json.dumps(line), flush=True)
        sys.exit(0 if line["ok"] else 1)
    for seed in seeds:
        subprocess.run([sys.executable, __file__, cell, seed], check=False)

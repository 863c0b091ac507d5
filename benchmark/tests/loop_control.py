"""The control of ``correct`` for a looped stack (``architectures/ouro.py``):
the cell's model at weights drawn from the seed, compared with the
architecture's float32 reference through the functions
``kinds/train_job.py`` compares the program with (``tail_numbers``,
``decide``) and held to the configuration's own ``check``; then the same
weights through the program with each of ``planted``, every one of which
has to come out NOT correct. The benchmark's own runs never run it;
``tests/attention_kinds_control.py`` is the same for Mellum's attention.

    chiprun -- python3 benchmark/tests/loop_control.py <cell> <seed> [<seed> ...]

Why it exists (PR 42): the tail logits the check compares are pass ``T``'s,
so they see the looped stack (a missing pass, a missing output norm, the
wrong state handed on) and nothing of exits 1 to ``T - 1``, the gate or
the entropy term. Only ``loss_err`` sees those, and a mean over thousands
of positions moves little: the configuration's ``loss_err`` limit is set
under what the two faults that leave the logits alone read here.

``planted``: ``passes_3`` (``total_ut_steps`` less one), ``no_output_norms``
(a layer without the two norms on its sublayers' outputs),
``unnormed_carry`` (the state BEFORE the final norm enters the next pass),
``uniform_exit`` (``p_t = 1 / T`` in place of the gate's distribution),
``beta_0`` (no entropy term). Each is judged by its own tail logits and its
own loss. No engine is built: the weights are the model's own ``init`` from
the seed, raised to float32 (the reference's) and rounded back (the
program's); the loss is the model's ``loss``, which is what the engine's
first step reports. One JSON line a seed (a new process each); a line that
is not ``ok`` exits 1.
"""

import dataclasses
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def planted(model) -> dict:
    """{name: a model with one departure from the looped stack}."""
    import jax
    import jax.numpy as jnp
    family, c = type(model), model.config

    class NoOutputNorms(family):
        def _layer(self, p, x, attn_fn, positions):
            h = self._norm(x, p["ln1_scale"])
            q, k, v = self._qkv(p, h, positions)
            x = x + self._attn_out(p, attn_fn(q, k, v, causal=True))
            return x + self._mlp(p, self._norm(x, p["ln2_scale"]))[0]

    class UnnormedCarry(family):
        def _exit_states(self, params, tokens, *, attn_fn=None, **kw):
            from deepspeed_tpu.ops import layers as L
            if attn_fn is None and self.config.attn_impl == "flash":
                from deepspeed_tpu.ops.pallas.flash_attention import \
                    flash_attention as attn_fn
            attn_fn = attn_fn or L.dot_product_attention
            x, exits = self.embed(params, tokens), []
            for _ in range(self.config.total_ut_steps):
                x, _ = jax.lax.scan(
                    lambda x, p: (self._layer(p, x, attn_fn, None), None),
                    x, params["layers"])
                exits.append(self._norm(x, params["final_norm"]["scale"]))
            return jnp.stack(exits)

    class UniformExit(family):
        def _exit_log_probs(self, params, exits):
            t = exits.shape[0]
            return jnp.full(exits.shape[:3], -jnp.log(float(t)), jnp.float32)

    return {
        "passes_3": family(config=dataclasses.replace(
            c, total_ut_steps=c.total_ut_steps - 1)),
        "no_output_norms": NoOutputNorms(config=c),
        "unnormed_carry": UnnormedCarry(config=c),
        "uniform_exit": UniformExit(config=c),
        "beta_0": family(config=dataclasses.replace(
            c, exit_entropy_beta=0.0)),
    }


# the faults that leave pass T's logits alone: only the loss sees them
LOSS_ONLY = ("uniform_exit", "beta_0")


def loop_control(cell_name: str, seed: int, rig: dict) -> dict:
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
    if getattr(c, "total_ut_steps", 1) < 2:
        raise SystemExit(f"{cell_name}: the model does not loop; there is "
                         f"no fault to plant")
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
    programs = {"program": model, **planted(model)}
    for name, program in programs.items():
        if name in LOSS_ONLY:
            got = dict(out["program"]["got"])
            del got["loss_err"]
        else:
            tail = jax.jit(lambda p, t, f=program: f.apply(p, t)[
                :, -train_job.TAIL:])(params, tokens)
            got = train_job.tail_numbers(tail, ref_tail, counted)
            del tail
        loss = float(jax.jit(program.loss)(params, (tokens, targets)))
        correct = train_job.decide(got, ref_loss, loss, cfg["check"])
        out[name] = {"got": got, "loss": loss, "correct": correct}
    out["ref_loss"] = ref_loss
    # a right program is seen as right and every planted fault as a fault
    out["ok"] = out["program"]["correct"] and not any(
        out[name]["correct"] for name in programs if name != "program")
    return out


if __name__ == "__main__":
    cell, *seeds = sys.argv[1:]
    if len(seeds) == 1:
        line = loop_control(cell, int(seeds[0]), {})
        print(json.dumps(line), flush=True)
        sys.exit(0 if line["ok"] else 1)
    for seed in seeds:
        subprocess.run([sys.executable, __file__, cell, seed], check=True)

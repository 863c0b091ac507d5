"""The control of ``correct`` for what a window-and-full stack at two
attention widths, gated a head, adds (``architectures/laguna.py``): the
cell's model at weights drawn from the seed, compared with the
architecture's float32 reference through the functions
``kinds/train_job.py`` compares the program with (``tail_numbers``,
``decide``) and held to the configuration's own ``check``; then the same
weights through the program with each of ``FAULTS`` planted, every one of
which has to come out NOT correct. The benchmark's own runs never run it;
``tests/lfm_control.py`` is the same for LFM2, whose frame this is.

    chiprun -- python3 benchmark/tests/laguna_control.py <cell> [key=value ...] <seed> [<seed> ...]

``plant(model, params, fault)`` returns (model, params) with ONE departure
from the equations: a switch of the model's configuration (a rotary
section, the scaling factor), the one call a gate makes out
(``jax.nn.sigmoid`` inside ``_attention``), or weights the program is
handed and the reference is not (a gate's projection at 0, the shared
expert taken away, a window layer's last third of heads cut off from the
output projection). The tier-1 test (``tests/test_laguna.py``) plants the
same faults at the tiny widths. A ``key=value`` overrides one of the
configuration's ``model_overrides``. No engine is built: the weights are
the model's own ``init`` from the seed, raised to float32 (the
reference's) and rounded back (the program's). A planted program is judged
by its tail logits alone. One JSON line a seed (a new process each); a
line that is not ``ok`` exits 1.
"""

import dataclasses
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE),
                HERE]
from lfm_control import _patched, _value  # noqa: E402  (the frame's own)

FAULTS = ("gate_left_out", "gate_taken_as_a_half",
          "full_layer_rotates_the_whole_head",
          "window_layer_uses_the_full_layers_table",
          "scaling_factor_left_out", "shared_expert_left_out",
          "window_layer_at_the_full_layers_heads")


def _edited(params, edit):
    """``params`` with ``edit(keys, leaf)`` in the place of every leaf."""
    import jax
    return jax.tree_util.tree_map_with_path(
        lambda path, w: edit([getattr(k, "key", None) for k in path], w),
        params)


def plant(model, params, fault: str):
    """(a model of ``model``'s class, the weights it is run on) with
    ``fault`` (one of ``FAULTS``) planted; a patch acts while the model's
    layers are traced."""
    import jax
    import jax.numpy as jnp
    c = model.config
    rope = c.rope_parameters
    full = rope["full_attention"]
    if fault == "full_layer_rotates_the_whole_head":
        return type(model)(config=dataclasses.replace(c, rope_parameters={
            **rope, "full_attention": {
                **full, "partial_rotary_factor": 1}})), params
    if fault == "window_layer_uses_the_full_layers_table":
        return type(model)(config=dataclasses.replace(c, rope_parameters={
            **rope, "sliding_attention": full})), params
    if fault == "scaling_factor_left_out":
        return type(model)(config=dataclasses.replace(
            c, routed_scaling_factor=1.0)), params
    if fault == "gate_left_out":
        faulty = type(model)(config=dataclasses.replace(c))
        real = faulty._attention

        def attention(*a, **kw):
            with _patched(jax.nn, "sigmoid", jnp.ones_like):
                return real(*a, **kw)
        faulty._attention = attention
        return faulty, params
    if fault == "gate_taken_as_a_half":
        return model, _edited(params, lambda keys, w: (
            jnp.zeros_like(w) if keys[-1] == "wg" else w))
    if fault == "shared_expert_left_out":
        return model, _edited(params, lambda keys, w: (
            jnp.zeros_like(w) if keys[-2:] == ["shared", "w_down"] else w))
    if fault == "window_layer_at_the_full_layers_heads":
        heads = c.kind_heads
        keep = heads["full_attention"] * c.head_dim

        def cut(keys, w):
            if keys[-2:] == ["swa", "wo"]:
                return w.at[..., keep:, :].set(0)
            return w
        assert heads["sliding_attention"] > heads["full_attention"]
        return model, _edited(params, cut)
    raise ValueError(f"fault {fault!r}: one of {FAULTS}")


def laguna_control(cell_name: str, seed: int, rig: dict,
                   overrides: dict | None = None) -> dict:
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
    cfg["program"]["model_overrides"].update(overrides or {})
    model = modelspec.build_model(cfg, arch, rig)
    c = model.config
    m = modelspec.reference_model(arch, model, cfg["check"])
    batch = traffic.train_batches(cell["traffic_file"], seed,
                                  int(cell["chips"]), c.vocab_size)[0]
    tokens, targets = jnp.asarray(batch[:, :-1]), jnp.asarray(batch[:, 1:])
    bf16 = cfg["program"]["ds_config"].get("bf16", {}).get("enabled")
    compute = jnp.bfloat16 if bf16 else jnp.float32     # as the engine casts
    tail = train_job.TAIL

    master = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float32), model.init(key)))(
            jax.random.PRNGKey(seed % (2 ** 31 - 1)))
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_tail, counted = arch.reference(
            master, tokens, targets, m, tail)
    params = jax.tree_util.tree_map(lambda w: w.astype(compute), master)
    del master
    loss = float(jax.jit(model.loss)(params, (tokens, targets)))
    out = {"cell": cell_name, "seed": seed, "overrides": overrides or {},
           "limits": {k: cfg["check"][limit]
                      for k, limit in train_job.LIMITS.items()
                      if limit in cfg["check"]},
           "device": jax.devices()[0].device_kind}
    for name in ("program", *FAULTS):
        program, weights = (model, params) if name == "program" else plant(
            model, params, name)
        got_tail = jax.jit(lambda p, t, f=program: f.apply(p, t)[
            :, -tail:])(weights, tokens)
        got = train_job.tail_numbers(got_tail, ref_tail, counted)
        out[name] = {"got": got, "correct": train_job.decide(
            got, ref_loss, loss, cfg["check"])}
        del got_tail, weights
    # a right program is seen as right and every planted fault as a fault
    out["ok"] = (out["program"]["correct"]
                 and not any(out[name]["correct"] for name in FAULTS))
    return out


if __name__ == "__main__":
    cell, *rest = sys.argv[1:]
    sets = [a for a in rest if "=" in a]
    seeds = [a for a in rest if "=" not in a]
    if len(seeds) == 1:
        line = laguna_control(cell, int(seeds[0]), {}, {
            k: _value(v) for k, v in (a.split("=", 1) for a in sets)})
        print(json.dumps(line), flush=True)
        sys.exit(0 if line["ok"] else 1)
    for seed in seeds:
        subprocess.run([sys.executable, __file__, cell, *sets, seed],
                       check=False)

"""The control of ``correct`` for what a stack of rotated, direct-query
latent attention over routed experts beside two shared ones adds
(``architectures/deepseek_v3.py``): the cell's model at weights drawn from
the seed, compared with the architecture's float32 reference through the
functions ``kinds/train_job.py`` compares the program with
(``tail_numbers``, ``decide``) and held to the configuration's own
``check``; then the same weights through the program with each of
``FAULTS`` planted, every one of which has to come out NOT correct. The
benchmark's own runs never run it; ``tests/mhc_control.py`` is the same for
Xing4.0, whose frame this is.

    chiprun -- python3 benchmark/tests/kanana_control.py <cell> [key=value ...] <seed> [<seed> ...]

``plant(model, fault)`` returns the model with ONE departure from the
equations, made where the model calls out (``ops/layers.py``'s
``pairs_to_halves`` and ``apply_rotary`` inside ``_mla``, the query in
``_mla_query``, the shared experts' weights in ``_routed``, the router's
numbers in the config), so the tier-1 test (``tests/test_deepseek_v3.py``)
plants the same faults at the tiny widths. A ``key=value`` overrides one
of the configuration's ``model_overrides``. No engine is built: the
weights are the model's own ``init`` from the seed, raised to float32 (the
reference's) and rounded back (the program's). A planted program is judged
by its tail logits alone. The right program is also read at each of
``MARGINS`` of the reference's mask (``program_at_margins``: where the
flips that move its error lie). One JSON line a seed (a new process each);
a line that is not ``ok`` exits 1.
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

FAULTS = ("rotate_half_for_the_pairs", "k_pe_a_head_from_other_columns",
          "five_experts", "weights_not_renormalised", "scaling_left_out",
          "one_shared_expert", "a_query_norm")
MARGINS = (0.0, 0.005, 0.01, 0.015, 0.02, 0.03)


def plant(model, fault: str):
    """A model of ``model``'s class and configuration with ``fault`` (one
    of ``FAULTS``) planted; the patches act while its layers are traced."""
    import jax.numpy as jnp
    from deepspeed_tpu.ops import layers as L
    c = model.config
    replaced = {"five_experts": dict(moe_top_k=c.moe_top_k - 1),
                "weights_not_renormalised": dict(moe_norm_topk=False),
                "scaling_left_out": dict(routed_scaling_factor=1.0)}
    if fault in replaced:
        return type(model)(config=dataclasses.replace(c, **replaced[fault]))
    faulty = type(model)(config=dataclasses.replace(c))

    def wrapped(name, patch):
        real = getattr(faulty, name)

        def method(*a, **kw):
            with _patched(*patch):
                return real(*a, **kw)
        setattr(faulty, name, method)

    if fault == "rotate_half_for_the_pairs":
        wrapped("_mla", (L, "pairs_to_halves", lambda x: x))
    elif fault == "k_pe_a_head_from_other_columns":
        # the key's rotated part is the one call on ONE head: here every
        # head takes its own, the shared one's columns moved on by the head
        rotary = L.apply_rotary
        wrapped("_mla", (L, "apply_rotary", lambda x, cos, sin: rotary(
            x if x.shape[2] > 1 else jnp.concatenate(
                [jnp.roll(x, h, axis=-1) for h in range(c.num_heads)],
                axis=2), cos, sin)))
    elif fault == "one_shared_expert":
        routed, f = faulty._routed, c.moe_intermediate_size
        faulty._routed = lambda p, h: routed({**p, "shared": {
            "w_gate": p["shared"]["w_gate"][..., :f],
            "w_up": p["shared"]["w_up"][..., :f],
            "w_down": p["shared"]["w_down"][..., :f, :]}}, h)
    elif fault == "a_query_norm":
        query, qk = faulty._mla_query, c.qk_nope_head_dim + c.qk_rope_head_dim
        faulty._mla_query = lambda p, h: (lambda q: L.rms_norm(
            q.reshape(*q.shape[:-1], c.num_heads, qk),
            jnp.ones((qk,), q.dtype), c.norm_eps).reshape(q.shape))(
                query(p, h))
    else:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")
    return faulty


def kanana_control(cell_name: str, seed: int, rig: dict,
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
        hidden, least = arch._forward(master, tokens, m)
        ref_loss = float(arch.loss_of(hidden, master["lm_head"], targets))
        ref_tail = arch.logits_of(hidden[:, -tail:], master["lm_head"])
    counted = (least >= m["routing_margin"])[:, -tail:]
    params = jax.tree_util.tree_map(lambda w: w.astype(compute), master)
    del master
    loss = float(jax.jit(model.loss)(params, (tokens, targets)))
    out = {"cell": cell_name, "seed": seed, "overrides": overrides or {},
           "limits": {k: cfg["check"][limit]
                      for k, limit in train_job.LIMITS.items()
                      if limit in cfg["check"]},
           "device": jax.devices()[0].device_kind}
    for name, program in {"program": model, **{
            fault: plant(model, fault) for fault in FAULTS}}.items():
        got_tail = jax.jit(lambda p, t, f=program: f.apply(p, t)[
            :, -tail:])(params, tokens)
        got = train_job.tail_numbers(got_tail, ref_tail, counted)
        out[name] = {"got": got, "correct": train_job.decide(
            got, ref_loss, loss, cfg["check"])}
        if name == "program":
            # the right program at other margins of the reference's mask
            out["program_at_margins"] = {
                str(margin): train_job.tail_numbers(
                    got_tail, ref_tail, (least >= margin)[:, -tail:])
                for margin in MARGINS}
    # a right program is seen as right and every planted fault as a fault
    out["ok"] = (out["program"]["correct"]
                 and not any(out[name]["correct"] for name in FAULTS))
    return out


if __name__ == "__main__":
    cell, *rest = sys.argv[1:]
    sets = [a for a in rest if "=" in a]
    seeds = [a for a in rest if "=" not in a]
    if len(seeds) == 1:
        line = kanana_control(cell, int(seeds[0]), {}, {
            k: _value(v) for k, v in (a.split("=", 1) for a in sets)})
        print(json.dumps(line), flush=True)
        sys.exit(0 if line["ok"] else 1)
    for seed in seeds:
        subprocess.run([sys.executable, __file__, cell, *sets, seed],
                       check=False)

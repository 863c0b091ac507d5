"""The control of ``correct`` for what a stack of ONE-sublayer layers adds
(``architectures/nemotron_h.py``: Mamba-2 heads in groups with the gated
norm a group, LatentMoE with a non-gated ``relu(.)^2`` body beside a shared
expert, attention without positions): the cell's model at weights drawn
from the seed, compared with the architecture's float32 reference through
the functions ``kinds/train_job.py`` compares the program with
(``tail_numbers``, ``decide``) and held to the configuration's own
``check``; then the same weights through the program with each of
``FAULTS`` planted, every one of which (but ``NOT_AT_EVERY_SEED``'s, which
is reported beside them) has to come out NOT correct. The
benchmark's own runs never run it; ``tests/kanana_control.py`` is the same
for Kanana-2, whose frame this is.

    chiprun -- python3 benchmark/tests/nemotron_control.py <cell> [key=value ...] <seed> [<seed> ...]

``plant(model, fault)`` returns the model with ONE departure from the
equations, made where the model calls out (the router's numbers in the
config, ``moe.sharded_moe.moe_ffn_held`` as ``_routed`` calls it, the
config's ``mamba_shape``, the scan and the attention of ``_mixers``), so
the tier-1 test (``tests/test_nemotron_h.py``) plants the same faults at
the tiny widths. A fault that the seeded weights hide is judged at weights
drawn larger where it acts (``FAULTS``' value names a rule of ``BOOSTS``,
as ``tests/attention_control.py`` does for Granite's one attention layer):
the right program is read at those weights too and has to pass. A
``key=value`` overrides one of the configuration's ``model_overrides``. No
engine is built: the weights are the model's own ``init`` from the seed,
raised to float32 (the reference's) and rounded back (the program's). A
planted program is judged by its tail logits alone. The right program is
also read at each of ``MARGINS`` of the reference's mask
(``program_at_margins``: where the flips that move its error lie). One
JSON line a seed (a new process each); a line that is not ``ok`` exits 1.
"""

import contextlib
import dataclasses
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE),
                HERE]
from lfm_control import _patched, _value  # noqa: E402  (the frame's own)

# fault -> the rule of ``BOOSTS`` its weights are drawn by (None: the seed's)
FAULTS = {"twenty_one_experts": None, "scaling_left_out": None,
          "weights_not_renormalised": None, "relu_for_relu2": None,
          "a_silu_gated_body": None, "shared_expert_left_out": None,
          "group_0s_b_and_c_for_every_head": None,
          "norm_over_all_channels": "mamba",
          "softmax_scale_1_over_head_dim": "attention"}
MARGINS = (0.0, 0.005, 0.01, 0.02, 0.03, 0.05)


def _gate_of_the_last_group(w, c):
    """``w_in`` with the z columns of the last group of heads four times
    larger: its gate's mean of squares is then another than the first
    group's, and a norm over all channels another than a norm a group."""
    import jax.numpy as jnp
    inner = c.mamba_num_heads * c.mamba_head_dim
    first = inner - inner // c.n_groups
    cols = jnp.arange(w.shape[-1])
    return w * jnp.where((cols >= first) & (cols < inner), 4.0, 1.0)


# rule -> {leaf name: a factor, or a function of (leaf, config)}: powers of
# two, exact in bfloat16
BOOSTS = {
    None: {},
    # values and an output projection that count: at the seed's one
    # attention layer of eleven adds under 1% to the stream. The scores
    # stay as drawn (deviation 1.6): with W_q and W_k four times larger as
    # well (26) the RIGHT program reads 0.139 / 0.051 against its float32
    # self in bfloat16 (my chip run, PR 66, call 1)
    "attention": {"wv": 4.0, "wo": 8.0},
    # the gate of one group alone: with W_out four times larger as well the
    # RIGHT program reads up to 0.091 / 0.021 (call 2: the scan's bfloat16
    # rounding weighs four times as much in the stream)
    "mamba": {"w_in": _gate_of_the_last_group},
}
# planted and reported, and NOT demanded of ``ok``: a share of 8 of 512
# experts cannot show it at every seed. 21 experts for 22 moves this
# chip's routed part by 22 / 21 (the positions whose 22nd choice is held
# lie at the boundary, and the mask leaves them out): 0.042 to 0.111 /
# 0.018 to 0.022 at the seed's weights, NOT correct at 2 seeds of 4 (calls
# 1, 2 and 4); with the latent twice as large (the routed part four times) it
# reads 0.233 to 0.521 / 0.058 to 0.083 and the RIGHT program 0.069 to
# 0.123 / 0.017 to 0.025, refused 2 of 3 by the max (call 3): the routed
# branch's bfloat16 rounding grows with the fault. tests/test_nemotron_h.py
# holds it at the tiny widths, where float32 shows it
NOT_AT_EVERY_SEED = ("twenty_one_experts",)


def plant(model, fault: str):
    """A model of ``model``'s class and configuration with ``fault`` (one
    of ``FAULTS``) planted; the patches act while its layers are traced."""
    import jax.numpy as jnp
    from deepspeed_tpu.moe import sharded_moe
    from deepspeed_tpu.ops.pallas import _common
    c = model.config
    replaced = {"twenty_one_experts": dict(moe_top_k=c.moe_top_k - 1),
                "weights_not_renormalised": dict(moe_norm_topk=False),
                "scaling_left_out": dict(routed_scaling_factor=1.0)}
    if fault in replaced:
        return type(model)(config=dataclasses.replace(c, **replaced[fault]))
    faulty = type(model)(config=dataclasses.replace(c))

    def routed_with(change=lambda experts, shared, kw: (experts, shared, kw),
                    patch=None):
        """``_routed`` with its ``moe_ffn_held`` handed ``change``'s
        (experts, shared, keywords), under ``patch``; the kernels are
        traced anew on both sides of it (``_bind`` keeps one trace a
        shape)."""
        real, held = faulty._routed, sharded_moe.moe_ffn_held

        def call(x, router, bias, experts, shared, **kw):
            experts, shared, kw = change(experts, shared, kw)
            return held(x, router, bias, experts, shared, **kw)

        def method(p, h):
            _common._TRACED.clear()
            try:
                with contextlib.ExitStack() as stack:
                    stack.enter_context(
                        _patched(sharded_moe, "moe_ffn_held", call))
                    if patch is not None:
                        stack.enter_context(_patched(*patch))
                    return real(p, h)
            finally:
                _common._TRACED.clear()
        faulty._routed = method

    def mixers_with(change):
        real = faulty._mixers
        faulty._mixers = lambda attn_fn, act_sharding: change(
            *real(attn_fn, act_sharding))

    if fault == "relu_for_relu2":
        routed_with(patch=(jnp, "square", lambda x: x))
    elif fault == "a_silu_gated_body":
        # silu(u W1) * (u W1) for relu(u W1)^2, in the experts and the
        # shared expert alike
        gate = lambda p: {**p, "w_gate": p["w_up"]}  # noqa: E731
        routed_with(lambda experts, shared, kw: (
            gate(experts), gate(shared), {**kw, "body": "swiglu"}))
    elif fault == "shared_expert_left_out":
        routed_with(lambda experts, shared, kw: (experts, None, kw))
    elif fault == "norm_over_all_channels":
        shape = faulty.config.mamba_shape
        faulty.config.mamba_shape = lambda: shape()._replace(norm_groups=1)
    elif fault == "group_0s_b_and_c_for_every_head":
        first = lambda v: jnp.broadcast_to(v[:, :, :1], v.shape)  # noqa: E731
        mixers_with(lambda attn, ssd, conv: (
            attn, lambda x, dt, A, B, C, **kw: ssd(
                x, dt, A, first(B), first(C), **kw), conv))
    elif fault == "softmax_scale_1_over_head_dim":
        mixers_with(lambda attn, ssd, conv: (
            lambda q, k, v, **kw: attn(
                q * jnp.asarray(c.head_dim ** -0.5, q.dtype), k, v, **kw),
            ssd, conv))
    else:
        raise ValueError(f"fault {fault!r}: one of {sorted(FAULTS)}")
    return faulty


def boosted(params, c, rule):
    """``params`` with the leaves ``BOOSTS[rule]`` names drawn larger."""
    import jax

    def one(path, w):
        by = BOOSTS[rule].get(getattr(path[-1], "key", None), 1.0)
        return by(w, c) if callable(by) else w * by
    return jax.tree_util.tree_map_with_path(one, params)


def nemotron_control(cell_name: str, seed: int, rig: dict,
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
    out = {"cell": cell_name, "seed": seed, "overrides": overrides or {},
           "limits": {k: cfg["check"][limit]
                      for k, limit in train_job.LIMITS.items()
                      if limit in cfg["check"]},
           "device": jax.devices()[0].device_kind}
    rules = rig.get("rules", sorted(set(FAULTS.values()), key=str))
    for rule in rules:
        master = jax.jit(lambda key, rule=rule: boosted(
            jax.tree_util.tree_map(lambda w: w.astype(jnp.float32),
                                   model.init(key)), c, rule))(
            jax.random.PRNGKey(seed % (2 ** 31 - 1)))
        with jax.default_matmul_precision("highest"):
            hidden, least = arch._forward(master, tokens, m)
            ref_loss = float(arch.loss_of(hidden, master["lm_head"], targets))
            ref_tail = arch.logits_of(hidden[:, -tail:], master["lm_head"])
        del hidden
        counted = (least >= m["routing_margin"])[:, -tail:]
        params = jax.tree_util.tree_map(lambda w: w.astype(compute), master)
        del master
        loss = float(jax.jit(model.loss)(params, (tokens, targets)))
        right = "program" if rule is None else f"program_at_{rule}_weights"
        for name, program in {right: model, **{
                fault: plant(model, fault) for fault, by in FAULTS.items()
                if by == rule}}.items():
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
        del params
    # a right program is seen as right and every planted fault as a fault
    rights = [k for k in out if k.startswith("program") and "margins" not in k]
    out["ok"] = (all(out[k]["correct"] for k in rights)
                 and not any(out[name]["correct"] for name in FAULTS
                             if name in out
                             and name not in NOT_AT_EVERY_SEED))
    return out


if __name__ == "__main__":
    cell, *rest = sys.argv[1:]
    sets = [a for a in rest if "=" in a]
    seeds = [a for a in rest if "=" not in a]
    if len(seeds) == 1:
        line = nemotron_control(cell, int(seeds[0]), {}, {
            k: _value(v) for k, v in (a.split("=", 1) for a in sets)})
        print(json.dumps(line), flush=True)
        sys.exit(0 if line["ok"] else 1)
    for seed in seeds:
        subprocess.run([sys.executable, __file__, cell, *sets, seed],
                       check=False)

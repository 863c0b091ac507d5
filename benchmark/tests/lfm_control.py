"""The control of ``correct`` for what a gated short-convolution stack with
bias-corrected sigmoid routing adds (``architectures/lfm2_moe.py``): the
cell's model at weights drawn from the seed, compared with the
architecture's float32 reference through the functions
``kinds/train_job.py`` compares the program with (``tail_numbers``,
``decide``) and held to the configuration's own ``check``; then the same
weights through the program with each of ``FAULTS`` planted, every one of
which has to come out NOT correct. The benchmark's own runs never run it;
``tests/gdn_control.py`` is the same for Qwen3-Next, whose frame this is.

    chiprun -- python3 benchmark/tests/lfm_control.py <cell> [key=value ...] <seed> [<seed> ...]

``plant(model, fault)`` returns the model with ONE departure from the
published layer, made where the model calls out (its ``_mixers``, its
``_attention``, its ``_routed``, its ``embed``), so the tier-1 test
(``tests/test_lfm2_moe.py``) plants the same faults at the tiny widths. A
``key=value`` overrides one of the configuration's ``model_overrides`` (how
``qk_norm_init`` was chosen: ``PERF.md`` section 6). No engine is built:
the weights are the model's own ``init`` from the seed, raised to float32
(the reference's) and rounded back (the program's). A planted program is
judged by its tail logits alone (its loss is taken as the right program's,
so that one loss is compiled). The line also carries ``margins``: the
right program's two logits errors and the share left out at other routing
margins than the configuration's (how ``routing_margin`` was chosen). One
JSON line a seed (a new process each); a line that is not ``ok`` exits 1.
"""

import contextlib
import dataclasses
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

FAULTS = ("silu_after_the_taps", "cg_left_out", "b_left_out",
          "taps_reversed", "no_qk_norm", "no_rotation",
          "selection_without_the_bias", "weights_not_renormalised",
          "final_norm_applied_first")
MARGINS = (0.0, 0.005, 0.01, 0.02, 0.03, 0.05)


@contextlib.contextmanager
def _patched(owner, name, value):
    real = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield real
    finally:
        setattr(owner, name, real)


def _departed_conv(fault: str):
    """The gated short convolution in ``jax.numpy`` with ``fault``."""
    import jax
    import jax.numpy as jnp

    def conv(bcx, w):
        n, c = w.shape
        s = bcx.shape[1]
        f32 = jnp.float32
        gate_b, gate_c, x = (bcx[..., r * c:(r + 1) * c].astype(f32)
                             for r in range(3))
        if fault == "taps_reversed":
            w = w[::-1]
        u = x if fault == "b_left_out" else gate_b * x
        u = jnp.pad(u, ((0, 0), (n - 1, 0), (0, 0)))
        y = sum(u[:, i:i + s] * w[i].astype(f32) for i in range(n))
        if fault == "silu_after_the_taps":
            y = jax.nn.silu(y)
        if fault != "cg_left_out":
            y = gate_c * y
        return y.astype(bcx.dtype)
    return conv


def plant(model, fault: str):
    """A model of ``model``'s class and configuration with ``fault`` (one
    of ``FAULTS``) planted; the patches act while its layers are traced."""
    import jax.numpy as jnp
    from deepspeed_tpu.moe import sharded_moe
    from deepspeed_tpu.ops import layers as L
    c = model.config
    if fault == "weights_not_renormalised":
        return type(model)(config=dataclasses.replace(c, moe_norm_topk=False))
    faulty = type(model)(config=dataclasses.replace(c))

    def wrapped(name, patch):
        real = getattr(faulty, name)

        def method(*a, **kw):
            with _patched(*patch):
                return real(*a, **kw)
        setattr(faulty, name, method)

    if fault in ("silu_after_the_taps", "cg_left_out", "b_left_out",
                 "taps_reversed"):
        real = faulty._mixers
        faulty._mixers = lambda *a, **kw: (real(*a, **kw)[0],
                                           _departed_conv(fault))
    elif fault == "no_qk_norm":
        wrapped("_attention", (L, "rms_norm", lambda x, scale, eps: x))
    elif fault == "no_rotation":
        wrapped("_attention", (L, "apply_rotary", lambda x, cos, sin: x))
    elif fault == "selection_without_the_bias":
        held = sharded_moe.moe_ffn_held
        wrapped("_routed", (
            sharded_moe, "moe_ffn_held",
            lambda h, w_router, bias, *a, **kw: held(
                h, w_router, jnp.zeros_like(bias), *a, **kw)))
    elif fault == "final_norm_applied_first":
        embed = faulty.embed
        faulty.embed = lambda params, tokens, positions=None: L.rms_norm(
            embed(params, tokens, positions), params["final_norm"]["scale"],
            c.norm_eps)
        faulty._norm = lambda x, scale, bias=None: x
    else:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")
    return faulty


def lfm_control(cell_name: str, seed: int, rig: dict,
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

    @jax.jit
    def weights(key):
        master = jax.tree_util.tree_map(
            lambda w: w.astype(jnp.float32), model.init(key))
        return master, jax.tree_util.tree_map(
            lambda w: w.astype(compute), master)

    master, params = weights(jax.random.PRNGKey(seed % (2 ** 31 - 1)))
    # ``arch.reference`` by its parts, so that the routing distances are
    # there for the other margins
    tail = train_job.TAIL
    with jax.default_matmul_precision("highest"):
        hidden, least = arch._forward(master, tokens, m)
        head = arch.head_of(master)
        ref_loss = float(arch.loss_of(hidden, head, targets))
        ref_tail = arch.logits_of(hidden[:, -tail:], head)
    least = least[:, -tail:]
    counted = least >= m["routing_margin"]
    del master, hidden
    out = {"cell": cell_name, "seed": seed, "overrides": overrides or {},
           "limits": {k: cfg["check"][limit]
                      for k, limit in train_job.LIMITS.items()
                      if limit in cfg["check"]},
           "device": jax.devices()[0].device_kind}
    loss = float(jax.jit(model.loss)(params, (tokens, targets)))
    programs = {"program": model,
                **{fault: plant(model, fault) for fault in FAULTS}}
    for name, program in programs.items():
        got_tail = jax.jit(lambda p, t, f=program: f.apply(p, t)[
            :, -tail:])(params, tokens)
        got = train_job.tail_numbers(got_tail, ref_tail, counted)
        correct = train_job.decide(got, ref_loss, loss, cfg["check"])
        out[name] = {"got": got, "correct": correct}
        if name == "program":
            out["margins"] = {
                str(margin): train_job.tail_numbers(got_tail, ref_tail,
                                                    least >= margin)
                for margin in MARGINS}
    # a right program is seen as right and every planted fault as a fault
    out["ok"] = out["program"]["correct"] and not any(
        out[name]["correct"] for name in FAULTS)
    return out


def _value(text: str):
    try:
        return json.loads(text)
    except ValueError:
        return text


if __name__ == "__main__":
    cell, *rest = sys.argv[1:]
    sets = [a for a in rest if "=" in a]
    seeds = [a for a in rest if "=" not in a]
    if len(seeds) == 1:
        line = lfm_control(cell, int(seeds[0]), {}, {
            k: _value(v) for k, v in (a.split("=", 1) for a in sets)})
        print(json.dumps(line), flush=True)
        sys.exit(0 if line["ok"] else 1)
    for seed in seeds:
        subprocess.run([sys.executable, __file__, cell, *sets, seed],
                       check=False)

"""The control of ``correct`` for what a stack of hyper-connected residual
streams round rotated latent attention adds (``architectures/xing4.py``):
the cell's model at weights drawn from the seed, compared with the
architecture's float32 reference through the functions
``kinds/train_job.py`` compares the program with (``tail_numbers``,
``decide``) and held to the configuration's own ``check``; then the same
weights through the program with each of ``FAULTS`` planted, every one of
which has to come out NOT correct. The benchmark's own runs never run it;
``tests/lfm_control.py`` is the same for LFM2, whose frame this is.

    chiprun -- python3 benchmark/tests/mhc_control.py <cell> [key=value ...] <seed> [<seed> ...]

``plant(model, fault)`` returns the model with ONE departure from the
equations, made where the model calls out (``ops/mhc.py``'s two passes and
its ``coefficients``, ``ops/layers.py``'s rotation and norm inside
``_mla``, the config's ``rope_scaling``), so the tier-1 test
(``tests/test_xing4_limits.py``) plants the same faults at the tiny
widths. ``clamp_dropped`` also plants a res logit of 100 (past the clamp's
30; ``exp`` of it overflows float32) in the weights of the right program
and of the faulty one alike: the right one clamps it. A ``key=value``
overrides one of the configuration's ``model_overrides``. No engine is
built: the weights are the model's own ``init`` from the seed, raised to
float32 (the reference's) and rounded back (the program's). A planted
program is judged by its tail logits alone. One JSON line a seed (a new
process each); a line that is not ``ok`` exits 1.
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

FAULTS = ("h_post_without_its_2", "one_sinkhorn_iteration",
          "input_dependent_term_dropped", "clamp_dropped",
          "h_res_transposed", "k_pe_not_rotated", "q_a_norm_left_out",
          "softmax_scale_without_m2")
PLANTED_LOGIT = 100.0


def with_planted_logit(params):
    """``params`` with ``PLANTED_LOGIT`` at ``b_res[0, 1]`` of every
    layer's first sublayer: past the clamp, so only a program that clamps
    survives it."""
    import jax

    def one(path, w):
        keys = [getattr(k, "key", None) for k in path]
        if keys[-2:] == ["hc1", "b"]:
            n = int((1 + w.shape[-1]) ** 0.5) - 1
            return w.at[..., 2 * n + 1].set(PLANTED_LOGIT)
        return w
    return jax.tree_util.tree_map_with_path(one, params)


def plant(model, fault: str):
    """A model of ``model``'s class and configuration with ``fault`` (one
    of ``FAULTS``) planted; the patches act while its layers are traced."""
    import jax.numpy as jnp
    from deepspeed_tpu.ops import layers as L
    from deepspeed_tpu.ops import mhc
    c = model.config
    if fault == "one_sinkhorn_iteration":
        return type(model)(config=dataclasses.replace(c, hc_sinkhorn_iters=1))
    if fault == "clamp_dropped":
        return type(model)(config=dataclasses.replace(
            c, mhc_h_res_clamp_min=-1e30, mhc_h_res_clamp_max=1e30))
    if fault == "softmax_scale_without_m2":
        return type(model)(config=dataclasses.replace(
            c, rope_scaling={**c.rope_scaling, "mscale": 0,
                             "mscale_all_dim": 0}))
    faulty = type(model)(config=dataclasses.replace(c))

    def wrapped(name, patch):
        real = getattr(faulty, name)

        def method(*a, **kw):
            with _patched(*patch):
                return real(*a, **kw)
        setattr(faulty, name, method)

    pre, post = mhc.mhc_pre, mhc.mhc_post
    if fault == "h_post_without_its_2":
        wrapped("_sublayer", (mhc, "mhc_post", lambda x, y, h_post, h_res:
                              post(x, y, 0.5 * h_post, h_res)))
    elif fault == "h_res_transposed":
        wrapped("_sublayer", (mhc, "mhc_post", lambda x, y, h_post, h_res:
                              post(x, y, h_post,
                                   jnp.swapaxes(h_res, -1, -2))))
    elif fault == "input_dependent_term_dropped":
        wrapped("_sublayer", (mhc, "mhc_pre", lambda x, phi, b, alpha, **kw:
                              pre(x, phi, b, jnp.zeros_like(alpha), **kw)))
    elif fault == "k_pe_not_rotated":
        # the key's rotated part is the one call on ONE head
        rotary = L.apply_rotary
        wrapped("_mla", (L, "apply_rotary", lambda x, cos, sin:
                         x if x.shape[2] == 1 else rotary(x, cos, sin)))
    elif fault == "q_a_norm_left_out":
        norm = L.rms_norm
        wrapped("_mla", (L, "rms_norm", lambda x, scale, eps:
                         x if x.shape[-1] == c.q_lora_rank
                         else norm(x, scale, eps)))
    else:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")
    return faulty


def mhc_control(cell_name: str, seed: int, rig: dict,
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

    @jax.jit
    def weights(key):
        master = jax.tree_util.tree_map(
            lambda w: w.astype(jnp.float32), model.init(key))
        return master, with_planted_logit(master)

    def judged(master, programs):
        """{name: the kind's numbers and decision} of ``programs`` on
        ``master`` rounded as the engine rounds, against the reference on
        ``master``."""
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_tail, counted = arch.reference(
                master, tokens, targets, m, tail)
        params = jax.tree_util.tree_map(lambda w: w.astype(compute), master)
        loss = float(jax.jit(model.loss)(params, (tokens, targets)))
        out = {}
        for name, program in programs.items():
            got_tail = jax.jit(lambda p, t, f=program: f.apply(p, t)[
                :, -tail:])(params, tokens)
            got = train_job.tail_numbers(got_tail, ref_tail, counted)
            out[name] = {"got": got, "correct": train_job.decide(
                got, ref_loss, loss, cfg["check"])}
        return out

    master, planted = weights(jax.random.PRNGKey(seed % (2 ** 31 - 1)))
    out = {"cell": cell_name, "seed": seed, "overrides": overrides or {},
           "limits": {k: cfg["check"][limit]
                      for k, limit in train_job.LIMITS.items()
                      if limit in cfg["check"]},
           "device": jax.devices()[0].device_kind}
    out.update(judged(master, {"program": model, **{
        fault: plant(model, fault) for fault in FAULTS
        if fault != "clamp_dropped"}}))
    del master
    clamped = judged(planted, {"program": model,
                               "clamp_dropped": plant(model, "clamp_dropped")})
    out["program_with_a_logit_past_the_clamp"] = clamped["program"]
    out["clamp_dropped"] = clamped["clamp_dropped"]
    # a right program is seen as right and every planted fault as a fault
    out["ok"] = (out["program"]["correct"]
                 and clamped["program"]["correct"]
                 and not any(out[name]["correct"] for name in FAULTS))
    return out


if __name__ == "__main__":
    cell, *rest = sys.argv[1:]
    sets = [a for a in rest if "=" in a]
    seeds = [a for a in rest if "=" not in a]
    if len(seeds) == 1:
        line = mhc_control(cell, int(seeds[0]), {}, {
            k: _value(v) for k, v in (a.split("=", 1) for a in sets)})
        print(json.dumps(line), flush=True)
        sys.exit(0 if line["ok"] else 1)
    for seed in seeds:
        subprocess.run([sys.executable, __file__, cell, *sets, seed],
                       check=False)

"""Traffic kind ``train_job``: build the training engine through
``ds.initialize``, check it against the plain reference, warm up, then
train for the window, every step ending in ``block_until_ready``.

From the program this takes only the system under test (the engine and
its model) and, in a traced run, the spans it mirrors into the profiler.
"""

from __future__ import annotations

import time

import numpy as np

from . import compilewatch, modelspec, reference, traffic as traffic_mod

TAIL = 256      # last positions of each sequence whose logits are compared


def build_engine(cfg_file: dict, n_chips: int, seed: int, rig):
    import deepspeed_tpu as ds
    model = modelspec.build_model(cfg_file, rig)
    prog = cfg_file["program"]
    ds_config = dict(prog["ds_config"])
    ds_config["train_batch_size"] = (
        int(prog["sequences_per_chip"]) * n_chips)
    ds_config["seed"] = int(seed % (2 ** 31 - 1))
    engine, _, _, _ = ds.initialize(model=model, config=ds_config)
    fsdp = engine.topology.sizes["fsdp"]
    if fsdp != n_chips:
        raise RuntimeError(f"mesh fsdp resolved to {fsdp}, the cell asks "
                           f"for {n_chips} chips")
    return engine, model


def _put(engine, x):
    """Host array [batch, ...] onto the engine's mesh, batch-sharded."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.device_put(x, NamedSharding(
        engine.mesh, PartitionSpec(engine.topology.batch_axes())))


def _program_tail_logits(engine, model, tokens):
    """Logits of the last TAIL positions from the PROGRAM's forward
    (its model code and flash kernel, bf16 compute params), bound to the
    mesh the way the engine binds its loss."""
    import jax
    attn_fn = None
    c = model.config
    if engine.mesh.size > 1 and getattr(c, "attn_impl", None) == "flash":
        from deepspeed_tpu.ops.pallas.flash_attention import \
            sharded_flash_attention
        attn_fn = sharded_flash_attention(
            engine.mesh, engine.topology.batch_axes(),
            window=c.sliding_window)

    @jax.jit
    def fwd(params, toks):
        return model.apply(params, toks, attn_fn=attn_fn)[:, -TAIL:]

    return fwd(engine.state["params"], _put(engine, tokens))


def agreement(engine, model, cfg_file: dict, batch: np.ndarray) -> dict:
    """Before the first step: the reference's loss and tail logits on the
    engine's own float32 master weights, and the program's tail logits.
    The engine's first-step loss is compared by the caller."""
    import jax
    m = modelspec.reference_model(cfg_file, model)
    tokens, targets = batch[:, :-1], batch[:, 1:]
    master = engine.state["master"] or engine.state["params"]
    toks_dev, tgts_dev = _put(engine, tokens), _put(engine, targets)
    with jax.default_matmul_precision("highest"):
        hidden = reference.final_hidden(master, toks_dev, m)
        ref_loss = float(reference.loss_of(hidden, master["lm_head"],
                                           tgts_dev))
        ref_tail = reference.logits_of(hidden[:, -TAIL:], master["lm_head"])
    del hidden
    got_tail = _program_tail_logits(engine, model, tokens)
    err_max, err_rms = reference.errors(got_tail, ref_tail)
    del got_tail, ref_tail
    return {"ref_loss": ref_loss, "logits_err_max": err_max,
            "logits_err_rms": err_rms}


def run(cell: dict, args, rig: dict, *, tracer, t_start: float) -> dict:
    cfg_file, tr = cell["config_file"], cell["traffic_file"]
    n_chips = int(cell["chips"])
    engine, model = build_engine(cfg_file, n_chips, args.seed, rig)
    vocab = model.config.vocab_size
    seq = int(tr["seq_len"])
    pool = traffic_mod.train_batches(tr, args.seed, n_chips, vocab)
    tokens_per_step = pool[0].shape[0] * seq

    def step(i):
        b = pool[i % len(pool)]
        loss = engine.train_batch((b[:, :-1], b[:, 1:]))
        loss.block_until_ready()
        return loss

    # ---- set-up: agreement with the reference, then warm-up ------------
    tol = cfg_file["check"]
    agree = agreement(engine, model, cfg_file, pool[0])
    first_loss = float(step(0))
    agree["first_step_loss"] = first_loss
    agree["loss_err"] = abs(first_loss - agree["ref_loss"]) / abs(
        agree["ref_loss"])
    agree_ok = (agree["logits_err_max"] <= tol["logits_err_max"]
                and agree["logits_err_rms"] <= tol["logits_err_rms"]
                and agree["loss_err"] <= tol["loss_err"])
    print(f"agreement: {agree} tolerances {tol} ok={agree_ok}", flush=True)
    for i in range(1, int(tr["warmup_steps"])):
        step(i)
    warm = int(tr["warmup_steps"])
    exe0 = compilewatch.executables()

    if tracer is not None:
        tracer.start()
    # ---- the window ----------------------------------------------------
    # ends with the first step that finishes at or after --seconds, so the
    # rate is all the tokens over all the time, with no step cut in two
    losses = []
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    n = 0
    while True:
        losses.append(step(warm + n))
        n += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= args.seconds:
            break
    if tracer is not None:
        tracer.stop()
    window_s = elapsed
    losses = [float(x) for x in losses]
    compiles_in_window = compilewatch.executables() - exe0

    finite = bool(np.all(np.isfinite(losses)))
    cyc = len(pool)
    if n >= 2 * cyc:
        first, last = np.mean(losses[:cyc]), np.mean(losses[-cyc:])
    else:
        half = max(1, n // 2)
        first, last = np.mean(losses[:half]), np.mean(losses[-half:])
    falling = bool(last < first) if n >= 2 else True
    print(f"train: steps={n} window_s={window_s:.3f} "
          f"step_ms_mean={1e3 * window_s / n:.2f} "
          f"loss first={first:.4f} last={last:.4f} finite={finite} "
          f"compiles_in_window={compiles_in_window}", flush=True)

    tokens_per_s = n * tokens_per_step / window_s
    return {
        "correct": bool(agree_ok and finite and falling),
        "attempted": n, "failed": 0 if finite else 1,
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "context": {"steps": n, "window_s": window_s,
                    "tokens_per_step": tokens_per_step, "seq_len": seq,
                    "tokens_per_s": tokens_per_s, "chips": n_chips,
                    "model": modelspec.reference_model(cfg_file, model),
                    "sequences": pool[0].shape[0]},
    }

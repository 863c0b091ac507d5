"""Traffic kind ``train_job``: build the training engine through
``ds.initialize``, check it against the plain reference, warm up, then
train for the window. An untraced run keeps the traffic's
``dispatch_ahead_seconds`` of steps sent ahead of the one it waits for
(``steps_ahead``), so the chip is fed while the host stands still; a
traced run waits for every step, which is what its host readers
(``host_gap_ms.train``, ``clock_bracket_us.train``) read.

From the program this takes only the system under test (the engine and
its model) and, in a traced run, the spans it mirrors into the profiler
and its own account of the set-up (``lib/telemetry.py``). The model
family's reference, widths and FLOP counts come from the cell's
architecture module (``cell["arch"]``). ``traced`` is what a traced run of
this kind adds beside its metrics: two ``breakdown`` lists and the
``setup:``, ``clock:`` and ``steptrace`` lines.
"""

from __future__ import annotations

import json
import time

import numpy as np

from lib import compilewatch, modelspec, telemetry, traffic as traffic_mod
from reducers import program

TAIL = 256      # last positions of each sequence whose logits are compared
MAX_AHEAD = 64  # steps in flight at most, whatever the traffic asks for
LEDGER_ENTRY = "compiled_step"      # the train step in the program's ledger


def build_engine(cfg_file: dict, arch, n_chips: int, seed: int, rig):
    import deepspeed_tpu as ds
    model = modelspec.build_model(cfg_file, arch, rig)
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


def errors(got, ref, counted=None) -> tuple[float, float]:
    """(max, rms) error of ``got`` against ``ref``, each relative to the
    reference's own scale: max|got-ref| / max|ref| and
    rms(got-ref) / rms(ref). With ``counted`` (boolean, the tails' shape
    without the vocabulary axis) both errors and both scales are taken
    over the counted positions only. Non-finite output is infinitely
    wrong wherever it is, and so is a mask that counts nothing."""
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    if not bool(jnp.all(jnp.isfinite(got))):
        return float("inf"), float("inf")
    if counted is None:
        return (float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref))),
                float(jnp.sqrt(jnp.mean((got - ref) ** 2))
                      / jnp.sqrt(jnp.mean(ref ** 2))))
    if not bool(jnp.any(counted)):
        return float("inf"), float("inf")
    keep = jnp.asarray(counted)[..., None]
    d = jnp.where(keep, got - ref, 0.0)
    ref = jnp.where(keep, ref, 0.0)
    # the number of counted positions cancels in the ratio of the means
    return (float(jnp.max(jnp.abs(d)) / jnp.max(jnp.abs(ref))),
            float(jnp.sqrt(jnp.sum(d ** 2)) / jnp.sqrt(jnp.sum(ref ** 2))))


def reference_of(arch, params, tokens, targets, m: dict):
    """``arch.reference`` as (loss, tail logits, counted): an architecture
    whose forward pass takes no discrete decision returns two values and
    every position counts (``counted`` None)."""
    loss, tail, *mask = arch.reference(params, tokens, targets, m, TAIL)
    return loss, tail, (mask[0] if mask else None)


def tail_numbers(got_tail, ref_tail, counted) -> dict:
    """What the tails give the decision: the two logits errors over the
    counted positions and, where the architecture returned a mask, the
    share of positions it left out and the number it counted."""
    err_max, err_rms = errors(got_tail, ref_tail, counted)
    out = {"logits_err_max": err_max, "logits_err_rms": err_rms}
    if counted is not None:
        n = int(np.sum(np.asarray(counted)))
        out.update(excluded_share=1.0 - n / counted.size,
                   positions_counted=n)
    return out


# number of the agreement -> the key of ``check`` that limits it
LIMITS = {"logits_err_max": "logits_err_max",
          "logits_err_rms": "logits_err_rms", "loss_err": "loss_err",
          "excluded_share": "excluded_share_max"}


def decide(numbers: dict, ref_loss: float, got_loss: float,
           check: dict) -> bool:
    """THE decision, for the program (``run``) and for whatever stands in
    its place (``tests/control.py``): adds ``loss_err`` to ``numbers``
    and holds every number of ``LIMITS`` that is there to its limit in
    the configuration's ``check``."""
    numbers["loss_err"] = abs(got_loss - ref_loss) / abs(ref_loss)
    return all(numbers[k] <= check[limit]
               for k, limit in LIMITS.items() if k in numbers)


def agreement(engine, model, arch, batch: np.ndarray, check: dict) -> dict:
    """Before the first step: the architecture's reference loss (as the
    engine defines it), tail logits and mask on the engine's own float32
    master weights, and the program's tail logits. The engine's
    first-step loss is compared by the caller."""
    import jax
    m = modelspec.reference_model(arch, model, check)
    tokens, targets = batch[:, :-1], batch[:, 1:]
    master = engine.state["master"] or engine.state["params"]
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_tail, counted = reference_of(
            arch, master, _put(engine, tokens), _put(engine, targets), m)
    got_tail = _program_tail_logits(engine, model, tokens)
    numbers = tail_numbers(got_tail, ref_tail, counted)
    del got_tail, ref_tail
    return {"ref_loss": ref_loss, **numbers}


def steps_ahead(traffic: dict, warm_step_s: float, traced: bool) -> int:
    """How many steps the window keeps sent beyond the one it waits for:
    the traffic's ``dispatch_ahead_seconds`` of steps at the warm-up's step
    time, at least 1 and at most ``MAX_AHEAD``; 0 (wait for each step) in
    a traced run and where the traffic has no such key."""
    ahead_s = float(traffic.get("dispatch_ahead_seconds", 0.0))
    if traced or ahead_s <= 0.0:
        return 0
    return int(min(MAX_AHEAD, max(1, -(-ahead_s // max(warm_step_s, 1e-3)))))


def run(cell: dict, args, rig: dict, *, tracer, t_start: float) -> dict:
    cfg_file, tr, arch = (cell["config_file"], cell["traffic_file"],
                          cell["arch"])
    n_chips = int(cell["chips"])
    pre_build_s = time.perf_counter() - t_start
    engine, model = build_engine(cfg_file, arch, n_chips, args.seed, rig)
    # the program's account of itself exists only in a traced run
    at_build = telemetry.program_state() if tracer is not None else None
    vocab = model.config.vocab_size
    seq = int(tr["seq_len"])
    pool = traffic_mod.train_batches(tr, args.seed, n_chips, vocab)
    tokens_per_step = pool[0].shape[0] * seq

    def send(i):
        b = pool[i % len(pool)]
        return engine.train_batch((b[:, :-1], b[:, 1:]))

    def step(i):
        loss = send(i)
        loss.block_until_ready()
        return loss

    # ---- set-up: agreement with the reference, then warm-up ------------
    tol = cfg_file["check"]
    agree = agreement(engine, model, arch, pool[0], tol)
    first_loss = float(step(0))
    agree["first_step_loss"] = first_loss
    agree_ok = decide(agree, agree["ref_loss"], first_loss, tol)
    print(f"agreement: {agree} tolerances {tol} ok={agree_ok}", flush=True)
    warm = int(tr["warmup_steps"])
    warm_step_s = float("inf")
    for i in range(1, warm):
        t_step = time.perf_counter()
        step(i)
        warm_step_s = min(warm_step_s, time.perf_counter() - t_step)
    ahead = steps_ahead(tr, warm_step_s, tracer is not None)
    exe0 = compilewatch.executables()

    if tracer is not None:
        tracer.start()
    # ---- the window ----------------------------------------------------
    # sends a step, waits for the one ``ahead`` steps before it, and reads
    # the clock; once --seconds are up it sends nothing more, waits for all
    # that was sent and reads the clock after that wait: the rate is all
    # the tokens over all the time, with no step cut in two and nothing
    # unfinished counted. ``ahead`` 0 waits for each step as it is sent.
    losses = []
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    done_at = []    # when each wait returned: a fed chip's own step times
    n = 0
    while True:
        losses.append(send(warm + n))
        n += 1
        if n - len(done_at) > ahead:
            losses[len(done_at)].block_until_ready()
            done_at.append(time.perf_counter())
        if time.perf_counter() - t0 >= args.seconds:
            break
    for loss in losses[len(done_at):]:
        loss.block_until_ready()
        done_at.append(time.perf_counter())
    window_s = done_at[-1] - t0
    if tracer is not None:
        tracer.stop()
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
    # the time between two waits' returns: where steps are sent ahead the
    # chip's own step, by the window's first and last quarter and the longest
    gaps = 1e3 * np.diff(done_at) if n >= 2 else np.zeros(1)
    q = max(1, len(gaps) // 4)
    print(f"train: steps={n} ahead={ahead} window_s={window_s:.3f} "
          f"step_ms_mean={1e3 * window_s / n:.2f} "
          f"p50_first_quarter={np.median(gaps[:q]):.2f} "
          f"p50_last_quarter={np.median(gaps[-q:]):.2f} "
          f"longest={np.max(gaps):.2f} "
          f"loss first={first:.4f} last={last:.4f} finite={finite} "
          f"compiles_in_window={compiles_in_window}", flush=True)

    tokens_per_s = n * tokens_per_step / window_s
    context = {"steps": n, "window_s": window_s,
               "tokens_per_step": tokens_per_step, "seq_len": seq,
               "tokens_per_s": tokens_per_s, "chips": n_chips, "arch": arch,
               "model": modelspec.reference_model(arch, model),
               "sequences": pool[0].shape[0], "setup_s": setup_s,
               "pre_build_s": pre_build_s}
    if tracer is not None:
        # nothing compiles inside the window (printed above), so the
        # program's account now is its account at the window's start
        context.update(program_at_build=at_build,
                       program=telemetry.program_state(),
                       step_rows=telemetry.step_rows(n),
                       ledger_entry=LEDGER_ENTRY,
                       **telemetry.export(str(tracer.dir), cell["name"]))
    return {
        "correct": bool(agree_ok and finite and falling),
        "attempted": n, "failed": 0 if finite else 1,
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "context": context,
    }


# -- lines of a traced run that are not metrics ------------------------------
def _pct(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def report_lines(ctx, metrics: dict) -> list[str]:
    """``metrics`` are read by the part of a name before its first dot, so
    a cell whose names end otherwise than ``.train`` is reported too."""
    out = []
    stem = {k.partition(".")[0]: v["value"] for k, v in metrics.items()}
    got = {k: stem[k] for k in ("setup_import_s", "setup_init_s",
                                "setup_compile_s") if k in stem}
    if len(got) == 3 and ctx.get("setup_s"):
        rest = ctx["setup_s"] - sum(got.values())
        by_phase = ctx.get("program", {}).get("compile_s", {})
        first = ctx.get("program", {}).get("spans", {}).get(
            "first_step", [0.0])[0]
        built = ctx.get("program_at_build", {}).get("compile_s", {})
        pre = ctx.get("pre_build_s", 0.0)
        rnd = lambda d: {k: round(v, 3) for k, v in d.items()}  # noqa: E731
        out.append(
            f"setup: setup_s={ctx['setup_s']:.3f} = import "
            f"{got['setup_import_s']:.3f} + init "
            f"{got['setup_init_s']:.3f} + compile "
            f"{got['setup_compile_s']:.3f} + the rest {rest:.3f}. "
            f"The rest: {pre - got['setup_import_s']:.3f} before "
            f"the engine is built and not the package's imports (jax's "
            f"import, the device runtime's start, the compile cache), and "
            f"{rest - pre + got['setup_import_s']:.3f}"
            f" after it (the agreement check's and the warm-up's run time, "
            f"tracing). first_step span {first:.3f}; compile seconds by "
            f"phase at the window {rnd(by_phase)}, when the engine was "
            f"built {rnd(built)}")
    br = ctx.get("clock_bracket")
    if br:
        us = lambda x: None if x is None else round(1e6 * x, 1)  # noqa: E731
        out.append(
            f"clock: device minus host between {us(br['lower'])} and "
            f"{us(br['upper'])} us over {br['steps']} steps, midpoint "
            f"{us(br['midpoint'])} us"
            + ("" if br["lower"] is not None else
               " (lower limit dropped: it contradicts the upper one, so "
               "the caller did not block on each step)"))
    rows = ctx.get("step_rows") or []
    if rows:
        keys = [k for k in rows[0] if k.endswith("_ms")
                and any(r[k] for r in rows)]
        series = {k: {"p5": _pct([r[k] for r in rows], 0.05),
                      "p50": _pct([r[k] for r in rows], 0.50),
                      "p95": _pct([r[k] for r in rows], 0.95),
                      "max": max(r[k] for r in rows)} for k in keys}
        out.append(f"steptrace over {len(rows)} steps (host clock, ms): "
                   + json.dumps(series))
        # the program's own split of a step beside the trace's (ROADMAP
        # queue 3 item 4 decides whether steptrace keeps these two)
        seen = {k: stem.get(k) for k in ("device_step_ms",
                                         "exposed_collective_ms")}
        out.append(
            f"steptrace device_compute_ms p50 "
            f"{series.get('device_compute_ms', {}).get('p50', 0.0)} "
            f"exposed_comm_ms p50 "
            f"{series.get('exposed_comm_ms', {}).get('p50', 0.0)} against "
            f"the trace's {seen}")
    return out


def traced(ctx: dict, metrics: dict, span_pattern: str) -> dict:
    """After the reducers have run on ``ctx``: the lists this kind adds to
    the line's ``breakdown`` and the lines it prints before the result."""
    return {"breakdown": {
                "device_scopes": program.device_scopes(ctx, 10),
                "idle_gaps_aligned": program.idle_gaps_aligned(
                    ctx, span_pattern, 10)},
            "lines": report_lines(ctx, metrics)}

"""Single-chip ZeRO-Infinity: layer-streamed parameters + optimizer.

The reference makes 7B-class models trainable on one device by swapping
parameters and optimizer state between GPU, pinned CPU memory and NVMe
(reference: runtime/zero/stage3.py:1926 optimizer-state swap,
runtime/swap_tensor/partitioned_param_swapper.py
AsyncPartitionedParameterSwapper, runtime/zero/offload_config.py). The
TPU-native equivalent keeps the whole training step COMPILED and lets
XLA's memory-space support do the swapping:

- the fp32 master copy of every transformer layer (plus Adam moments)
  lives in ``pinned_host`` memory on the TPU host — model size is bounded
  by host RAM, not HBM; under the **nvme tier**
  (``offload_optimizer.device="nvme"``) master+moments live on DISK
  instead and page per layer through the native AIO op into the C++ CPU
  Adam (one-layer read-ahead, the PipelinedOptimizerSwapper pattern), so
  model size is bounded by NVMe capacity;
- phase A streams the fp32 master per layer and casts on device
  (default), or — with ``offload_param.stream_dtype="compute"`` — reads
  a bf16 copy of the layer stacks that the optimizer phase refreshes,
  halving fwd/bwd H2D bytes at +2 bytes/param of pinned host RAM
  (measured net NEGATIVE at 7B on a v5e host near its pinned limit:
  the pressure cost exceeded the byte saving; see config.py);
- the forward pass is a ``lax.scan`` over the stacked ``[L, ...]`` layer
  leaves whose body explicitly ``device_put``s one layer's slice into
  HBM — XLA turns that into a per-layer H2D DMA pipelined against
  compute, so HBM holds ~one layer at a time (measured: 16 MB of compiled
  temp for a 1 GiB host-resident stack);
- the backward is a HAND-ROLLED reverse scan (``jax.vjp`` per layer with
  in-scan recompute) whose per-layer grads are written straight back to
  pinned_host as scan outputs. Autodiff-of-scan is deliberately avoided:
  its transposed accumulation materializes the full stacked grad buffer
  in HBM (measured: 1.16 GiB temp for the same stack);
- the optimizer is a second compiled scan that streams (grads, master,
  m, v) per layer through HBM, runs Adam on device, and writes the
  updated state back to pinned_host. Embedding/head/final-norm leaves are
  small and stay device-resident with the same Adam math.

Everything runs inside jit on the TPU host's PCIe — nothing round-trips
through the client process (which may be far from the chip).

Scope (documented limits, enforced at dispatch in ``initialize``):
single-replica (one chip per model instance — the multi-chip paths use
the sharded engine), decoder models built on models/transformer.py
DecoderLM, bf16 or fp32 compute (fp16 loss-scaling is a sharded-engine
feature), Adam/AdamW. Gradient accumulation runs the backward scan per
micro-batch with an in-scan add into a donated pinned_host grad stack,
so the master+moments stream — the dominant PCIe traffic — is paid once
per optimizer step, not once per micro-batch (grads accumulate in the
compute dtype, mirroring the reference's fp16 grad buffers).

On non-TPU backends the memory-kind annotations are skipped (single
memory space) but the identical streaming program runs, so CPU tests
exercise the exact scan/vjp structure that runs on hardware.
"""

from __future__ import annotations

import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from ..utils.logging import log_dist, logger
from ..utils.telemetry_probe import (NULL_CM as _NULLCM,
                                     active_telemetry as _tel)
from .config import DeepSpeedConfig
from .lr_schedules import build_schedule

PyTree = Any


def _is_streamable_module(module) -> bool:
    """Stacked-layer decoder contract: embed/block/_norm/_project_vocab
    plus params['layers'] leaves carrying a leading L dim."""
    return all(hasattr(module, a) for a in
               ("embed", "block", "_norm", "_project_vocab", "config"))


class StreamedZeroEngine:
    """ZeRO-3 + offload_param=cpu for models larger than HBM, one chip.

    API: a subset of DeepSpeedEngine — train_batch / eval_batch /
    host_memory_report / save_checkpoint / load_checkpoint / params.
    """

    def __init__(self, module, config: DeepSpeedConfig,
                 lr_scheduler=None, model_parameters=None):
        if not _is_streamable_module(module):
            raise ValueError(
                "param streaming needs a DecoderLM-style module "
                "(embed/block/_norm/_project_vocab)")
        self.module = module
        self.config = config
        self.model_config = module.config
        self._init_params = model_parameters

        tb, mb, ga = config.resolve_batch_sizes(1)
        if config.fp16.enabled:
            raise NotImplementedError(
                "param streaming supports bf16/fp32; fp16 loss scaling "
                "is a sharded-engine feature")
        self.train_batch_size_ = tb
        self.micro_batch_size_ = mb
        # ga>1 accumulates per-layer grads into a donated pinned_host
        # stack inside the backward scan (one extra H2D read of the grad
        # stack per micro-batch) while the master+moments stream — the
        # dominant PCIe traffic — runs ONCE per step (reference GAS
        # semantics: runtime/engine.py:2007)
        self.gradient_accumulation_steps_ = ga
        self.compute_dtype = (jnp.bfloat16 if config.bf16.enabled
                              else jnp.float32)
        self._mixed = config.bf16.enabled

        # --- optimizer hyperparameters (Adam/AdamW only) ---------------
        opt_cfg = config.optimizer
        name = (opt_cfg.type if opt_cfg else "adamw").lower().replace("_", "")
        if name not in ("adam", "adamw", "fusedadam", "fusedadamw",
                        "cpuadam", "deepspeedcpuadam"):
            raise NotImplementedError(
                f"param streaming implements Adam/AdamW (got {name!r})")
        p = dict(opt_cfg.params) if opt_cfg else {}
        self._b1, self._b2 = p.get("betas", (0.9, 0.999))
        self._eps = p.get("eps", 1e-8)
        self._wd = p.get("weight_decay", 0.0)
        # reference FusedAdam defaults to decoupled (adamw-style) decay
        self._adamw_mode = bool(p.get("adam_w_mode", True)) \
            or name in ("adamw", "fusedadamw")
        if not self._adamw_mode and self._wd:
            raise NotImplementedError(
                "param streaming implements decoupled (adamw-style) "
                "weight decay only; adam_w_mode=false with weight_decay "
                "would need pre-moment L2 folding")
        sched_cfg = config.scheduler
        self.lr_schedule = (lr_scheduler if callable(lr_scheduler)
                            else build_schedule(
                                sched_cfg.type if sched_cfg else None,
                                sched_cfg.params if sched_cfg else {},
                                p.get("lr", 1e-3)))

        off = config.zero_optimization.offload_optimizer
        self._moment_dtype = jnp.dtype(off.moment_dtype)
        # nvme tier: master + moments page through NVMe per layer during
        # the optimizer phase; only the compute-dtype stream stack (+
        # transient grad stacks) occupy host RAM, so model size is
        # bounded by DISK, not host RAM (reference:
        # swap_tensor/partitioned_param_swapper.py,
        # stage3.py:1926 optimizer-state swap)
        self._nvme = off.device == "nvme"
        # separate compute-dtype stream stack? (nvme: always — master is
        # on disk; cpu tier: only when mixed AND configured "compute")
        self._stream_separate = self._nvme or (
            self._mixed and
            config.zero_optimization.offload_param.stream_dtype
            == "compute")
        if self._nvme:
            import os
            # Swap files are scratch (checkpoints are self-contained):
            # per-engine subdir + cleanup via ops.aio.engine_scratch_dir
            from ..ops.aio import engine_scratch_dir
            base = off.nvme_path or os.path.join(os.getcwd(), "ds_nvme_swap")
            self._nvme_dir, self._nvme_cleanup = engine_scratch_dir(base)
            from ..ops.aio import get_aio_handle
            self._aio = get_aio_handle(config.aio)
            from ..ops.cpu_optimizers import DeepSpeedCPUAdam
            self._cpu_opt = DeepSpeedCPUAdam(
                lr=p.get("lr", 1e-3), betas=(self._b1, self._b2),
                eps=self._eps, weight_decay=self._wd,
                adamw_mode=self._adamw_mode)
            self._have_moments = False
            self._last_nvme_io = {"read": 0, "written": 0}
        dev = jax.devices()[0]
        on_tpu = jax.default_backend() == "tpu"
        self._dev_sh = SingleDeviceSharding(dev)
        self._host_sh = (SingleDeviceSharding(dev, memory_kind="pinned_host")
                         if on_tpu else self._dev_sh)

        self._init_state()
        self._phase_a = None
        self._phase_a_acc = None
        self._phase_b = None
        self._phase_b_dev = None
        self._eval_jit = None
        self.global_steps = 0
        self.global_samples = 0
        self.skipped_steps = 0
        self._last_metrics = None
        if config.telemetry.enabled or config.wall_clock_breakdown:
            from ..utils.telemetry_probe import activate
            activate(config.telemetry)
        n = self.model_config.num_params()
        cdt_size = jnp.dtype(self.compute_dtype).itemsize
        if self._nvme:
            state_gib = (4 + 2 * self._moment_dtype.itemsize) \
                * self._n_layer_params / 2 ** 30
            log_dist(f"StreamedZeroEngine: {n/1e9:.2f}B params, "
                     f"master+moments on NVMe ({state_gib:.1f} GiB at "
                     f"{self._nvme_dir}), {jnp.dtype(self.compute_dtype).name} "
                     f"stream stack in pinned_host "
                     f"({cdt_size * self._n_layer_params / 2**30:.1f} GiB)")
        else:
            state_gib = (4 + (cdt_size if self._stream_separate else 0)
                         + 2 * self._moment_dtype.itemsize) \
                * self._n_layer_params / 2 ** 30
            tiers = ("master+stream+moments" if self._stream_separate
                     else "master+moments")
            log_dist(f"StreamedZeroEngine: {n/1e9:.2f}B params, "
                     f"layers {tiers} in "
                     f"{'pinned_host' if on_tpu else 'device (cpu test rig)'} "
                     f"({state_gib:.1f} GiB host state, moments "
                     f"{self._moment_dtype.name}), "
                     f"dtype={jnp.dtype(self.compute_dtype).name}")

    # ------------------------------------------------------------------
    def _init_state(self):
        """fp32 master + zero moments, layer stacks in pinned_host.

        Init runs as one jit whose layer outputs go straight to host
        memory — device high-water is the full tree transiently, so this
        path supports models up to ~HBM at init while training supports
        ~host-RAM. (Per-leaf init jits would lift the init bound too;
        not needed for the 7B target.)
        """
        rng = jax.random.PRNGKey(self.config.seed)

        def init32(rng):
            params = self.module.init(rng)
            return jax.tree.map(lambda x: x.astype(jnp.float32), params)

        abstract = jax.eval_shape(init32, rng)
        # only rank>=3 stacked leaves (per-layer MATRICES — the O(L*D^2)
        # bytes) stream through pinned_host; per-layer vectors (norm
        # scales, biases: [L, D]) stay device-resident — the TPU host-DMA
        # emitter requires multi-sublane slices, and their total size is
        # negligible anyway. Partition is by leaf PATH so nested layer
        # trees (MoE expert stacks) split correctly.
        from ..checkpoint.universal import flatten_with_names
        named = flatten_with_names(abstract["layers"])
        self._layer_treedef = jax.tree.structure(abstract["layers"])
        self._layer_names = [n for n, _ in named]
        self._stream_names = sorted(
            n for n, l in named if l.ndim >= 3)
        stream = set(self._stream_names)
        small_names = [n for n in self._layer_names if n not in stream]

        def split_flat(layers_tree):
            flat = dict(flatten_with_names(layers_tree))
            return ({n: flat[n] for n in self._stream_names},
                    {n: flat[n] for n in small_names})

        self._split_flat = split_flat

        fp32_bytes = sum(int(np.prod(l.shape)) * 4
                         for _, l in flatten_with_names(abstract))
        if self._init_params is not None:
            # pretrained / resume weights become the fp32 master directly
            # instead of re-initializing from config.seed (reference
            # semantics: deepspeed.initialize(model_parameters=...) trains
            # the GIVEN weights; ADVICE r3 high finding)
            given = self._init_params
            try:
                g_abs = jax.eval_shape(lambda t: t, given)
                ok = (jax.tree.structure(g_abs)
                      == jax.tree.structure(abstract)
                      and all(a.shape == b.shape for a, b in zip(
                          jax.tree.leaves(g_abs),
                          jax.tree.leaves(abstract))))
            except (TypeError, ValueError):
                ok = False  # not an abstractifiable pytree of arrays
            if not ok:
                raise ValueError(
                    "model_parameters does not match module.init's tree "
                    "structure/shapes; param streaming cannot consume it")

            def put32(x, sh):
                if isinstance(x, jax.Array):
                    return jax.device_put(x.astype(jnp.float32), sh)
                return jax.device_put(np.asarray(x, np.float32), sh)

            big_in, small_in = split_flat(given["layers"])
            if self._nvme:
                # given weights go straight to disk as the fp32 master;
                # only the compute-dtype stream copy lands in pinned_host
                big = {}
                for n_, l in big_in.items():
                    arr = np.asarray(l, np.float32)
                    arr.tofile(self._nvme_file(n_, "master"))
                    big[n_] = jax.device_put(
                        arr.astype(np.dtype(self.compute_dtype)),
                        self._host_sh)
                    del arr
            else:
                big = {n: put32(l, self._host_sh)
                       for n, l in big_in.items()}
            small = {n: put32(l, self._dev_sh)
                     for n, l in small_in.items()}
            dev_rest = {k: jax.tree.map(lambda x: put32(x, self._dev_sh), v)
                        for k, v in given.items() if k != "layers"}
            # release the engine's references to the input tree (the
            # caller should del theirs too — at Infinity scale two
            # resident copies of the weights exhaust host RAM)
            self._init_params = given = big_in = small_in = None
        elif fp32_bytes < 6 * 2 ** 30 and not self._nvme:
            # small model: one init jit, big leaves straight to host
            out_sh = jax.tree.map(lambda _: self._dev_sh, abstract)
            sh_flat = dict(flatten_with_names(out_sh["layers"]))
            out_sh["layers"] = jax.tree.unflatten(
                self._layer_treedef,
                [self._host_sh if n in stream else sh_flat[n]
                 for n in self._layer_names])
            params32 = jax.jit(init32, out_shardings=out_sh)(rng)
            big, small = split_flat(params32["layers"])
            dev_rest = {k: v for k, v in params32.items()
                        if k != "layers"}
        else:
            # model bigger than a fraction of HBM: init ONE streamed
            # leaf per jit — XLA dead-code-eliminates every other leaf's
            # init math, so device high-water is one fp32 leaf, not the
            # tree (the zero.Init role at Infinity scale)
            big = {}
            for name in self._stream_names:
                def pick(rng, _n=name):
                    flat = dict(flatten_with_names(init32(rng)["layers"]))
                    return flat[_n]
                leaf = jax.jit(
                    pick, out_shardings=self._host_sh)(rng)
                # deliberate per-leaf sync: exactly ONE fp32 leaf may be
                # in flight — overlapping inits would stack their full
                # fp32 buffers and defeat the bounded-RAM init
                leaf.block_until_ready()   # graftlint: disable=GL003
                if self._nvme:
                    # one leaf at a time: fp32 never accumulates in RAM
                    arr = np.asarray(leaf)
                    arr.tofile(self._nvme_file(name, "master"))
                    del leaf
                    big[name] = jax.device_put(
                        arr.astype(np.dtype(self.compute_dtype)),
                        self._host_sh)
                    del arr
                else:
                    big[name] = leaf

            def rest(rng):
                p = init32(rng)
                _, small = split_flat(p["layers"])
                return {**{k: v for k, v in p.items() if k != "layers"},
                        "layers_small": small}

            dev_all = jax.jit(rest)(rng)
            small = dev_all.pop("layers_small")
            dev_rest = dev_all

        self.dev_master = dev_rest                          # fp32, device
        self.dev_master["layers_small"] = small
        self.dev_params = jax.tree.map(
            lambda x: x.astype(self.compute_dtype), self.dev_master)

        if self._nvme:
            # `big` already holds the compute-dtype stream stack; master
            # is on disk, moments are created lazily at the first step
            self.master_layers = None
            self.stream_layers = big
            self.m_layers = self.v_layers = None
        else:
            self.master_layers = big
            if self._stream_separate:
                # phase A reads a compute-dtype copy of the layer stacks
                # — HALF the per-micro-batch H2D bytes of streaming the
                # fp32 master (the dominant PCIe traffic at ga>1);
                # phase B refreshes it from the updated master in-scan
                cast_host = jax.jit(
                    lambda t: jax.tree.map(
                        lambda x: x.astype(self.compute_dtype), t),
                    out_shardings=jax.tree.map(
                        lambda _: self._host_sh,
                        jax.eval_shape(lambda t: t, big)))
                self.stream_layers = cast_host(big)
            else:
                # stream IS the master (fp32 compute, or
                # stream_dtype="master"): phase A casts per layer
                self.stream_layers = big
            mdt = self._moment_dtype
            zeros_like_host = jax.jit(
                lambda t: jax.tree.map(
                    lambda x: jnp.zeros(x.shape, mdt), t),
                out_shardings=jax.tree.map(lambda _: self._host_sh,
                                           jax.eval_shape(lambda t: t,
                                                          big)))
            self.m_layers = zeros_like_host(self.master_layers)
            self.v_layers = zeros_like_host(self.master_layers)
        self.dev_m = jax.tree.map(jnp.zeros_like, self.dev_master)
        self.dev_v = jax.tree.map(jnp.zeros_like, self.dev_master)
        self.step_count = 0
        self._n_layer_params = sum(
            int(np.prod(l.shape)) for n, l in named if n in stream)

    def _nvme_file(self, name: str, field: str) -> str:
        import os
        from ..ops.aio import safe_leaf_name
        return os.path.join(
            self._nvme_dir, f"streamed_{field}_{safe_leaf_name(name)}.bin")

    def close(self) -> None:
        """Release the NVMe scratch dir now (it is also removed at
        interpreter exit, but sweeps building several engines in one
        process should not strand fp32-state-sized dirs)."""
        cleanup = getattr(self, "_nvme_cleanup", None)
        if cleanup is not None:
            cleanup()
            self._nvme_cleanup = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass  # interpreter teardown

    # ------------------------------------------------------------------
    def _assemble_layer(self, big_flat: dict, small_flat: dict) -> PyTree:
        """Rebuild the nested layers tree from the two flat name->leaf
        dicts (works for per-layer slices and full stacks alike)."""
        merged = {**small_flat, **big_flat}
        return jax.tree.unflatten(
            self._layer_treedef,
            [merged[n] for n in self._layer_names])

    @property
    def params(self) -> PyTree:
        """Full parameter tree view; the streamed layer matrices are the
        HOST-RESIDENT fp32 master (reads are fine, they stream). Under
        the nvme tier the compute-dtype stream stack stands in — the
        fp32 master lives on disk (use save_checkpoint for exact
        state)."""
        big = self.stream_layers if self._nvme else self.master_layers
        out = {k: v for k, v in self.dev_params.items()
               if k != "layers_small"}
        out["layers"] = self._assemble_layer(
            big, self.dev_params["layers_small"])
        return out

    def host_memory_report(self) -> dict:
        import os
        out = {"pinned_host": 0, "device": 0, "nvme": 0}
        host_trees = [self.master_layers, self.m_layers, self.v_layers]
        if self._stream_separate:
            host_trees.append(self.stream_layers)
        # arrays placed through _host_sh are the HOST TIER by design; on
        # the CPU backend (tests, host-side nvme runs) there is no
        # pinned_host memory kind so the designed placement is reported
        # (everything there IS host memory)
        on_tpu = jax.default_backend() == "tpu"
        for leaf in jax.tree.leaves([t for t in host_trees
                                     if t is not None]):
            kind = getattr(leaf.sharding, "memory_kind", None)
            host = kind == "pinned_host" or not on_tpu
            out["pinned_host" if host else "device"] += \
                int(leaf.size) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves([self.dev_master, self.dev_m,
                                     self.dev_v]):
            out["device"] += int(leaf.size) * leaf.dtype.itemsize
        if self._nvme:
            for name in self._stream_names:
                for f in ("master", "exp_avg", "exp_avg_sq"):
                    path = self._nvme_file(name, f)
                    if os.path.exists(path):
                        out["nvme"] += os.path.getsize(path)
        total = out["pinned_host"] + out["device"] + out["nvme"]
        out["host_fraction"] = out["pinned_host"] / total if total else 0.0
        out["offloaded_fraction"] = ((out["pinned_host"] + out["nvme"])
                                     / total if total else 0.0)
        return out

    # ------------------------------------------------------------------
    def _to_dev(self, t):
        return jax.device_put(t, self._dev_sh)

    def _to_host(self, t):
        return jax.device_put(t, self._host_sh)

    def _build_phase_a(self, accumulate: bool = False):
        """grads: streamed fwd scan + manual reverse vjp scan.

        Returns (loss, grads_layers[host, compute-dtype], dev_grads[f32],
        grad_norm, finite). Gradients are seeded with 1/ga so the
        accumulated stacks hold the MEAN-loss gradient after the last
        micro-batch (reference GAS scales by 1/gas before the step).

        ``accumulate=True`` builds the micro-batch 1..ga-1 variant: the
        backward scan fetches the previous micro-batches' grad slice
        from pinned_host, adds this micro-batch's contribution, and
        writes the sum back — the host grad stacks are DONATED so the
        accumulator aliases in place; grad-norm/finite are computed over
        the accumulated values (so the last call's norm is the step's
        true mean-grad norm, and an earlier micro's NaN propagates).
        """
        module = self.module
        cdt = self.compute_dtype
        aux_coef = module.aux_loss_coef()
        inv_ga = 1.0 / self.gradient_accumulation_steps_

        def fetch(lh):
            # one layer's compute-dtype stream slice -> HBM (the cast is
            # a no-op in bf16 mode: phase B already wrote the stack in
            # compute dtype, halving this H2D stream vs fp32 master)
            return jax.tree.map(
                lambda t: self._to_dev(t).astype(cdt), lh)

        from ..models.transformer import _unpack_batch
        from ..ops.layers import cross_entropy_loss

        def head_loss(dev_params, x_last, targets):
            x = module._norm(x_last,
                             dev_params["final_norm"]["scale"],
                             dev_params["final_norm"].get("bias"))
            logits = module._project_vocab(dev_params, x)
            return cross_entropy_loss(logits, targets)

        split = self._split_flat
        assemble = self._assemble_layer

        def phase_a(stream_layers, dev_params, batch, *acc_args):
            tokens, targets = _unpack_batch(batch)
            small_stack = dev_params["layers_small"]

            def embed_fn(dp):
                return module.embed(dp, tokens)

            x0, embed_vjp = jax.vjp(embed_fn, dev_params)

            def fbody(carry, xs):
                x, aux = carry
                lh, small = xs
                y, la = module.block(assemble(fetch(lh), small), x)
                return (y, aux + la), x          # ys: layer input acts

            (xL, aux), acts = jax.lax.scan(
                fbody, (x0, jnp.zeros((), jnp.float32)),
                (stream_layers, small_stack))

            ce, head_vjp = jax.vjp(
                functools.partial(head_loss, targets=targets),
                dev_params, xL)
            loss = ce + aux_coef * aux
            d_head_dev, dxL = head_vjp(jnp.asarray(inv_ga, ce.dtype))

            if accumulate:
                grads_acc, dev_acc = acc_args
                bxs = (stream_layers, small_stack, acts, grads_acc)
            else:
                bxs = (stream_layers, small_stack, acts)

            def bbody(carry, xs):
                g, sq, finite = carry
                if accumulate:
                    lh, small, x_in, gacc = xs
                else:
                    (lh, small, x_in), gacc = xs, None

                def layer(lp, x):
                    return module.block(lp, x)

                lp = assemble(fetch(lh), small)
                _, vjp = jax.vjp(layer, lp, x_in)
                dlp, dx = vjp((g, jnp.asarray(aux_coef * inv_ga,
                                              jnp.float32)))
                dbig, dsmall = split(dlp)
                if accumulate:
                    dbig = jax.tree.map(
                        lambda a, b: self._to_dev(a) + b.astype(a.dtype),
                        gacc, dbig)
                for t in jax.tree.leaves(dbig):
                    sq += jnp.sum(jnp.square(t.astype(jnp.float32)))
                    finite &= jnp.isfinite(t).all()
                dsmall = jax.tree.map(
                    lambda t: t.astype(jnp.float32), dsmall)
                return (dx, sq, finite), (
                    jax.tree.map(self._to_host, dbig), dsmall)

            (dx0, sq, finite), (dlayers, dsmall_stack) = jax.lax.scan(
                bbody,
                (dxL, jnp.zeros((), jnp.float32), jnp.array(True)),
                bxs, reverse=True)

            (d_embed_dev,) = embed_vjp(dx0)
            dev_grads = jax.tree.map(
                lambda a, b: (a.astype(jnp.float32)
                              + b.astype(jnp.float32)),
                d_head_dev, d_embed_dev)
            # embed/head contribute zeros for layers_small, so this add
            # installs the per-layer small-grad stacks
            dev_grads["layers_small"] = jax.tree.map(
                jnp.add, dev_grads["layers_small"], dsmall_stack)
            if accumulate:
                dev_grads = jax.tree.map(jnp.add, dev_grads, dev_acc)
            # norm/finite over the (accumulated) device-resident grads,
            # including the small per-layer stacks
            for t in jax.tree.leaves(dev_grads):
                sq += jnp.sum(jnp.square(t))
                finite &= jnp.isfinite(t).all()
            return loss, dlayers, dev_grads, jnp.sqrt(sq), finite

        host = self._host_sh
        dev = self._dev_sh
        abstract = jax.eval_shape(
            lambda t: jax.tree.map(lambda x: x, t), self.stream_layers)
        grads_sh = jax.tree.map(lambda _: host, abstract)
        return jax.jit(
            phase_a,
            out_shardings=(dev, grads_sh, None, dev, dev),
            donate_argnums=(3, 4) if accumulate else ())

    def _adam_leaf(self, mst, m, v, g, t, lr, coef):
        b1, b2, eps, wd = self._b1, self._b2, self._eps, self._wd
        mdt, vdt = m.dtype, v.dtype   # storage dtype (moment_dtype)
        g = g.astype(jnp.float32) * coef
        m = b1 * m.astype(jnp.float32) + (1 - b1) * g
        v = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g)
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        u = mhat / (jnp.sqrt(vhat) + eps)
        if self._adamw_mode and wd:
            # decoupled decay only; __init__ rejects L2-mode decay
            u = u + wd * mst
        return mst - lr * u, m.astype(mdt), v.astype(vdt)

    @staticmethod
    def _untriple(out):
        is_t = lambda x: isinstance(x, tuple)   # noqa: E731
        return tuple(jax.tree.map(lambda o, _i=i: o[_i], out, is_leaf=is_t)
                     for i in range(3))

    def _dev_adam(self, dev_master, dev_m, dev_v, dev_grads, t, lr, coef):
        """Adam over the device-resident leaves (embed/head/norm/small
        per-layer stacks); returns (master', m', v', params')."""
        out = jax.tree.map(
            lambda a, b_, c, d: self._adam_leaf(a, b_, c, d, t, lr, coef),
            dev_master, dev_m, dev_v, dev_grads,
            is_leaf=lambda x: isinstance(x, jax.Array))
        dmst2, dm2, dv2 = self._untriple(out)
        dev_params2 = jax.tree.map(
            lambda x: x.astype(self.compute_dtype), dmst2)
        return dmst2, dm2, dv2, dev_params2

    def _build_phase_b(self):
        """Streamed Adam: scan (g, master, m, v) per layer through HBM;
        device-resident leaves update in the same program. Also emits
        the refreshed compute-dtype stream stack phase A reads."""
        cdt = self.compute_dtype
        sep = self._stream_separate

        def phase_b(master_layers, m_layers, v_layers, grads_layers,
                    stream_old, dev_master, dev_m, dev_v, dev_grads,
                    t, lr, coef):
            # stream_old is never read — it is DONATED so the refreshed
            # stream output aliases its pinned buffer instead of paying
            # a multi-GiB pinned-host allocation every step (measured:
            # fresh pinning cost ~8% of the 7B step)
            del stream_old
            def body(_, xs):
                mst, m, v, g = xs
                mst, m, v, g = jax.tree.map(self._to_dev, (mst, m, v, g))
                out = jax.tree.map(
                    lambda a, b_, c, d: self._adam_leaf(a, b_, c, d, t,
                                                        lr, coef),
                    mst, m, v, g,
                    is_leaf=lambda x: isinstance(x, jax.Array))
                mst2, m2, v2 = self._untriple(out)
                ys = [mst2, m2, v2]
                if sep:
                    ys.append(jax.tree.map(lambda x: x.astype(cdt), mst2))
                return (), tuple(jax.tree.map(self._to_host, x)
                                 for x in ys)

            _, host_out = jax.lax.scan(
                body, (), (master_layers, m_layers, v_layers,
                           grads_layers))
            dev_out = self._dev_adam(dev_master, dev_m, dev_v, dev_grads,
                                     t, lr, coef)
            return (*host_out, *dev_out)

        host = self._host_sh
        habs = jax.eval_shape(lambda t: t, self.master_layers)
        hsh = jax.tree.map(lambda _: host, habs)
        n_host = 4 if self._stream_separate else 3
        # grads_layers (arg 3) is deliberately NOT donated: it has no
        # same-shaped output to alias with (the r3 bench's "donated
        # buffers were not usable" warning was exactly these stacks);
        # train_batch deletes it right after the call instead.
        # stream_old (arg 4) IS donated even though unread: its pinned
        # buffer aliases the refreshed stream output (fp32 mode passes
        # an empty dict — stream aliases master there).
        return jax.jit(
            phase_b,
            out_shardings=(*([hsh] * n_host), None, None, None, None),
            donate_argnums=(0, 1, 2, 4, 5, 6, 7))

    # ------------------------------------------------------------------
    def _nvme_stream_step(self, grads_layers, lr: float, coef: float,
                          t: int) -> None:
        """Optimizer phase of the nvme tier: master + Adam moments page
        from NVMe one LAYER at a time with one-layer read-ahead (the
        PipelinedOptimizerSwapper pattern, reference:
        runtime/swap_tensor/pipelined_optimizer_swapper.py +
        stage3.py:1926), the native CPU Adam (csrc/cpu_optimizers.cpp)
        updates the fp32 shard in a bounce buffer, and the updated
        compute-dtype weights refresh the pinned_host stream stack that
        phase A reads. RAM high-water per leaf: two layers of fp32
        state + one compute-dtype stack.

        Runs in the client process, which is the TPU host, so
        reads/writes hit local NVMe."""
        if getattr(self, "_nvme_failed", None):
            raise RuntimeError(
                f"nvme swap state is corrupt ({self._nvme_failed}); "
                "reload from a checkpoint before training further")
        cdt_np = np.dtype(self.compute_dtype)
        mdt_np = np.dtype(self._moment_dtype)   # on-disk moment dtype
        m32 = mdt_np == np.float32
        io_stats = {"read": 0, "written": 0}
        new_stream = {}
        old_stream = self.stream_layers
        self.stream_layers = None
        try:
            self._nvme_sweep(grads_layers, lr, coef, t, cdt_np, mdt_np,
                             m32, io_stats, new_stream, old_stream)
        except Exception as e:
            # the sweep mutates disk state leaf-by-leaf and consumes the
            # grad stacks as it goes; a mid-sweep failure leaves master/
            # moments part step-t, part step-t-1 — poison the engine so
            # every later call says so instead of silently training on
            # (or checkpointing) corrupt state
            self._nvme_failed = f"{type(e).__name__}: {e}"
            raise
        self.stream_layers = new_stream
        self._have_moments = True
        self._last_nvme_io = io_stats

    def _nvme_sweep(self, grads_layers, lr, coef, t, cdt_np, mdt_np,
                    m32, io_stats, new_stream, old_stream):
        for name in self._stream_names:
            g_all = np.asarray(grads_layers[name])        # [L, ...] cdt
            del grads_layers[name]
            # the old stream leaf dies BEFORE the new one allocates —
            # the stacks never coexist, so host high-water stays one
            # stream stack + two layers of fp32 state
            old_stream.pop(name, None)
            L = g_all.shape[0]
            lshape = g_all.shape[1:]
            n_el = int(np.prod(lshape))
            nbytes = n_el * 4                   # master is fp32 on disk
            m_nbytes = n_el * mdt_np.itemsize
            paths = {f: self._nvme_file(name, f)
                     for f in ("master", "exp_avg", "exp_avg_sq")}
            # per-leaf scratch is allocated ONCE and reused across steps
            # (multi-GiB allocations per step otherwise): the stream
            # staging array, double buffers — read layer l+1 while layer
            # l computes, write layer l-1 behind both (synchronize() at
            # each iteration also completes the slot's previous write
            # before its buffer is reused) — and, when the disk moment
            # dtype differs, an fp32 compute view (the C++ optimizer
            # updates fp32; moment_dtype only sets STORAGE, matching
            # the cpu tier's semantics)
            cache = getattr(self, "_nvme_scratch", None) or {}
            self._nvme_scratch = cache
            if name not in cache:
                cache[name] = {
                    "stream": np.empty(g_all.shape, cdt_np),
                    "bufs": [
                        {"master": np.empty(lshape, np.float32),
                         "exp_avg": np.empty(lshape, mdt_np),
                         "exp_avg_sq": np.empty(lshape, mdt_np)}
                        for _ in range(2)],
                    "scratch32": (None if m32 else
                                  {f: np.empty(lshape, np.float32)
                                   for f in ("exp_avg", "exp_avg_sq")}),
                }
            stream_np = cache[name]["stream"]
            bufs = cache[name]["bufs"]
            scratch32 = cache[name]["scratch32"]

            def start_read(l, slot):
                self._aio.async_pread(bufs[slot]["master"],
                                      paths["master"], l * nbytes)
                if self._have_moments:
                    for f in ("exp_avg", "exp_avg_sq"):
                        self._aio.async_pread(bufs[slot][f], paths[f],
                                              l * m_nbytes)

            start_read(0, 0)
            for l in range(L):
                slot = l % 2
                rc = self._aio.synchronize()   # read(l) + write(l-1)
                if rc:
                    raise IOError(f"nvme swap I/O failed (rc={rc}) on "
                                  f"{paths['master']}")
                if l + 1 < L:
                    start_read(l + 1, 1 - slot)
                b = bufs[slot]
                if m32:
                    moments = {"exp_avg": b["exp_avg"],
                               "exp_avg_sq": b["exp_avg_sq"]}
                    if not self._have_moments:
                        for buf in moments.values():
                            buf.fill(0.0)
                else:
                    moments = scratch32
                    for f, buf in moments.items():
                        if self._have_moments:
                            buf[:] = b[f]      # mdt -> fp32 cast
                        else:
                            buf.fill(0.0)
                # always a fresh C-order fp32 buffer: the pinned-host
                # stack can come back F-contiguous on TPU backends, and
                # the C++ optimizer requires C-contiguous input
                g = np.array(g_all[l], dtype=np.float32, order="C")
                if coef != 1.0:
                    g *= np.float32(coef)
                self._cpu_opt.step_raw(b["master"], g, moments, lr, t)
                stream_np[l] = b["master"].astype(cdt_np)
                if not m32:
                    for f, buf in moments.items():
                        b[f][:] = buf          # fp32 -> mdt for disk
                self._aio.async_pwrite(b["master"], paths["master"],
                                       l * nbytes)
                for f in ("exp_avg", "exp_avg_sq"):
                    self._aio.async_pwrite(b[f], paths[f], l * m_nbytes)
                io_stats["read"] += (nbytes + 2 * m_nbytes
                                     if self._have_moments else nbytes)
                io_stats["written"] += nbytes + 2 * m_nbytes
            rc = self._aio.synchronize()
            if rc:
                raise IOError(f"nvme swap write failed (rc={rc})")
            # TPU: device_put into pinned_host COPIES (registration
            # boundary), so the cached staging buffer is safe to reuse
            # next step. CPU rig: device_put may alias the numpy buffer
            # zero-copy — hand it a private copy so a caller holding
            # engine.params across steps never sees mutation.
            src = (stream_np if jax.default_backend() == "tpu"
                   else stream_np.copy())
            new_stream[name] = jax.device_put(src, self._host_sh)
            del g_all

    # ------------------------------------------------------------------
    def _check_usable(self):
        if self._nvme and getattr(self, "_nvme_failed", None):
            raise RuntimeError(
                f"nvme swap state is corrupt ({self._nvme_failed}); "
                "reload from a checkpoint before using this engine")

    def train_batch(self, batch=None, data_iter=None):
        tel = _tel()
        with (tel.span("train_batch", step=self.global_steps + 1,
                       engine="streamed")
              if tel is not None else _NULLCM):
            return self._train_batch_impl(batch, data_iter)

    def _train_batch_impl(self, batch=None, data_iter=None):
        self._check_usable()
        ga = self.gradient_accumulation_steps_
        if self._phase_a is None:
            self._phase_a = self._build_phase_a()
            if self._nvme:
                self._phase_b_dev = jax.jit(self._dev_adam,
                                            donate_argnums=(0, 1, 2))
            else:
                self._phase_b = self._build_phase_b()
            self._phase_a_acc = (self._build_phase_a(accumulate=True)
                                 if ga > 1 else None)
        # assemble the step's micro-batches: a full train batch splits
        # along the leading axis; a data_iter yields one micro-batch per
        # draw (reference train_batch pulls gas micro-batches)
        if batch is None:
            if data_iter is None:
                raise ValueError("train_batch needs a batch or data_iter")
            micros = [next(data_iter) for _ in range(ga)]
            for m in micros:
                n = np.shape(jax.tree.leaves(m)[0])[0]
                if n != self.micro_batch_size_:
                    raise ValueError(
                        f"data_iter yielded a {n}-row batch; the streamed "
                        f"engine draws {ga} MICRO-batches of "
                        f"{self.micro_batch_size_} rows per step "
                        "(pass batch= for a full train batch instead)")
        elif ga == 1:
            micros = [batch]
        else:
            mb = self.micro_batch_size_
            n = np.shape(jax.tree.leaves(batch)[0])[0]
            if n != self.train_batch_size_:
                raise ValueError(
                    f"train_batch got {n} samples; expected "
                    f"train_batch_size={self.train_batch_size_} "
                    f"(= {mb} micro x {ga} accumulation)")
            micros = [jax.tree.map(lambda x: x[i * mb:(i + 1) * mb],
                                   batch) for i in range(ga)]
        t0 = time.perf_counter()
        losses = []
        grads_layers = dev_grads = None
        for i, micro in enumerate(micros):
            micro = jax.tree.map(
                lambda x: jax.device_put(jnp.asarray(x), self._dev_sh),
                micro)
            if i == 0:
                loss, grads_layers, dev_grads, norm, finite = \
                    self._phase_a(self.stream_layers, self.dev_params,
                                  micro)
            else:
                loss, grads_layers, dev_grads, norm, finite = \
                    self._phase_a_acc(self.stream_layers,
                                      self.dev_params, micro,
                                      grads_layers, dev_grads)
            losses.append(loss)
        loss = losses[0] if ga == 1 else jnp.mean(jnp.stack(losses))
        metrics = {"loss": loss, "grad_norm": norm,
                   "loss_scale": jnp.ones(()), "overflow": ~finite}
        if bool(finite):
            lr = float(self.lr_schedule(self.step_count))
            clip = self.config.gradient_clipping
            coef = 1.0
            if clip and clip > 0:
                coef = min(1.0, clip / (float(norm) + 1e-6))
            t = self.step_count + 1
            if self._nvme:
                (self.dev_master, self.dev_m, self.dev_v,
                 self.dev_params) = self._phase_b_dev(
                    self.dev_master, self.dev_m, self.dev_v, dev_grads,
                    jnp.asarray(t, jnp.float32),
                    jnp.asarray(lr, jnp.float32),
                    jnp.asarray(coef, jnp.float32))
                self._nvme_stream_step(grads_layers, lr, coef, t)
            else:
                # the old stream stack is DONATED into phase_b so the
                # refreshed one aliases its pinned buffer (when the
                # stream IS the master — fp32 compute or
                # stream_dtype="master" — donate nothing extra, the
                # alias renews below)
                old_stream = (self.stream_layers
                              if self._stream_separate else {})
                self.stream_layers = None
                out = self._phase_b(
                    self.master_layers, self.m_layers, self.v_layers,
                    grads_layers, old_stream, self.dev_master,
                    self.dev_m, self.dev_v, dev_grads,
                    jnp.asarray(t, jnp.float32),
                    jnp.asarray(lr, jnp.float32),
                    jnp.asarray(coef, jnp.float32))
                del old_stream
                if self._stream_separate:
                    (self.master_layers, self.m_layers, self.v_layers,
                     self.stream_layers, self.dev_master, self.dev_m,
                     self.dev_v, self.dev_params) = out
                else:
                    (self.master_layers, self.m_layers, self.v_layers,
                     self.dev_master, self.dev_m, self.dev_v,
                     self.dev_params) = out
                    self.stream_layers = self.master_layers
            self.step_count = t
        else:
            self.skipped_steps += 1
        del grads_layers
        self.global_steps += 1
        self.global_samples += self.train_batch_size_
        self._last_metrics = metrics
        if self.global_steps % self.config.steps_per_print == 0:
            dt = time.perf_counter() - t0
            logger.info(f"[streamed] step {self.global_steps} "
                        f"loss={float(loss):.4f} "
                        f"norm={float(norm):.3f} {dt*1e3:.0f}ms")
        return metrics["loss"]

    def _build_eval(self):
        """Forward-only streamed loss — no backward scan, no grad D2H
        (the slow direction), ~1/3 the FLOPs of phase A."""
        module = self.module
        cdt = self.compute_dtype
        aux_coef = module.aux_loss_coef()
        assemble = self._assemble_layer
        from ..models.transformer import _unpack_batch
        from ..ops.layers import cross_entropy_loss

        def fwd(stream_layers, dev_params, batch):
            tokens, targets = _unpack_batch(batch)
            x = module.embed(dev_params, tokens)

            def body(carry, xs):
                x, aux = carry
                lh, small = xs
                lp = assemble(jax.tree.map(
                    lambda t: self._to_dev(t).astype(cdt), lh), small)
                y, la = module.block(lp, x)
                return (y, aux + la), ()

            (xL, aux), _ = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)),
                (stream_layers, dev_params["layers_small"]))
            xn = module._norm(xL, dev_params["final_norm"]["scale"],
                              dev_params["final_norm"].get("bias"))
            logits = module._project_vocab(dev_params, xn)
            return cross_entropy_loss(logits, targets) + aux_coef * aux

        return jax.jit(fwd, out_shardings=self._dev_sh)

    def eval_batch(self, batch):
        self._check_usable()
        if getattr(self, "_eval_jit", None) is None:
            self._eval_jit = self._build_eval()
        batch = jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x), self._dev_sh), batch)
        return self._eval_jit(self.stream_layers, self.dev_params, batch)

    def get_global_grad_norm(self):
        m = self._last_metrics
        return float(m["grad_norm"]) if m is not None else None

    def save_16bit_model(self, save_dir, checkpoint_name="model_weights.npz"):
        """Consolidated weights export — the bridge OFF the streamed
        tier: the npz loads into init_inference(checkpoint=...) or back
        into the sharded engine via model_parameters, so a model trained
        7B-style on one chip can be served or resumed sharded on a pod
        (reference: engine.save_16bit_model:3638)."""
        from types import SimpleNamespace

        from .checkpointing import save_16bit_model as _save
        return _save(SimpleNamespace(state={"params": self.params}),
                     save_dir, checkpoint_name)

    # ------------------------------------------------------------------
    # checkpointing: host state pulls through the client process (the
    # TPU host)
    def save_checkpoint(self, save_dir, tag=None, client_state=None, **_kw):
        self._check_usable()
        import os
        import pickle
        from ..checkpoint.universal import flatten_with_names
        tag = tag or f"global_step{self.step_count}"
        path = os.path.join(save_dir, tag)
        os.makedirs(path, exist_ok=True)
        arrays = {}
        if self._nvme:
            # stream the fp32 master/moments out of the swap files one
            # leaf at a time (never materializing the full fp32 tree)
            for name in self._stream_names:
                shape = self.stream_layers[name].shape
                mdt = np.dtype(self._moment_dtype)
                for prefix, f in (("master", "master"), ("m", "exp_avg"),
                                  ("v", "exp_avg_sq")):
                    swap_path = self._nvme_file(name, f)
                    dt = np.float32 if prefix == "master" else mdt
                    if prefix == "master" or self._have_moments:
                        arrays[f"{prefix}::{name}"] = np.fromfile(
                            swap_path, dt).reshape(shape)
                    else:
                        arrays[f"{prefix}::{name}"] = np.zeros(shape, dt)
            host_trees = ()
        else:
            host_trees = (("master", self.master_layers),
                          ("m", self.m_layers), ("v", self.v_layers))
        for prefix, tree in (*host_trees,
                             ("dev_master", self.dev_master),
                             ("dev_m", self.dev_m),
                             ("dev_v", self.dev_v)):
            for name, leaf in flatten_with_names(tree):
                arrays[f"{prefix}::{name}"] = np.asarray(leaf)
        arrays["__step__"] = np.asarray(self.step_count)
        # full progress counters, not just the optimizer step — a resumed
        # run reports the same global_steps/samples it left off with
        # (reference engine.save_checkpoint state dict parity)
        arrays["__progress__"] = np.asarray(
            [self.global_steps, self.global_samples, self.skipped_steps])
        arrays["__client_state__"] = np.frombuffer(
            pickle.dumps(client_state or {}), dtype=np.uint8)
        np.savez(os.path.join(path, "streamed_state.npz"), **arrays)
        with open(os.path.join(save_dir, "latest"), "w") as f:
            f.write(tag)
        return True

    def load_checkpoint(self, load_dir, tag=None,
                        load_optimizer_states=True,
                        load_module_only=False, **_kw):
        """Restore streamed state. ``load_optimizer_states=False`` (or
        ``load_module_only=True``) restores weights but keeps zero
        moments / step 0 — the reference's weights-only reload. Other
        reference kwargs (load_lr_scheduler_states, custom loaders) have
        no referent here: the schedule is a pure function of step_count."""
        import os
        import pickle
        if tag is None:
            with open(os.path.join(load_dir, "latest")) as f:
                tag = f.read().strip()
        data = np.load(os.path.join(load_dir, tag, "streamed_state.npz"))
        from ..checkpoint.universal import flatten_with_names

        def restore(prefix, tree, sharding):
            leaves = []
            for name, leaf in flatten_with_names(tree):
                arr = jnp.asarray(data[f"{prefix}::{name}"],
                                  dtype=leaf.dtype)
                leaves.append(jax.device_put(arr, sharding))
            flat, treedef = jax.tree.flatten(tree)
            return jax.tree.unflatten(treedef, leaves)

        opt = load_optimizer_states and not load_module_only
        if self._nvme:
            import os
            cdt_np = np.dtype(self.compute_dtype)
            stream = {}
            for name in self._stream_names:
                master = np.ascontiguousarray(data[f"master::{name}"],
                                              dtype=np.float32)
                master.tofile(self._nvme_file(name, "master"))
                stream[name] = jax.device_put(
                    master.astype(cdt_np), self._host_sh)
                for prefix, f in (("m", "exp_avg"), ("v", "exp_avg_sq")):
                    path = self._nvme_file(name, f)
                    if opt:
                        np.ascontiguousarray(
                            data[f"{prefix}::{name}"],
                            dtype=np.dtype(self._moment_dtype)) \
                            .tofile(path)
                    elif os.path.exists(path):
                        os.unlink(path)
            self.stream_layers = stream
            self._have_moments = opt
            self._nvme_failed = None   # disk state is clean again
        else:
            self.master_layers = restore("master", self.master_layers,
                                         self._host_sh)
            if self._stream_separate:
                self.stream_layers = jax.jit(
                    lambda t: jax.tree.map(
                        lambda x: x.astype(self.compute_dtype), t),
                    out_shardings=jax.tree.map(
                        lambda _: self._host_sh,
                        jax.eval_shape(lambda t: t,
                                       self.master_layers)))(
                    self.master_layers)
            else:
                self.stream_layers = self.master_layers
        self.dev_master = restore("dev_master", self.dev_master,
                                  self._dev_sh)
        if opt:
            if not self._nvme:
                self.m_layers = restore("m", self.m_layers,
                                        self._host_sh)
                self.v_layers = restore("v", self.v_layers,
                                        self._host_sh)
            self.dev_m = restore("dev_m", self.dev_m, self._dev_sh)
            self.dev_v = restore("dev_v", self.dev_v, self._dev_sh)
        else:
            # weights-only reload must also RESET moments: step_count
            # goes to 0, and t=1 bias correction against stale trained
            # moments would wildly overscale the first update
            def zeros(tree, sh):
                return jax.tree.map(
                    lambda x: jax.device_put(
                        jnp.zeros(x.shape, x.dtype), sh), tree)
            if not self._nvme:
                self.m_layers = zeros(self.m_layers, self._host_sh)
                self.v_layers = zeros(self.v_layers, self._host_sh)
            self.dev_m = zeros(self.dev_m, self._dev_sh)
            self.dev_v = zeros(self.dev_v, self._dev_sh)
        self.dev_params = jax.tree.map(
            lambda x: x.astype(self.compute_dtype), self.dev_master)
        self.step_count = int(data["__step__"]) if opt else 0
        if "__progress__" in data and opt:
            gs, gsa, sk = (int(x) for x in data["__progress__"])
            self.global_steps, self.global_samples = gs, gsa
            self.skipped_steps = sk
        client_state = {}
        if "__client_state__" in data:
            client_state = pickle.loads(bytes(data["__client_state__"]))
        return load_dir, client_state

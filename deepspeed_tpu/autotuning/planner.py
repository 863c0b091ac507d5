"""Candidate search (ISSUE 7 tentpole part 2).

The planner enumerates a deterministic candidate grid over
``MeshTopology`` axis factorizations x microbatch x ZeRO stage x remat
policy x optimizer-offload ratio x overlap ratio, prunes it with the
audited :class:`~.cost_model.MemoryModel` against measured HBM
headroom, AOT-compiles every survivor through the ledger's shared
``lower_compiled()`` path — compiler cost/memory/collective truth
without dispatching a single training step — ranks by the calibrated
:class:`~.cost_model.CostModel`'s predicted step time, and (optionally)
measures the top-K candidates with hermetic in-process trials, the
same trial harness the reference-shaped :class:`~.autotuner.Autotuner`
runs.

Scoring is deterministic: candidate order is lexicographic, the cost
model contains no clock or RNG, and ties break on the candidate key —
the same inputs always produce the same ranked plan. Only the
(optional, explicitly requested) measured trials touch the wall clock,
and their results are reported next to the prediction, never silently
substituted into it.

Host-only contract (graftlint GL041): nothing in this module is
jit-reachable; engines are built and AOT-compiled at the host level.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import time
from typing import Any, Callable, Optional

from .config import AutotuningConfig
from .cost_model import (AOTFacts, Calibration, CostModel, MemoryModel,
                         dtype_bytes, hbm_headroom_bytes, model_dims)
from .plan import Plan, deep_merge

# mesh axes whose product shards the batch (parallel/mesh.py BATCH_AXES)
_BATCH_AXES = ("dp", "fsdp", "zps")
_ALL_AXES = ("pp", "dp", "fsdp", "zps", "ep", "sp", "tp")


def _hlo_collectives():
    """The pure-host HLO collective analysis (telemetry/collectives.py).
    Imported here, not at module top: the planner is an offline tool the
    user invoked explicitly, so pulling the telemetry package in is
    fine, but it must never ride the import of ``deepspeed_tpu``
    itself (the disabled-mode zero-import contract)."""
    from ..telemetry import collectives  # graftlint: disable=GL040 — offline planner tool, explicit user entry point; analyze_hlo is pure host text analysis
    return collectives


@dataclasses.dataclass(frozen=True, order=True)
class Candidate:
    """One point of the search space. Ordered + hashable so grids are
    deterministic and dedupable."""

    mesh: tuple[tuple[str, int], ...]   # searched axes only, sorted
    micro_batch: int
    zero_stage: int
    remat_policy: str
    offload_ratio: float
    overlap_ratio: float
    # qwZ/qgZ wire format for the sharded-DP collectives: "fp32" = XLA's
    # implicit full-precision wire, "int8"/"fp8" = the ZeRO++ quantized
    # protocol (runtime/zeropp.py). Joins the grid via
    # AutotuningConfig.wire_dtypes.
    wire_dtype: str = "fp32"
    # MoE routing grid (ISSUE 16), only populated for MoE models:
    # capacity factor 0.0 = keep the model config's value; moe_wire is
    # the dispatch all-to-all wire (runtime/comm/moe_alltoall.py),
    # independent of the ZeRO wire_dtype above. Joins the grid via
    # AutotuningConfig.moe_capacity_factors / moe_wire_dtypes.
    moe_capacity_factor: float = 0.0
    moe_wire: str = "fp32"

    @property
    def mesh_sizes(self) -> dict[str, int]:
        return dict(self.mesh)

    def label(self) -> str:
        mesh = "x".join(f"{a}{s}" for a, s in self.mesh if s > 1) or "1dev"
        off = (f" off={self.offload_ratio:g}" if self.offload_ratio > 0
               else "")
        wire = (f" wire={self.wire_dtype}" if self.wire_dtype != "fp32"
                else "")
        moe = ""
        if self.moe_capacity_factor > 0:
            moe += f" cf={self.moe_capacity_factor:g}"
        if self.moe_wire != "fp32":
            moe += f" a2a={self.moe_wire}"
        return (f"{mesh} mb{self.micro_batch} z{self.zero_stage} "
                f"remat={self.remat_policy}{off}{wire}{moe}")

    def config_patch(self, grad_accum: int = 1) -> dict:
        """The ds-config diff this candidate applies on the base
        config. ``Plan.apply`` replays exactly this patch, so a plan's
        chosen config reproduces the trial config bit-for-bit."""
        zero: dict[str, Any] = {"stage": self.zero_stage}
        if self.offload_ratio > 0:
            zero["offload_optimizer"] = {"device": "cpu",
                                         "ratio": self.offload_ratio}
        else:
            zero["offload_optimizer"] = {"device": "none"}
        if self.wire_dtype != "fp32":
            zero["zero_quantized_weights"] = True
            zero["zero_quantized_gradients"] = True
            zero["zero_quantized_dtype"] = self.wire_dtype
        else:
            # explicit off: the patch must override a base config that
            # had quantization on, or plan replay diverges
            zero["zero_quantized_weights"] = False
            zero["zero_quantized_gradients"] = False
        patch = {
            "mesh": {a: s for a, s in self.mesh},
            "train_micro_batch_size_per_gpu": self.micro_batch,
            "gradient_accumulation_steps": grad_accum,
            "train_batch_size": None,   # re-derived from mb x ga x dp
            "zero_optimization": zero,
            "activation_checkpointing": {"policy": self.remat_policy},
        }
        # only emitted when non-default so dense-model patches (and the
        # exact-dict assertions plan replay relies on) are unchanged
        moe: dict[str, Any] = {}
        if self.moe_wire != "fp32":
            moe["wire_dtype"] = self.moe_wire
        if self.moe_capacity_factor > 0:
            moe["capacity_factor"] = self.moe_capacity_factor
        if moe:
            patch["moe"] = moe
        return patch

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["mesh"] = dict(self.mesh)
        d["label"] = self.label()
        return d


def mesh_factorizations(n_free: int, axes: tuple[str, ...]) -> \
        list[tuple[tuple[str, int], ...]]:
    """Every assignment of ``n_free`` devices to ``axes`` whose product
    is exactly ``n_free``, each emitted in the CANONICAL (axis-sorted)
    tuple form every ``Candidate.mesh`` uses — membership tests and
    dedup against candidate meshes must not depend on the order the
    user listed ``mesh_axes`` in. Deterministic (sorted) output."""
    axes = tuple(axes)
    if not axes:
        return [()]
    out: list[tuple[tuple[str, int], ...]] = []

    def rec(i: int, remaining: int, acc: tuple):
        if i == len(axes) - 1:
            out.append(tuple(sorted(acc + ((axes[i], remaining),))))
            return
        for d in range(1, remaining + 1):
            if remaining % d == 0:
                rec(i + 1, remaining // d, acc + ((axes[i], d),))

    rec(0, max(int(n_free), 1), ())
    return sorted(out)


class Planner:
    """Searches the config space for ``model`` starting from
    ``base_config`` (a ds-config dict). ``make_batch(total_batch)``
    builds one training batch — required for AOT compilation (shapes)
    and measured trials."""

    def __init__(self, model, base_config: dict,
                 tuning_config: Optional[AutotuningConfig] = None,
                 make_batch: Optional[Callable[[int], Any]] = None,
                 calibration: Optional[Calibration] = None,
                 device_memory_bytes: Optional[int] = None):
        import jax
        self.model = model
        self.base_config = {k: v for k, v in dict(base_config).items()
                            if k != "autotuning"}
        self.cfg = tuning_config or AutotuningConfig(
            **base_config.get("autotuning", {}))
        self.make_batch = make_batch
        self.calibration = calibration
        self.n_devices = len(jax.devices())
        self.headroom = (int(device_memory_bytes)
                         if device_memory_bytes is not None
                         else hbm_headroom_bytes())
        mcfg = getattr(model, "config", None)
        self.model_dims = model_dims(mcfg) if mcfg is not None else {}
        self.num_params = self._num_params()
        # engine builds plumb each candidate's remat policy into the
        # model config; snapshot the starting values so the base grid
        # point stays stable and plan() can restore them
        self._base_remat_policy = str(getattr(
            mcfg, "remat_policy", "nothing_saveable"))
        self._base_remat_on = bool(getattr(mcfg, "remat", True))
        self._batch_cache: dict[int, Any] = {}
        # AOT facts keyed by trial-config JSON: the base candidate is
        # compiled once across calibrate()/scoring, and overlap-only
        # variants (byte-identical trial configs) share one compile
        self._aot_cache: dict[str, AOTFacts] = {}
        self._trial_log: list[dict] = []

    @property
    def trial_log(self) -> list[dict]:
        """Every measured trial this planner ran (calibration first):
        {label, step_s, tokens_per_sec} — the calibration row doubles
        as the hand-tuned-baseline throughput for bench comparisons."""
        return list(self._trial_log)

    # -- model facts ---------------------------------------------------
    def _num_params(self) -> int:
        mcfg = getattr(self.model, "config", None)
        if mcfg is not None and hasattr(mcfg, "num_params"):
            return int(mcfg.num_params())
        from .autotuner import model_info_profile
        return int(model_info_profile(self.model)["num_params"])

    def _compute_dtype_bytes(self) -> int:
        base = self.base_config
        if base.get("fp16", {}).get("enabled"):
            return 2
        if base.get("bf16", {}).get("enabled"):
            return 2
        return 4

    def memory_model(self, cand: Candidate) -> MemoryModel:
        sizes = self._merged_mesh_sizes(cand)
        sharded_dp = sizes.get("fsdp", 1) * sizes.get("zps", 1)
        return MemoryModel(num_params=self.num_params,
                           bytes_per_el=self._compute_dtype_bytes(),
                           world=max(sharded_dp, 1))

    @staticmethod
    def _axis_default(axis: str) -> int:
        # MeshConfig's defaults: an absent mesh block means fsdp=-1
        # (absorb all devices), every other axis 1 — the planner must
        # read a mesh-less base config the way the engine would
        return -1 if axis == "fsdp" else 1

    def _merged_mesh_sizes(self, cand: Candidate) -> dict[str, int]:
        base_mesh = dict(self.base_config.get("mesh", {}))
        sizes = {a: int(base_mesh.get(a, self._axis_default(a)))
                 for a in _ALL_AXES}
        sizes.update(cand.mesh_sizes)
        # an un-searched fsdp=-1 absorbs whatever the searched axes left
        if sizes.get("fsdp", 1) == -1:
            fixed = 1
            for a, s in sizes.items():
                if a != "fsdp" and s > 0:
                    fixed *= s
            sizes["fsdp"] = max(self.n_devices // max(fixed, 1), 1)
        return sizes

    def data_parallel_size(self, cand: Candidate) -> int:
        sizes = self._merged_mesh_sizes(cand)
        dp = 1
        for a in _BATCH_AXES:
            dp *= max(sizes.get(a, 1), 1)
        return dp

    def _grad_accum(self) -> int:
        return int(self.base_config.get("gradient_accumulation_steps", 1)
                   or 1)

    def total_batch(self, cand: Candidate) -> int:
        return (cand.micro_batch * self._grad_accum()
                * self.data_parallel_size(cand))

    def _n_free(self) -> int:
        """Devices left for the searched axes after the base config's
        fixed (non-searched, positive-size) axes. An un-searched
        fsdp=-1 contributes nothing fixed: the engine resolves it to
        absorb whatever the searched axes leave over."""
        base_mesh = dict(self.base_config.get("mesh", {}))
        searched = set(self.cfg.mesh_axes)
        fixed = 1
        for a in _ALL_AXES:
            if a in searched:
                continue
            s = int(base_mesh.get(a, self._axis_default(a)))
            if s > 0:
                fixed *= s
        return max(self.n_devices // max(fixed, 1), 1)

    # -- grid ----------------------------------------------------------
    def enumerate_candidates(self) -> list[Candidate]:
        cfg = self.cfg
        searched = tuple(cfg.mesh_axes)
        meshes = mesh_factorizations(self._n_free(), searched)
        stages = (sorted(set(cfg.zero_stages)) if cfg.zero_stages
                  else [0, 1, 2, 3])
        mbs = self._micro_batches()
        out: list[Candidate] = []
        wires = cfg.wire_dtypes or ["fp32"]
        # MoE grid (ISSUE 16): dense models keep a single default point
        # so their grids are byte-identical to before
        n_exp = int(getattr(getattr(self.model, "config", None),
                            "num_experts", 0) or 0)
        moe_cfs = (cfg.moe_capacity_factors or [0.0]) if n_exp else [0.0]
        moe_wires = (cfg.moe_wire_dtypes or ["fp32"]) if n_exp else ["fp32"]
        for mesh in meshes:
            # an ep shard must own a whole number of experts (dense
            # models have nothing to put on an ep axis at all)
            ep = dict(mesh).get("ep", 1)
            if ep > 1 and (n_exp <= 0 or n_exp % ep):
                continue
            for mb in mbs:
                for st in stages:
                    for remat in (cfg.remat_policies
                                  or ["nothing_saveable"]):
                        for off in (cfg.offload_ratios or [0.0]):
                            for ov in (cfg.overlap_ratios or [0.71]):
                                for wire in wires:
                                    # quantized wire is a ZeRO-3 shard
                                    # feature: nothing to quantize
                                    # below stage 2
                                    if wire != "fp32" and st < 2:
                                        continue
                                    for mcf in moe_cfs:
                                        for mwire in moe_wires:
                                            out.append(Candidate(
                                                mesh=mesh, micro_batch=mb,
                                                zero_stage=st,
                                                remat_policy=remat,
                                                offload_ratio=float(off),
                                                overlap_ratio=float(ov),
                                                wire_dtype=str(wire),
                                                moe_capacity_factor=float(mcf),
                                                moe_wire=str(mwire)))
        if cfg.include_base:
            base = self._base_candidate()
            if base is not None and base not in out:
                out.append(base)
        out = sorted(set(out))
        if cfg.max_train_batch_size:
            out = [c for c in out
                   if self.total_batch(c) <= cfg.max_train_batch_size]
        return out

    def _micro_batches(self) -> list[int]:
        cfg = self.cfg
        lo = max(cfg.min_train_micro_batch_size_per_gpu, 1)
        hi = cfg.max_train_micro_batch_size_per_gpu or lo * 2 ** (
            cfg.num_tuning_micro_batch_sizes - 1)
        out, mb = [], lo
        while mb <= hi:
            out.append(mb)
            mb *= 2
        return out[: cfg.num_tuning_micro_batch_sizes] or [lo]

    def _base_candidate(self) -> Optional[Candidate]:
        """The hand-tuned base config expressed as a grid point, so the
        plan can never choose something worse than what the user
        already had (when measured trials run). Searched axes the base
        leaves implicit take the engine's defaults (fsdp absorbs), and
        any -1 resolves against the devices the fixed axes leave free —
        the same arithmetic ``enumerate_candidates`` uses, so the base
        point really is a member of the grid."""
        base = self.base_config
        searched = tuple(self.cfg.mesh_axes)
        base_mesh = dict(base.get("mesh", {}))
        sizes = {a: int(base_mesh.get(a, self._axis_default(a)))
                 for a in searched}
        mesh = tuple(sorted(sizes.items()))
        if any(s == -1 for _, s in mesh):
            meshes = mesh_factorizations(self._n_free(), searched)
            if sum(1 for _, s in mesh if s == -1) == 1:
                # engine arithmetic: the -1 axis absorbs whatever the
                # other searched axes leave of the free devices
                fixed = 1
                for _, s in mesh:
                    if s > 0:
                        fixed *= s
                auto = max(self._n_free() // max(fixed, 1), 1)
                mesh = tuple(sorted((a, auto if s == -1 else s)
                                    for a, s in mesh))
            if mesh not in meshes:
                mesh = meshes[0] if meshes else ()
        try:
            mb = int(base.get("train_micro_batch_size_per_gpu") or 0)
            if not mb and base.get("train_batch_size"):
                probe = Candidate(mesh=mesh, micro_batch=1, zero_stage=0,
                                  remat_policy="nothing_saveable",
                                  offload_ratio=0.0, overlap_ratio=0.71)
                dp = self.data_parallel_size(probe)
                mb = max(int(base["train_batch_size"])
                         // (self._grad_accum() * dp), 1)
            if not mb:
                return None
        except Exception:
            return None
        zero = base.get("zero_optimization", {})
        off = zero.get("offload_optimizer", {})
        ratio = (float(off.get("ratio", 1.0))
                 if off.get("device") == "cpu" else 0.0)
        remat = (self._base_remat_policy if self._base_remat_on
                 else "none")
        ovs = self.cfg.overlap_ratios or [0.71]
        wire = (str(zero.get("zero_quantized_dtype", "int8"))
                if zero.get("zero_quantized_weights")
                or zero.get("zero_quantized_gradients") else "fp32")
        moe = base.get("moe", {}) or {}
        return Candidate(mesh=mesh, micro_batch=mb,
                         zero_stage=int(zero.get("stage", 0)),
                         remat_policy=remat,
                         offload_ratio=ratio, overlap_ratio=float(ovs[0]),
                         wire_dtype=wire,
                         moe_capacity_factor=float(
                             moe.get("capacity_factor") or 0.0),
                         moe_wire=str(moe.get("wire_dtype", "fp32")))

    # -- memory pruning ------------------------------------------------
    def prune(self, candidates: list[Candidate]) -> \
            tuple[list[Candidate], list[tuple[Candidate, dict]]]:
        """(kept, [(pruned, why)]) by the memory model against the
        measured headroom. Headroom 0 (unknown backend) keeps all."""
        kept, pruned = [], []
        dims = self.model_dims
        for c in candidates:
            mm = self.memory_model(c)
            kw = dict(micro_batch=c.micro_batch,
                      seq_len=dims.get("seq_len", 0),
                      hidden=dims.get("hidden", 0),
                      num_layers=dims.get("num_layers", 0),
                      remat_policy=c.remat_policy,
                      offload_ratio=c.offload_ratio,
                      vocab_size=dims.get("vocab_size", 0))
            if mm.fits(self.headroom, c.zero_stage,
                       safety_factor=self.cfg.memory_safety_factor, **kw):
                kept.append(c)
            else:
                pruned.append((c, {
                    "modeled_bytes": mm.total_bytes(c.zero_stage, **kw),
                    "headroom_bytes": self.headroom}))
        return kept, pruned

    # -- trial config / engine ----------------------------------------
    def trial_config(self, cand: Candidate) -> dict:
        cfg = json.loads(json.dumps(self.base_config))
        return deep_merge(cfg, cand.config_patch(self._grad_accum()))

    def _build_engine(self, cand: Candidate):
        import deepspeed_tpu as ds
        from ..parallel import mesh as mesh_mod
        mesh_mod.reset_topology()
        engine, _, _, _ = ds.initialize(model=self.model,
                                        config=self.trial_config(cand))
        return engine

    def _batch(self, total: int):
        if self.make_batch is None:
            raise ValueError("planner needs make_batch(total_batch) to "
                             "AOT-compile or measure candidates")
        if total not in self._batch_cache:
            self._batch_cache[total] = self.make_batch(total)
        return self._batch_cache[total]

    @staticmethod
    def _batch_seq_len(batch) -> int:
        import jax
        for leaf in jax.tree.leaves(batch):
            shape = getattr(leaf, "shape", ())
            if len(shape) >= 2:
                return int(shape[1])
        return 1

    # -- AOT facts (no dispatch) ---------------------------------------
    def _collect_facts(self, engine, batch) -> AOTFacts:
        """Compiler truth for one built engine's train step via the
        shared ``lower_compiled`` path. No step is dispatched; the
        compile lands in jax's per-signature cache, so a subsequent
        ``train_batch`` on the SAME engine reuses the executable."""
        from ..profiling.flops_profiler.profiler import (
            compiled_cost, compiled_memory, lower_compiled)
        compiled = lower_compiled(engine._train_step, engine.state,
                                  batch)
        cost = compiled_cost(compiled)
        memory = compiled_memory(compiled)
        coll = _hlo_collectives()
        records = coll.analyze_hlo(compiled.as_text(), mesh=engine.mesh)
        traffic = coll.traffic_matrix(records)
        by_axis: dict[str, float] = {}
        sites = 0
        for (axis, _op), row in traffic.items():
            by_axis[axis] = by_axis.get(axis, 0.0) + row["bytes"]
            sites += row["sites"]
        return AOTFacts(
            flops=float(cost.get("flops", 0.0)),
            bytes_accessed=float(cost.get("bytes accessed", 0.0)),
            peak_hbm_bytes=int(memory.get("peak", 0) or 0),
            memory=memory,
            collective_bytes_by_axis=by_axis,
            collective_sites=sites)

    def aot_facts(self, cand: Candidate) -> AOTFacts:
        """AOT cost/memory/collective truth for one candidate — never
        dispatches a step. Cached per trial config, so candidates whose
        configs coincide (e.g. overlap-ratio-only variants) share one
        engine build.

        Quantized-wire variants: with ``cfg.analytic_wire`` the
        fp32-wire sibling's compiled facts are transformed analytically
        (:func:`~.cost_model.quantized_wire_facts` — sharded-DP bytes
        scale by the wire ratio, the quantize/dequant bracket charges
        bytes_accessed), saving one engine build + compile per wire
        variant; otherwise the variant's own config is compiled and the
        facts are compiler truth end to end."""
        key = json.dumps(self.trial_config(cand), sort_keys=True)
        cached = self._aot_cache.get(key)
        if cached is not None:
            return cached
        if cand.wire_dtype != "fp32" and self.cfg.analytic_wire:
            from .cost_model import quantized_wire_facts
            base = self.aot_facts(
                dataclasses.replace(cand, wire_dtype="fp32"))
            facts = quantized_wire_facts(base, cand.wire_dtype)
            self._aot_cache[key] = facts
            return facts
        engine = self._build_engine(cand)
        try:
            facts = self._collect_facts(
                engine, self._batch(self.total_batch(cand)))
            self._aot_cache[key] = facts
            return facts
        finally:
            del engine
            gc.collect()

    # -- calibration ---------------------------------------------------
    def calibrate(self) -> Calibration:
        """Short measured run of the base-config candidate plus a
        second point at the grid's LARGEST micro-batch (so the fitted
        line spans the range being predicted — extrapolating a
        small-batch rate under-estimates large-batch XLA efficiency),
        fitting effective FLOPs/s + fixed per-step overhead. The base
        point's per-axis collective bytes become the comm baseline so
        the predictor charges only EXCESS collective payload (the
        fitted rate already contains the baseline's exposed comm)."""
        if self.calibration is not None:
            return self.calibration
        base = self._base_candidate()
        if base is None or self.make_batch is None:
            raise ValueError("calibration needs a resolvable base "
                             "candidate and make_batch; pass an explicit "
                             "Calibration otherwise")
        cands = [base]
        hi = max(self._micro_batches(), default=base.micro_batch)
        if hi != base.micro_batch:
            cands.append(dataclasses.replace(base, micro_batch=hi))
        elif base.micro_batch >= 2:
            cands.append(dataclasses.replace(
                base, micro_batch=base.micro_batch // 2))
        points: list[tuple[AOTFacts, float, Candidate]] = []
        for i, c in enumerate(cands):
            try:
                facts, step_s = self._facts_and_measure(
                    c, self.cfg.calibration_steps)
            except Exception:    # noqa: BLE001 — e.g. the big point OOMs
                if i == 0:
                    raise
                continue
            points.append((facts, step_s, c))
        cal = Calibration.fit([(f.flops, t) for f, t, _ in points],
                              overlap_ratio=(self.cfg.overlap_ratios
                                             or [0.71])[0],
                              headroom_bytes=self.headroom)
        ref = points[0][0]
        step_s = points[0][1]
        cal.baseline_comm_bytes_by_axis = dict(
            ref.collective_bytes_by_axis)
        if step_s > 0:
            cal.axis_algbw_bytes_per_s = {
                axis: nbytes / step_s for axis, nbytes
                in ref.collective_bytes_by_axis.items() if nbytes > 0}
        self.calibration = cal
        return cal

    # -- measured trials ----------------------------------------------
    def _timed_steps(self, engine, cand: Candidate, steps: int) -> \
            tuple[float, float]:
        """Warm up + time ``steps`` train_batch calls on an already-
        built engine, best of ``measure_windows`` windows (min
        seconds/step; short windows on a shared CPU host otherwise
        ride scheduler jitter): (seconds/step, tokens/s)."""
        import jax
        batch = self._batch(self.total_batch(cand))
        seq = self._batch_seq_len(batch)
        for _ in range(max(self.cfg.start_step, 1)):
            engine.train_batch(batch)
        jax.block_until_ready(engine.state["params"])
        n = max(int(steps), 1)
        dt = float("inf")
        for _ in range(max(self.cfg.measure_windows, 1)):
            t0 = time.perf_counter()
            for _ in range(n):
                engine.train_batch(batch)
            # deliberate per-window sync: a timing window ENDS at
            # device completion, that is the thing being measured
            jax.block_until_ready(engine.state["params"])  # graftlint: disable=GL003
            dt = min(dt, (time.perf_counter() - t0) / n)
        tokens = self.total_batch(cand) * seq
        self._trial_log.append({"label": cand.label(), "step_s": dt,
                                "tokens_per_sec": tokens / dt})
        return dt, tokens / dt

    def _measure(self, cand: Candidate, steps: int) -> tuple[float, float]:
        """Hermetic in-process trial: (seconds/step, tokens/s)."""
        engine = self._build_engine(cand)
        try:
            return self._timed_steps(engine, cand, steps)
        finally:
            del engine
            gc.collect()

    def _facts_and_measure(self, cand: Candidate, steps: int) -> \
            tuple[AOTFacts, float]:
        """Calibration helper: ONE engine serves both the AOT facts and
        the timed steps — ``lower_compiled`` compiles the engine's own
        jitted step, so the measured dispatches hit jax's executable
        cache instead of paying a second compile."""
        key = json.dumps(self.trial_config(cand), sort_keys=True)
        engine = self._build_engine(cand)
        try:
            facts = self._aot_cache.get(key)
            if facts is None:
                facts = self._collect_facts(
                    engine, self._batch(self.total_batch(cand)))
                self._aot_cache[key] = facts
            step_s, _tps = self._timed_steps(engine, cand, steps)
            return facts, step_s
        finally:
            del engine
            gc.collect()

    # -- the full pass -------------------------------------------------
    def plan(self, measure_top_k: Optional[int] = None) -> Plan:
        try:
            return self._plan_impl(measure_top_k)
        finally:
            # candidate engine builds plumbed their remat policies into
            # the (shared) model config; hand it back as we found it
            mcfg = getattr(self.model, "config", None)
            if mcfg is not None and hasattr(mcfg, "remat_policy"):
                mcfg.remat_policy = self._base_remat_policy
                mcfg.remat = self._base_remat_on

    def _plan_impl(self, measure_top_k: Optional[int] = None) -> Plan:
        cfg = self.cfg
        k = cfg.measure_top_k if measure_top_k is None else measure_top_k
        cal = self.calibration
        if cal is None:
            if k > 0 or cfg.calibrate:
                cal = self.calibrate()
            else:
                from ..accelerator import get_accelerator
                peak = float(get_accelerator().peak_flops())
                # uncalibrated fallback: accelerator peak x a generic
                # 0.4 efficiency — ranks, but don't trust absolutes
                cal = Calibration(flops_per_s=peak * 0.4,
                                  source="device-table")
        cost_model = CostModel(cal)
        cands = self.enumerate_candidates()
        kept, pruned = self.prune(cands)
        rows: list[dict] = []
        dims = self.model_dims
        for c in kept:
            row = c.to_dict()
            row["config_patch"] = c.config_patch(self._grad_accum())
            mm = self.memory_model(c)
            row["modeled_bytes"] = mm.total_bytes(
                c.zero_stage, micro_batch=c.micro_batch,
                seq_len=dims.get("seq_len", 0),
                hidden=dims.get("hidden", 0),
                num_layers=dims.get("num_layers", 0),
                remat_policy=c.remat_policy,
                offload_ratio=c.offload_ratio,
                vocab_size=dims.get("vocab_size", 0))
            try:
                facts = self.aot_facts(c)
            except Exception as e:    # noqa: BLE001 — invalid combos rank out
                row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
                rows.append(row)
                continue
            row["aot"] = facts.to_dict()
            row["memory_audit"] = mm.audit(row["modeled_bytes"],
                                           facts.memory)
            pred = cost_model.predict(facts, c.overlap_ratio)
            # tokens from the REAL batch shape (cached by aot_facts) so
            # predicted and measured tokens/s share a denominator; the
            # model's max_seq_len is only the no-batch fallback
            if self.make_batch is not None:
                seq = self._batch_seq_len(
                    self._batch(self.total_batch(c)))
            else:
                seq = dims.get("seq_len", 1)
            tokens = self.total_batch(c) * max(seq, 1)
            row["predicted_step_ms"] = round(pred["step_s"] * 1e3, 4)
            row["predicted"] = {kk: round(vv, 6)
                                for kk, vv in pred.items()}
            row["predicted_tokens_per_sec"] = round(
                tokens / pred["step_s"], 2) if pred["step_s"] > 0 else 0.0
            row["total_batch"] = self.total_batch(c)
            rows.append(row)
        for c, why in pruned:
            row = c.to_dict()
            row["pruned"] = why
            rows.append(row)
        # rank: AOT-scored rows by predicted throughput (desc), ties on
        # label; then errors; then pruned
        def order(row):
            if row.get("pruned"):
                grp = 2
            elif row.get("error"):
                grp = 1
            else:
                grp = 0
            return (grp, -row.get("predicted_tokens_per_sec", 0.0),
                    row["label"])
        rows.sort(key=order)
        for rank, row in enumerate(rows):
            if not row.get("pruned") and not row.get("error"):
                row["rank"] = rank + 1

        ranked = [r for r in rows if "rank" in r]
        if k > 0 and self.make_batch is not None:
            targets = ranked[: int(k)]
            base = self._base_candidate()
            # match by full candidate key, not label — labels omit the
            # overlap ratio, so label-matching could hand the base's
            # measurement to a different overlap variant's row
            if base is not None and all(
                    self._row_candidate(r) != base for r in targets):
                extra = [r for r in ranked
                         if self._row_candidate(r) == base]
                targets = targets + extra[:1]
            for row in targets:
                cand = self._row_candidate(row)
                steps = max(cfg.end_step - cfg.start_step, 1)
                try:
                    step_s, tps = self._measure(cand, steps)
                except Exception as e:   # noqa: BLE001 — OOM etc.
                    row["measure_error"] = \
                        f"{type(e).__name__}: {str(e)[:200]}"
                    continue
                row["measured_step_ms"] = round(step_s * 1e3, 4)
                row["measured_tokens_per_sec"] = round(tps, 2)
                if row.get("predicted_step_ms"):
                    row["prediction_rel_err"] = round(
                        abs(row["predicted_step_ms"]
                            - row["measured_step_ms"])
                        / row["measured_step_ms"], 4)

        chosen_idx = self._choose(rows)
        chosen_patch = (rows[chosen_idx]["config_patch"]
                        if chosen_idx >= 0 else {})
        info = {"num_params": self.num_params, **self.model_dims,
                "model": type(self.model).__name__,
                "compute_dtype_bytes": self._compute_dtype_bytes()}
        plan = Plan(n_devices=self.n_devices, model_info=info,
                    calibration=cal.to_dict(),
                    candidates=rows, chosen_index=chosen_idx,
                    chosen_patch=chosen_patch,
                    base_config=json.loads(json.dumps(self.base_config)))
        if cfg.plan_path:
            plan.save(cfg.plan_path)
        return plan

    def _row_candidate(self, row: dict) -> Candidate:
        return Candidate(mesh=tuple(sorted(row["mesh"].items())),
                         micro_batch=row["micro_batch"],
                         zero_stage=row["zero_stage"],
                         remat_policy=row["remat_policy"],
                         offload_ratio=row["offload_ratio"],
                         overlap_ratio=row["overlap_ratio"],
                         wire_dtype=row.get("wire_dtype", "fp32"),
                         moe_capacity_factor=row.get(
                             "moe_capacity_factor", 0.0),
                         moe_wire=row.get("moe_wire", "fp32"))

    @staticmethod
    def _choose(rows: list[dict]) -> int:
        measured = [(r["measured_tokens_per_sec"], i)
                    for i, r in enumerate(rows)
                    if r.get("measured_tokens_per_sec")]
        if measured:
            return max(measured)[1]
        for i, r in enumerate(rows):
            if "rank" in r:
                return i
        return -1

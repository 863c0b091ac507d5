"""SPMD pipeline parallelism over the ``pp`` mesh axis.

The reference implements pipelining as an eager instruction interpreter
(runtime/pipe/engine.py:1408 _exec_schedule) with NCCL p2p between stage
processes. The TPU translation compiles the whole pipeline into one XLA
program: layers are stacked ``[pp, L/pp, ...]`` with the stage dim manual
over ``pp`` (everything else — dp/fsdp/tp/sp — stays under GSPMD), and
``lax.scan`` ticks move microbatch activations between stages with
``ppermute``.

Stage 0 embeds its microbatch *inside* the manual region and the last
stage computes the per-microbatch cross-entropy there too, so no
full-batch activation or logits tensor ever exists: the embedding table
rides into the region replicated (weights, not pp x activations), and the
loss is an average of per-microbatch means — the same aggregation the
reference uses (pipe/engine.py:583 _aggregate_total_loss).

Two schedules (config ``pipeline.schedule``):

- **gpipe** (default): one differentiable scan over M + pp - 1 ticks;
  autodiff reverses it into the backward pipeline. Per-device activation
  residency is (M ticks) x (stage's layers) x (microbatch) = the flat
  run's footprint divided by pp. No recompute.
- **1f1b**: the reference ``TrainSchedule`` parity discipline
  (runtime/pipe/schedule.py:189) hand-scheduled inside a ``custom_vjp``:
  a half-tick clock where stage s forwards microbatch m at tick 2m+s and
  backwards it at tick 2m+2pp-1-s (opposite parity, so each stage runs
  exactly one forward OR one backward unit per tick under ``lax.cond``).
  In-flight microbatches per stage are bounded by the stage depth
  (<= pp); only stage *inputs* are ring-buffered and the backward
  re-runs the stage forward per microbatch (the Megatron-style
  activation-checkpointing regime the reference pipeline is normally run
  under) — activation residency is pp x one microbatch activation,
  independent of M.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ...models.transformer import _remat_policy, _unpack_batch
from ...ops.layers import cross_entropy_loss
from ...utils.jax_compat import shard_map

PyTree = Any


class PipelinedDecoderLM:
    """Wrap a DecoderLM-family model for pipeline execution.

    Parameters stay in the original ``[L, ...]`` layout (the engine's
    sharding plan pins dim 0 of layer stacks to ``pp``); apply()/loss()
    reshape views to ``[pp, L/pp, ...]`` which is a local no-op under
    that sharding.
    """

    def __init__(self, model, mesh, num_stages: int, num_microbatches: int,
                 schedule: str = "gpipe"):
        self.inner = model
        self.config = model.config
        self.mesh = mesh
        self.num_stages = num_stages
        self.num_microbatches = num_microbatches
        self.schedule = schedule
        if getattr(model.config, "total_ut_steps", 1) > 1:
            raise NotImplementedError(
                f"{type(model).__name__} is a looped stack: its hidden "
                f"state would go round the ring of stages total_ut_steps = "
                f"{model.config.total_ut_steps} times a forward pass, and "
                f"the schedules here make one trip")
        L = model.config.num_layers
        if L % num_stages != 0:
            raise ValueError(
                f"num_layers {L} must divide into {num_stages} stages")

    # engine hooks
    def init(self, rng):
        return self.inner.init(rng)

    def partition_rules(self):
        return self.inner.partition_rules()

    # ------------------------------------------------------------------
    def _split(self, params):
        """(stage-stacked layer params, head params: everything else)."""
        pp = self.num_stages
        per_stage = self.inner.config.num_layers // pp
        stage_params = jax.tree.map(
            lambda l: l.reshape(pp, per_stage, *l.shape[1:]),
            params["layers"])
        head_params = {k: v for k, v in params.items() if k != "layers"}
        return stage_params, head_params

    def _stage_unit(self, attn_fn):
        """One pipeline work unit, identical SPMD code on every stage:
        (maybe-embed) -> this stage's layers -> (maybe norm+logits+CE).
        Returns (h_out, per-unit loss term). The embed lookup runs on all
        stages (a cheap gather; jnp.where selects), but the logits matmul
        + CE run only on the last stage via lax.cond."""
        model = self.inner
        c = model.config
        pp = self.num_stages

        def unit(stage_p, head_p, x_in, tok_m, tgt_m):
            stage = lax.axis_index("pp")
            x_emb = model.embed(head_p, tok_m).astype(x_in.dtype)
            x = jnp.where(stage == 0, x_emb, x_in)

            def body(carry, layer_p):
                h, aux = carry
                h, a = model.block(layer_p, h, attn_fn=attn_fn)
                return (h, aux + a), None

            if c.remat and c.remat_policy != "segments":
                body = jax.checkpoint(body, prevent_cse=False,
                                      policy=_remat_policy(c.remat_policy))
            (h, aux), _ = lax.scan(
                body, (x, jnp.zeros((), jnp.float32)), stage_p)

            def loss_branch(h):
                z = model.unembed(head_p, h)
                return h, cross_entropy_loss(z, tgt_m)

            def pass_branch(h):
                return h, jnp.zeros((), jnp.float32)

            h_out, ce = lax.cond(stage == pp - 1, loss_branch, pass_branch,
                                 h)
            return h_out, ce + model.aux_loss_coef() * aux

        return unit

    def _perms(self):
        pp = self.num_stages
        fwd = [(i, i + 1) for i in range(pp - 1)]
        bwd = [(i, i - 1) for i in range(1, pp)]
        return fwd, bwd

    # ------------------------------------------------------------ loss
    def loss(self, params, batch, *, attn_fn=None):
        tokens, targets = _unpack_batch(batch)
        if self.schedule == "1f1b":
            return self._loss_1f1b(params, tokens, targets, attn_fn)
        return self._loss_gpipe(params, tokens, targets, attn_fn)

    def _microbatch(self, tokens, targets):
        M = self.num_microbatches
        B, S = tokens.shape
        if B % M != 0:
            raise ValueError(f"batch {B} must divide microbatches {M}")
        mb = B // M
        return (tokens.reshape(M, mb, S), targets.reshape(M, mb, S), mb, S)

    def _loss_gpipe(self, params, tokens, targets, attn_fn):
        """Differentiable pipelined loss: autodiff reverses the tick scan
        into the backward pipeline."""
        model = self.inner
        pp = self.num_stages
        M = self.num_microbatches
        tok_mb, tgt_mb, mb, S = self._microbatch(tokens, targets)
        D = model.config.hidden_size
        dtype = params["embed"]["tokens"].dtype
        stage_params, head_params = self._split(params)
        unit = self._stage_unit(attn_fn)
        fwd_perm, _ = self._perms()
        T = M + pp - 1

        def pipe_body(stage_p, head_p, tok, tgt):
            stage_p = jax.tree.map(lambda l: l[0], stage_p)
            head_p = jax.tree.map(lambda l: l[0], head_p)
            stage = lax.axis_index("pp")

            def tick(carry, t):
                act, lacc = carry
                m = jnp.clip(t - stage, 0, M - 1)
                valid = (t >= stage) & (t - stage < M)
                h_out, l_m = unit(stage_p, head_p,
                                  act,
                                  lax.dynamic_index_in_dim(tok, m, 0, False),
                                  lax.dynamic_index_in_dim(tgt, m, 0, False))
                lacc = lacc + jnp.where(valid, l_m, 0.0)
                act = lax.ppermute(h_out, "pp", fwd_perm)
                return (act, lacc), None

            act0 = jnp.zeros((mb, S, D), dtype)
            (_, lacc), _ = lax.scan(
                tick, (act0, jnp.zeros((), jnp.float32)), jnp.arange(T))
            # per-stage partial losses stacked on pp and summed OUTSIDE
            # the manual region: a psum here hits an XLA partitioner
            # crash ("Invalid binary instruction opcode copy") on
            # psum-of-masked-select across a partial-manual axis
            return lacc[None]

        # head params ride a pp-stacked leading dim (an HLO broadcast the
        # partitioner slices per stage — still one copy per device): a
        # replicated P() input would make the shard_map transpose insert
        # a psum inside the manual region for their gradients, hitting
        # the partitioner crash above; the broadcast transpose instead
        # sums the stacked cotangent in the outer GSPMD context.
        head_pp = jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (pp, *l.shape)),
            head_params)
        pipe = shard_map(
            pipe_body, mesh=self.mesh, axis_names={"pp"},
            in_specs=(jax.tree.map(lambda _: P("pp"), stage_params),
                      jax.tree.map(lambda _: P("pp"), head_params),
                      P(), P()),
            out_specs=P("pp"), check_vma=False)
        losses = pipe(stage_params, head_pp, tok_mb, tgt_mb)
        return jnp.sum(losses) / M

    def _loss_1f1b(self, params, tokens, targets, attn_fn):
        """Reference-TrainSchedule 1F1B inside a custom_vjp: forward rule
        runs the interleaved fwd/bwd schedule and stashes the parameter
        gradients as residuals; the backward rule scales them by the
        upstream cotangent. In-flight state per stage = a ring of <= pp+1
        stage inputs; stage forwards are recomputed in their backward
        unit (jax.vjp on the saved input)."""
        tok_mb, tgt_mb, mb, S = self._microbatch(tokens, targets)

        @jax.custom_vjp
        def pipe_loss(p):
            # primal (eval) path: forward ticks only
            return self._loss_gpipe(
                p, tokens, targets, attn_fn)

        def fwd(p):
            loss, grads = self._run_1f1b(p, tok_mb, tgt_mb, mb, S, attn_fn)
            return loss, grads

        def bwd(grads, ct):
            return (jax.tree.map(
                lambda g: (g * ct).astype(g.dtype), grads),)

        pipe_loss.defvjp(fwd, bwd)
        return pipe_loss(params)

    def _run_1f1b(self, params, tok_mb, tgt_mb, mb, S, attn_fn):
        model = self.inner
        pp = self.num_stages
        M = self.num_microbatches
        D = model.config.hidden_size
        dtype = params["embed"]["tokens"].dtype
        stage_params, head_params = self._split(params)
        unit = self._stage_unit(attn_fn)
        fwd_perm, bwd_perm = self._perms()
        depth = pp + 1          # ring slots; slot pp is the trash slot
        T = 2 * (M + pp - 1)    # half-tick clock, reference schedule.py:189

        def pipe_body(stage_p, head_p, tok, tgt):
            stage_p = jax.tree.map(lambda l: l[0], stage_p)
            stage = lax.axis_index("pp")
            last = pp - 1

            def fwd_unit(sp, hp, x_in, m):
                tok_m = lax.dynamic_index_in_dim(tok, m, 0, False)
                tgt_m = lax.dynamic_index_in_dim(tgt, m, 0, False)
                return unit(sp, hp, x_in, tok_m, tgt_m)

            def bwd_unit(sp, hp, x_in, m, d_out, d_loss):
                # recompute the stage forward, then pull cotangents back
                _, vjp_fn = jax.vjp(
                    lambda sp_, hp_, x_: fwd_unit(sp_, hp_, x_, m),
                    sp, hp, x_in)
                return vjp_fn((d_out, d_loss))

            gsp0 = jax.tree.map(jnp.zeros_like, stage_p)
            ghp0 = jax.tree.map(jnp.zeros_like, head_p)
            zeros_unit = (jnp.zeros((mb, S, D), dtype),
                          jnp.zeros((), jnp.float32))

            def tick(carry, k):
                act, cot, ring, gsp, ghp, lacc = carry
                # forward: mb m at k = 2m + stage (parity k+stage even)
                m_f = (k - stage) // 2
                valid_f = ((k >= stage) & ((k - stage) % 2 == 0)
                           & (m_f < M))
                m_f_c = jnp.clip(m_f, 0, M - 1)
                # backward: mb m at k = 2m + 2pp - 1 - stage
                off = 2 * pp - 1 - stage
                m_b = (k - off) // 2
                valid_b = (k >= off) & ((k - off) % 2 == 0) & (m_b < M)
                m_b_c = jnp.clip(m_b, 0, M - 1)
                read_slot = jnp.where(valid_b, m_b_c % pp, pp)
                x_saved = ring[read_slot]

                def do_fwd(_):
                    h_out, l_m = fwd_unit(stage_p, head_p, act, m_f_c)
                    return (h_out, jnp.where(valid_f, l_m, 0.0),
                            gsp0, ghp0, jnp.zeros((mb, S, D), dtype))

                def do_bwd(_):
                    d_out = jnp.where(stage == last,
                                      jnp.zeros_like(cot), cot)
                    # every stage's unit loss term feeds the total (CE on
                    # the last stage, MoE router aux on ALL stages) — the
                    # scalar cotangent is 1 everywhere, not just on last
                    d_loss = jnp.ones((), jnp.float32)
                    dsp, dhp, dx = bwd_unit(stage_p, head_p, x_saved,
                                            m_b_c, d_out, d_loss)
                    return (zeros_unit[0], zeros_unit[1], dsp, dhp, dx)

                h_out, l_m, dsp, dhp, dx = lax.cond(
                    valid_b, do_bwd, do_fwd, operand=None)

                # stash this tick's forward input for its backward unit
                write_slot = jnp.where(valid_f, m_f_c % pp, pp)
                ring = lax.dynamic_update_index_in_dim(
                    ring, act, write_slot, 0)
                gsp = jax.tree.map(lambda a, b: a + b, gsp, dsp)
                ghp = jax.tree.map(lambda a, b: a + b, ghp, dhp)
                lacc = lacc + l_m
                act_next = lax.ppermute(h_out, "pp", fwd_perm)
                cot_next = lax.ppermute(dx, "pp", bwd_perm)
                return (act_next, cot_next, ring, gsp, ghp, lacc), None

            carry0 = (jnp.zeros((mb, S, D), dtype),
                      jnp.zeros((mb, S, D), dtype),
                      jnp.zeros((depth, mb, S, D), dtype),
                      gsp0, ghp0, jnp.zeros((), jnp.float32))
            (act, cot, ring, gsp, ghp, lacc), _ = lax.scan(
                tick, carry0, jnp.arange(T))
            # stack per-stage partials on pp; reduced outside the manual
            # region (in-region psum crashes the SPMD partitioner — see
            # _loss_gpipe note)
            return (lacc[None],
                    jax.tree.map(lambda g: g[None], gsp),
                    jax.tree.map(lambda g: g[None], ghp))

        pipe = shard_map(
            pipe_body, mesh=self.mesh, axis_names={"pp"},
            in_specs=(jax.tree.map(lambda _: P("pp"), stage_params),
                      jax.tree.map(lambda _: P(), head_params), P(), P()),
            out_specs=(P("pp"),
                       jax.tree.map(lambda _: P("pp"), stage_params),
                       jax.tree.map(lambda _: P("pp"), head_params)),
            check_vma=False)
        losses, gsp, ghp = pipe(stage_params, head_params, tok_mb, tgt_mb)
        L = model.config.num_layers
        grads = jax.tree.map(lambda g: jnp.sum(g, axis=0) / M, ghp)
        grads["layers"] = jax.tree.map(
            lambda g, l: (g.reshape(L, *l.shape[1:]) / M).astype(l.dtype),
            gsp, params["layers"])
        return jnp.sum(losses) / M, grads

    # ------------------------------------------------------------ apply
    def apply(self, params, tokens, *, attn_fn=None, return_aux=False):
        """Forward-only pipelined apply returning full logits (eval /
        inference path — training uses loss() which never materializes
        them)."""
        model = self.inner
        pp = self.num_stages
        M = self.num_microbatches
        B, S = tokens.shape
        if B % M != 0:
            raise ValueError(f"batch {B} must divide microbatches {M}")
        mb = B // M
        D = model.config.hidden_size
        dtype = params["embed"]["tokens"].dtype
        stage_params, head_params = self._split(params)
        fwd_perm, _ = self._perms()
        T = M + pp - 1
        tok_mb = tokens.reshape(M, mb, S)

        def stage_fwd(sp, hp, x_in, tok_m, stage):
            x_emb = model.embed(hp, tok_m).astype(x_in.dtype)
            x = jnp.where(stage == 0, x_emb, x_in)

            def body(carry, layer_p):
                h, aux = carry
                h, a = model.block(layer_p, h, attn_fn=attn_fn)
                return (h, aux + a), None

            (h, aux), _ = lax.scan(
                body, (x, jnp.zeros((), jnp.float32)), sp)
            return h, aux

        def pipe_body(stage_p, head_p, tok):
            stage_p = jax.tree.map(lambda l: l[0], stage_p)
            stage = lax.axis_index("pp")

            def tick(carry, t):
                act, out, aux = carry
                m = jnp.clip(t - stage, 0, M - 1)
                valid = (t >= stage) & (t - stage < M)
                h, a = stage_fwd(stage_p, head_p, act,
                                 lax.dynamic_index_in_dim(tok, m, 0, False),
                                 stage)
                aux = aux + jnp.where(valid, a, 0.0)
                is_out = (stage == pp - 1) & valid
                out = lax.dynamic_update_index_in_dim(
                    out, jnp.where(is_out, h, out[m]), m, 0)
                act = lax.ppermute(h, "pp", fwd_perm)
                return (act, out, aux), None

            act0 = jnp.zeros((mb, S, D), dtype)
            out0 = jnp.zeros((M, mb, S, D), dtype)
            (_, out, aux), _ = lax.scan(
                tick, (act0, out0, jnp.zeros((), jnp.float32)),
                jnp.arange(T))
            return out[None], aux[None]

        pipe = shard_map(
            pipe_body, mesh=self.mesh, axis_names={"pp"},
            in_specs=(jax.tree.map(lambda _: P("pp"), stage_params),
                      jax.tree.map(lambda _: P(), head_params), P()),
            out_specs=(P("pp"), P("pp")), check_vma=False)
        out, aux = pipe(stage_params, head_params, tok_mb)
        out = out[-1]            # last stage holds the real activations
        aux = jnp.sum(aux) / max(M, 1)
        logits = model.unembed(params, out.reshape(B, S, D))
        return (logits, aux) if return_aux else logits


class PipelinedSpecStack:
    """Pipeline a heterogeneous ``LayerSpec`` list over pp stages.

    The reference partitions arbitrary LayerSpec lists across stage
    processes (module.py:391) and p2p-ships activations with a tensor-meta
    handshake (engine.py:928). The SPMD translation runs every stage's
    program on every device inside one compiled region and selects the
    local stage's branch with ``lax.switch`` on the pp axis index — the
    compiled analogue of "each rank builds only its own layers". Params
    ride a pp-stacked broadcast (one copy per device; see _loss_gpipe's
    partitioner-crash note) so tied-weight gradients sum across stages in
    the outer GSPMD context, which IS the reference's tied-weight
    all-reduce (module.py:459).

    Constraint of the compiled translation: every stage boundary must
    carry the same activation shape/dtype (checked up front with
    eval_shape) — shape-changing layers (e.g. a classifier head) must sit
    entirely inside one stage; adjust the partition if the check trips.
    """

    def __init__(self, spec_stack, module, mesh, num_stages: int,
                 num_microbatches: int):
        self.inner = spec_stack
        self.module = module
        self.config = None
        self.mesh = mesh
        self.num_stages = num_stages
        self.num_microbatches = num_microbatches
        self.bounds = module.partition_layers(num_stages)

    def init(self, rng):
        return self.inner.init(rng)

    def partition_rules(self):
        return self.inner.partition_rules()

    def _stage_fn(self, s: int):
        lo, hi = self.bounds[s], self.bounds[s + 1]
        return lambda params, x: self.inner.apply_range(params, x, lo, hi)

    def _check_boundaries(self, params, x_mb):
        """Boundary activations must be shape-uniform for the compiled
        carry; probe every stage with eval_shape and fail with a clear
        message."""
        shape = jax.eval_shape(self._stage_fn(0), params, x_mb)
        for s in range(1, self.num_stages):
            try:
                out = jax.eval_shape(self._stage_fn(s), params, shape)
            except Exception as e:
                raise ValueError(
                    f"stage {s} (layers {self.bounds[s]}:"
                    f"{self.bounds[s + 1]}) cannot consume the boundary "
                    f"activation {shape.shape}/{shape.dtype}: {e}; "
                    f"shape-changing layers must stay inside one stage — "
                    f"adjust partition_method or num_stages (boundaries "
                    f"{self.bounds})") from e
            if (s < self.num_stages - 1
                    and (out.shape, out.dtype) != (shape.shape,
                                                   shape.dtype)):
                raise ValueError(
                    f"stage {s} output {out.shape}/{out.dtype} differs "
                    f"from the stage-0 boundary {shape.shape}/"
                    f"{shape.dtype}; shape-changing layers must stay "
                    f"inside one stage — adjust partition_method or "
                    f"num_stages (boundaries {self.bounds})")
        return shape

    def loss(self, params, batch, **_kw):
        if self.module.loss_fn is None:
            raise ValueError("LayerSpec pipelines need loss_fn=")
        inputs, labels = batch
        pp = self.num_stages
        M = self.num_microbatches
        B = inputs.shape[0]
        if B % M != 0:
            raise ValueError(f"batch {B} must divide microbatches {M}")
        mb = B // M
        in_mb = inputs.reshape(M, mb, *inputs.shape[1:])
        lb_mb = labels.reshape(M, mb, *labels.shape[1:])
        bshape = self._check_boundaries(
            params, jax.ShapeDtypeStruct((mb, *inputs.shape[1:]),
                                         inputs.dtype))
        loss_fn = self.module.loss_fn
        stage_fns = [self._stage_fn(s) for s in range(pp)]
        fwd_perm = [(i, i + 1) for i in range(pp - 1)]
        T = M + pp - 1

        def pipe_body(params_pp, inp, lab):
            local = jax.tree.map(lambda l: l[0], params_pp)
            stage = lax.axis_index("pp")

            def tick(carry, t):
                act, lacc = carry
                m = jnp.clip(t - stage, 0, M - 1)
                valid = (t >= stage) & (t - stage < M)
                x0 = lax.dynamic_index_in_dim(inp, m, 0, False)
                lb = lax.dynamic_index_in_dim(lab, m, 0, False)

                def make_branch(s):
                    def branch(act):
                        x = x0 if s == 0 else act
                        h = stage_fns[s](local, x)
                        if s == pp - 1:
                            return (jnp.zeros(bshape.shape, bshape.dtype),
                                    jnp.asarray(loss_fn(h, lb),
                                                jnp.float32).reshape(()))
                        return h, jnp.zeros((), jnp.float32)
                    return branch

                h_out, l_m = lax.switch(
                    stage, [make_branch(s) for s in range(pp)], act)
                lacc = lacc + jnp.where(valid, l_m, 0.0)
                act = lax.ppermute(h_out, "pp", fwd_perm)
                return (act, lacc), None

            act0 = jnp.zeros(bshape.shape, bshape.dtype)
            (_, lacc), _ = lax.scan(
                tick, (act0, jnp.zeros((), jnp.float32)), jnp.arange(T))
            return lacc[None]

        params_pp = jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (pp, *l.shape)), params)
        pipe = shard_map(
            pipe_body, mesh=self.mesh, axis_names={"pp"},
            in_specs=(jax.tree.map(lambda _: P("pp"), params), P(), P()),
            out_specs=P("pp"), check_vma=False)
        losses = pipe(params_pp, in_mb, lb_mb)
        return jnp.sum(losses) / M

    def apply(self, params, x, **kw):
        """Non-pipelined whole-graph apply (eval convenience)."""
        return self.inner.apply(params, x, **kw)

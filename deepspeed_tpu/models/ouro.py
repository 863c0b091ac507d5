"""Ouro family: a looped decoder. The same stack of layers runs several
times a forward pass, and every pass is an exit.

``Ouro-2.6B`` (ByteDance, ``config.json``, ``model_type`` ``ouro``; the
LoopLM paper, arXiv 2510.25741): 48 sandwich-norm layers of hidden 2048,
16 heads of 128 (no grouping), SwiGLU 5632, rotary at 1e6, vocabulary
49152 untied, ``total_ut_steps`` 4::

    h(0) = E[tokens]
    for t = 1..T:                    the SAME L layers' weights every t
      u = h(t-1)
      for l = 1..L:
        a = u + rmsnorm(Attn_l(rmsnorm(u)))      a second norm on each
        u = a + rmsnorm(SwiGLU_l(rmsnorm(a)))    sublayer's OUTPUT
      h(t)      = rmsnorm_f(u)       the ONE final norm; the normed state
      logits(t) = h(t) W_head          enters pass t + 1
      lambda_t  = sigmoid(h(t) w_g + b_g)        the exit gate, d -> 1

    p_t = lambda_t prod_{j<t} (1 - lambda_j)  (t < T);  p_T = what is left
    loss = mean over positions of [ sum_t p_t nll_t - beta H(p) ]

``nll_t`` is the next-token cross-entropy of ``logits(t)`` and ``H`` the
entropy of the exit distribution (the paper's stage-I objective; ``beta``
is ``exit_entropy_beta``). Rotary sees the same positions in every pass.
``apply`` returns ``logits(T)``: an ``early_exit_threshold`` of 1 is
reached by the cumulative ``p`` only at the last pass.

**The loop** (``_exit_states``) is a ``lax.scan`` over the passes ROUND
the scan over the layers, the layers' weights closed over: one compiled
pass whatever ``T``. Every layer application is rematted whole, so a step
keeps ``T x L`` layer inputs and the ``T`` exits. A shared weight's
gradient is the sum of the passes': autodiff adds a closed-over
constant's cotangents in the constant's dtype, so under a bf16 engine the
sum over passes is made in bf16 before the engine's cast to float32
(``tests/test_ouro.py`` holds the error against the float32 reference).

**The head** runs ONCE over the ``T`` exits' rows stacked along the batch
(``transformer._chunked_weighted_cross_entropy``), with ``p`` as the rows'
weights: one float32 ``dW`` of the head lives from forward to backward,
not ``T``.

Not built: exit by the cumulative gate at inference, a KV cache (it would
hold ``T x L`` slots a token), the second training stage (the gate alone,
the LM frozen). ``block`` / ``block_decode`` / ``decode`` / ``init_cache``
refuse, and with them every engine that runs a model a layer at a time
(the pipeline, the streamed engine, serving): each would run ONE pass.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import layers as L
from ..parallel.mesh import constrain_free
from .base import ModelConfig, register_model
from .transformer import (DecoderLM, _chunk_logits, _dense_init,
                          _remat_policy, _unpack_batch, _valid_count)


@dataclasses.dataclass
class OuroConfig(ModelConfig):
    # the num_layers layers run total_ut_steps times a forward pass on the
    # SAME weights, the final norm after every pass, and every pass is an
    # exit
    total_ut_steps: int = 1
    sandwich_norm: bool = False     # a second norm on each sublayer's
    #                                 OUTPUT, before the residual add
    exit_gate: bool = False         # a d -> 1 gate on every exit's state:
    #                                 the loss is the expected loss under
    #                                 the exit distribution the gates give
    exit_entropy_beta: float = 0.0  # ... less this x that distribution's
    #                                 entropy, a position

    def num_params(self) -> int:
        """``DecoderLM``'s, the two output norms a layer and the gate."""
        d = self.hidden_size
        return (super().num_params()
                + (2 * d * self.num_layers if self.sandwich_norm else 0)
                + (d + 1 if self.exit_gate else 0))

    def _matmul_params(self) -> int:
        """Everything but the embedding's gather runs once a pass (the
        layers, the final norm, the head and the gate)."""
        n = self.num_active_params()
        again = n - (0 if self.tie_embeddings
                     else self.vocab_size * self.hidden_size)
        return n + (self.total_ut_steps - 1) * again

    def _mixer_flops(self, seq_len: int, causal: bool) -> float:
        return self.total_ut_steps * super()._mixer_flops(seq_len, causal)


def ouro_config(size: str = "2.6b", **overrides) -> OuroConfig:
    presets = {
        "tiny": dict(hidden_size=64, num_layers=2, num_heads=4,
                     num_kv_heads=4, intermediate_size=128, vocab_size=512,
                     max_seq_len=128, rope_theta=10000.0),
        "2.6b": dict(hidden_size=2048, num_layers=48, num_heads=16,
                     num_kv_heads=16, intermediate_size=5632,
                     vocab_size=49152, max_seq_len=65536,
                     rope_theta=1000000),
    }
    base = dict(norm_type="rmsnorm", activation="swiglu",
                position_embedding="rope", use_bias=False,
                tie_embeddings=False, norm_eps=1e-6, total_ut_steps=4,
                sandwich_norm=True, exit_gate=True, exit_entropy_beta=0.1)
    base.update(presets[size])
    base.update(overrides)
    return OuroConfig(**base)


@register_model("ouro")
class Ouro(DecoderLM):
    def __init__(self, config: OuroConfig | None = None,
                 size: str | None = None, **overrides):
        if config is not None and (size is not None or overrides):
            raise ValueError(
                "pass either an explicit config or size/overrides, not both")
        c = config or ouro_config(size or "2.6b", **overrides)
        if c.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps {c.total_ut_steps}: a looped "
                             f"stack runs at least once")
        if (not (c.sandwich_norm and c.exit_gate) or c.use_bias
                or c.norm_type != "rmsnorm" or c.activation != "swiglu"
                or c.position_embedding != "rope" or c.parallel_residual
                or c.sliding_window is not None or c.num_experts
                or c.tie_embeddings or c.remat_policy == "segments"):
            raise NotImplementedError(
                "Ouro is a looped stack of sandwich-norm RMSNorm / rotary / "
                "SwiGLU layers with full attention, no bias, an untied "
                "head and an exit gate, each layer application rematted "
                "whole")
        super().__init__(c)

    # what runs a model a layer, or a token, at a time would run ONE pass
    def _looped_only(self, *a, **kw):
        raise NotImplementedError(
            f"{type(self).__name__} is a looped stack (its layers run "
            f"total_ut_steps = {self.config.total_ut_steps} times a forward "
            f"pass): it runs through apply/loss only. A KV cache would hold "
            f"total_ut_steps x num_layers slots a token and a pipeline "
            f"would send the state round its ring of stages once a pass; "
            f"neither is built")

    block = block_decode = decode = init_cache = _looped_only

    # ---------------- init ----------------
    def init(self, rng: jax.Array):
        """``DecoderLM``'s weights, the two output norms a layer, and the
        gate: ``w_g`` normal(0, d^-1/2), so that on a normed state (unit
        rms) the gate's logits have unit variance and ``lambda`` differs
        by position and pass; ``b_g`` zero."""
        c = self.config
        dt = c.param_dtype
        d = c.hidden_size
        params = super().init(rng)
        for name in ("ln1_out_scale", "ln2_out_scale"):
            params["layers"][name] = jnp.ones((c.num_layers, d), dt)
        params["exit_gate"] = {
            "w": _dense_init(jax.random.fold_in(rng, 1), (d,), d ** -0.5,
                             dt),
            "b": jnp.zeros((), dt)}
        return params

    # ---------------- one layer, one pass, the loop ----------------
    def _layer(self, p, x, attn_fn, positions):
        """A sandwich-norm layer; each output norm inside its sublayer's
        device scope."""
        with jax.named_scope("ds.attn"):
            h = self._norm(x, p["ln1_scale"])
            rotary = self._hands_rotary(attn_fn, positions)
            q, k, v = self._qkv(p, h, positions, rotate=not rotary)
            a = self._attn_out(p, attn_fn(q, k, v, causal=True, **rotary))
            x = x + self._norm(a, p["ln1_out_scale"])
        with jax.named_scope("ds.mlp"):
            m, _ = self._mlp(p, self._norm(x, p["ln2_scale"]))
            return x + self._norm(m, p["ln2_out_scale"])

    def _exit_states(self, params, tokens, *, attn_fn=None, positions=None,
                     act_sharding=None):
        """The ``T`` final-normed states ``[T, B, S, D]``, pass ``t``'s at
        ``t - 1``. ``act_sharding`` pins the carries of both scans to the
        ZeRO plan's layout (``DecoderLM._final_hidden`` says why)."""
        c = self.config
        pin = (functools.partial(constrain_free, sharding=act_sharding)
               if act_sharding is not None else lambda x: x)
        if attn_fn is None:
            if c.attn_impl == "flash":
                from ..ops.pallas.flash_attention import flash_attention
                attn_fn = flash_attention
            else:
                attn_fn = L.dot_product_attention
        with jax.named_scope("ds.embed"):
            x = pin(self.embed(params, tokens, positions))
        layers, final = params["layers"], params["final_norm"]["scale"]

        def one_layer(x, p):
            return pin(self._layer(p, x, attn_fn, positions)), None

        if c.remat:
            one_layer = jax.checkpoint(one_layer, prevent_cse=False,
                                       policy=_remat_policy(c.remat_policy))

        def one_pass(x, _):
            with jax.named_scope("ds.loop"):
                x, _ = jax.lax.scan(one_layer, x, layers)
                x = pin(self._norm(x, final))
            return x, x

        with jax.named_scope("ds.layers"):
            _, exits = jax.lax.scan(one_pass, x, None,
                                    length=c.total_ut_steps)
        return exits

    def _final_hidden(self, params, tokens, *, attn_fn=None, positions=None,
                      act_sharding=None):
        """Pass ``T``'s state and no auxiliary term: what ``apply``
        projects."""
        exits = self._exit_states(params, tokens, attn_fn=attn_fn,
                                  positions=positions,
                                  act_sharding=act_sharding)
        return exits[-1], jnp.zeros((), jnp.float32)

    # ---------------- the exits ----------------
    def _exit_log_probs(self, params, exits):
        """``log p`` ``[T, B, S]`` float32 of the exit distribution: from
        the gates' logits ``z``, ``log lambda = log_sigmoid(z)`` and
        ``log(1 - lambda) = log_sigmoid(-z)``, so nothing is lost where a
        gate saturates; the last pass takes what is left."""
        gate = params["exit_gate"]
        z = (jnp.einsum("tbsd,d->tbs", exits, gate["w"].astype(exits.dtype),
                        preferred_element_type=jnp.float32)
             + gate["b"].astype(jnp.float32))
        stay = jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)   # log prod(1-l)
        before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
        return jnp.concatenate(
            [jax.nn.log_sigmoid(z[:-1]) + before[:-1], before[-1:]])

    def loss(self, params, batch, *, attn_fn=None, act_sharding=None,
             with_stats: bool = False):
        """The expected next-token loss over the ``T`` exits less ``beta``
        x the exit distribution's entropy, a mean over the positions whose
        target is not ``-100``. ``with_stats`` also returns the
        micro-batch's means a pass of ``p`` and of the NLL and its mean
        entropy, for ``after_step``."""
        c = self.config
        tokens, targets = _unpack_batch(batch)
        exits = self._exit_states(params, tokens, attn_fn=attn_fn,
                                  act_sharding=act_sharding)
        t, b, s, d = exits.shape
        valid = targets != -100
        count = _valid_count(targets)
        gate_scope = functools.partial(jax.named_scope, "ds.exit_gate")
        with jax.named_scope("ds.loss_head"):
            with gate_scope():
                log_p = self._exit_log_probs(params, exits)
                p = jnp.exp(log_p)
                entropy = jnp.sum(jnp.where(
                    valid, -jnp.sum(p * log_p, axis=0), 0.0)) / count
            rows = exits.reshape(t * b, s, d)
            every = jnp.tile(targets, (t, 1))
            weights = p.reshape(t * b, s)
            if c.loss_chunk > 0:
                mixed, nll = self._chunked_ce(params, rows, every, weights)
            else:   # the plain form: one slab, autodiff's gradient
                nll = _chunk_logits(rows, every,
                                    params["lm_head"].astype(rows.dtype),
                                    None, rows=True)[-1]
                mixed = jnp.sum(weights * nll) / (t * count)
            with gate_scope():
                # ``mixed`` is a mean over the T passes' rows of weights
                # that sum to one a position
                loss = t * mixed - c.exit_entropy_beta * entropy
                if not with_stats:
                    return loss
                mean = lambda rows: jnp.sum(jnp.where(  # noqa: E731
                    valid, rows.reshape(t, b, s), 0.0), (1, 2)) / count
                stats = {"exit_prob": mean(p), "exit_nll": mean(nll),
                         "exit_entropy": entropy,
                         "micro_batches": jnp.float32(1)}
        # statistics: no gradient flows through them (the weighted head's
        # backward rule takes none for its rows)
        return loss, jax.lax.stop_gradient(stats)

    # ---------------- what the engine does with the statistics ----------
    def after_step(self, params, stats):
        """No weight moves after the optimizer's update. The step's
        statistics (``loss(with_stats=True)``'s, summed over the
        micro-batches) become its metrics, device scalars: the mean exit
        probability and NLL of each pass, the mean entropy, the passes."""
        n = stats["micro_batches"]
        t = self.config.total_ut_steps
        metrics = {"loop_passes": jnp.int32(t),
                   "exit_entropy_mean": stats["exit_entropy"] / n}
        for i in range(t):
            metrics[f"exit_prob_mean_{i + 1}"] = stats["exit_prob"][i] / n
            metrics[f"exit_nll_mean_{i + 1}"] = stats["exit_nll"][i] / n
        return params, metrics

    @staticmethod
    def record_step_metrics(reg, metrics: dict) -> None:
        """One finished step's ``after_step`` metrics into the telemetry
        registry, on the host (the engine calls this one step behind):
        gauges of the LAST finished step."""
        t = int(metrics["loop_passes"])
        reg.gauge("ds_loop_passes",
                  "passes a looped stack makes a forward pass").set(t)
        reg.gauge("ds_exit_entropy_mean",
                  "mean entropy of the exit distribution, last step").set(
                      float(metrics["exit_entropy_mean"]))
        prob = reg.gauge("ds_exit_prob_mean",
                         "mean exit probability of a pass, last step")
        nll = reg.gauge("ds_exit_nll_mean",
                        "mean next-token NLL of a pass's exit, last step")
        for i in range(1, t + 1):
            label = {"pass": str(i)}
            prob.set(float(metrics[f"exit_prob_mean_{i}"]), **label)
            nll.set(float(metrics[f"exit_nll_mean_{i}"]), **label)

    # ---------------- sharding ----------------
    def partition_rules(self):
        return super().partition_rules() + [
            (r"layers/ln\d_out_scale", P()),
            (r"exit_gate", P()),
        ]

"""Xing4.0 family: four residual streams mixed by Sinkhorn-constrained
hyper-connections (mHC) round rotated, low-rank-query latent attention, a
dense SwiGLU in the leading layers and bias-corrected sigmoid-routed
experts with one shared expert after them.

``Xing4.0-29B-A4B`` (XingChen-AGI, ``config.json``, ``model_type``
``xing4_0``): 40 layers of hidden 3584, every one latent attention (MLA:
``q_lora_rank`` 768, ``kv_lora_rank`` 512, 32 heads of ``qk_nope_head_dim``
128 + ``qk_rope_head_dim`` 64, ``v_head_dim`` 128) followed by a dense
SwiGLU of 9216 in the first ``first_k_dense_replace`` (2) layers and by 64
routed experts of 1024 (top 4, sigmoid scores with a selection bias,
renormalised, times ``routed_scaling_factor`` 2, one shared expert) in the
others. The residual path is not ``x + f(norm(x))``: the state between
sublayers is ``X`` [B, S, n, C], ``n`` = ``hc_mult`` (4) streams of the
hidden size, and each sublayer ``F`` (the attention, then the channel
mixer, each with its own ``phi``, ``b``, ``alpha``) runs as
(``ops/mhc.py``; arXiv:2512.24880 on arXiv:2409.19606)::

    u, H_post, H_res, X = mhc_pre(X, phi, b, alpha)   u = sum_i H_pre[i] X[i]
    y  = F(rmsnorm(u))                                as in any pre-norm stack
    X' = mhc_post(X, y, H_post, H_res)                H_res X + H_post^T y

(``mhc_post`` reads the ``X`` that ``mhc_pre`` hands on, so the streams have
one consumer and their two cotangents are added inside the pre pass's
backward kernel, not by an op of XLA's over [S, n C].)

The embedding is copied to all ``n`` streams before the first layer
(scope ``ds.mhc_spread``) and the streams are summed before the final norm
(``ds.mhc_fold``): both sit in ``_layer_stack`` here, so ``models/
stack.py`` and ``models/transformer.py`` ``_final_hidden`` are the other
families' as they were. The layer scan's carry is [B, S, n C], the streams
side by side in the last axis: the remat boundary and ``pin`` see three
axes as in any family, and a rematted layer saves n times the bytes.

**MLA** (on the normed ``u``; rotated, low-rank query)::

    cq = rmsnorm(u Wqa);  q = cq Wqb  as H x (nope + rope)
    [c, k_pe] = u Wkva  (kv_lora + rope);  [k_nope, v] = rmsnorm(c) Wkvb
    q_pe, k_pe rotated (rotate-half, YaRN's table at attention factor
    mscale / mscale_all_dim);   k_h = [k_nope_h, k_pe]
    y = softmax_causal(q k^T (nope + rope)^-1/2 m^2) v Wo
    m = 0.1 mscale_all_dim ln(factor) + 1

``m^2`` rides on the query's latent norm (float32 inside the norm; ``q`` is
linear in it): the attention kernels' scale is the key width's ``d^-1/2``. In training the latent is expanded
and the layer runs as H-head attention with a key of 192 and a value of
128 through the flash kernels, as Kimi-Linear's.

**Routed layers** are ``moe.sharded_moe.moe_ffn_held`` as Kimi-Linear's
call it; ``optimizer_frozen`` keeps the optimizer off the selection bias,
``after_step`` moves it against the load (``_balanced``) and hands back,
beside the held-expert counts, ``mhc_sinkhorn_residual``: the step's
largest ``|rowsum - 1|`` or ``|colsum - 1|`` of any ``H_res``.

Serving, the pipeline and the multi-token prediction module are not here
(``ROADMAP.md`` queue 2 A).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import layers as L
from ..ops import mhc
from .base import mean_context, register_model
from .stack import (LatentAttention, RoutedStackConfig, RoutedStackOfKinds,
                    mla_params)
from .transformer import _dense_init


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


@dataclasses.dataclass
class Xing4Config(RoutedStackConfig):
    # key names as published
    first_k_dense_replace: int = 0  # leading layers whose FFN is dense
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: dict | None = None  # the published group ("type": yarn)
    hc_mult: int = 4                # residual streams
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6            # the stream norm's and Sinkhorn's
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # not published (init): one value, or three (pre, post, res)
    mhc_alpha_init: float | tuple | list = 0.01  # what the alphas start from
    mhc_b_std: float | tuple | list = 0.0   # deviation the static
    #                                 coefficients are drawn with, round
    #                                 b_res's I

    def __post_init__(self):
        super().__post_init__()
        self.rope_scaling = dict(self.rope_scaling or {})
        for knob in ("mhc_alpha_init", "mhc_b_std"):
            value = getattr(self, knob)
            three = tuple(value) if isinstance(value, (tuple, list)) else (
                value,) * 3
            if len(three) != 3:
                raise ValueError(f"{knob} {value!r}: one value or three "
                                 f"(pre, post, res)")
            setattr(self, knob, tuple(float(v) for v in three))

    def layer_kinds(self) -> list[tuple[str, str]]:
        """(token mixer, channel mixer) of each layer: ``mla`` and
        ``dense`` | ``routed``."""
        return [("mla", "dense" if i < self.first_k_dense_replace
                 or self.num_experts <= 0 else "routed")
                for i in range(self.num_layers)]

    def lead_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_mscale(self) -> float:
        """YaRN's ``m``: the softmax scale carries its square."""
        rs = self.rope_scaling
        if not rs or not rs.get("mscale_all_dim"):
            return 1.0
        return _yarn_mscale(rs["factor"], rs["mscale_all_dim"])

    def rope_table_scaling(self) -> dict:
        """``rope_scaling`` as ``ops.layers.rotary_embedding(scaling=)``
        reads a section of ``rope_parameters``."""
        rs = self.rope_scaling
        if not rs:
            return {"rope_type": "default", "rope_theta": self.rope_theta}
        return {
            "rope_type": rs["type"], "rope_theta": self.rope_theta,
            "factor": rs["factor"],
            "original_max_position_embeddings":
                rs["original_max_position_embeddings"],
            "beta_fast": rs.get("beta_fast", 32),
            "beta_slow": rs.get("beta_slow", 1),
            "attention_factor": (
                _yarn_mscale(rs["factor"], rs.get("mscale", 1))
                / _yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0)))}

    def _mhc_params(self) -> int:
        """phi, b and alpha of ONE sublayer."""
        n = self.hc_mult
        return (n * self.hidden_size + 1) * n * (n + 2) + 3

    def _layer_params(self, kind) -> int:
        """As ``Xing4._init_layer`` builds a layer: the mixer with its two
        latent norms, the layer's two norms, both sublayers' mHC and the
        channel mixer (the router, its bias, the shared expert and the
        experts held)."""
        d = self.hidden_size
        if kind[1] == "dense":
            ff = 3 * d * self.intermediate_size
        else:
            ff = ((d + 1) * self.num_experts
                  + self._expert_params() * self.moe_num_shared_experts
                  + self._held_params())
        return mla_params(self) + 2 * d + 2 * self._mhc_params() + ff

    def _layer_idle_params(self, kind) -> float:
        return self._idle_held_params() if kind[1] == "routed" else 0

    def _layer_mixer_flops(self, kind, seq_len, causal) -> float:
        """Beside the 6 N (``phi`` is in N: a token's stream vector is
        multiplied with all of it): a key of qk width and a value of v
        width a visible pair and head, and the streams' own products
        (``H_pre``, ``H_res`` and ``H_post`` over n C channels, twice a
        layer); x3 for training."""
        n = self.hc_mult
        pairs = 6 * self.num_heads * mean_context(seq_len, causal) * (
            self.qk_head_dim + self.v_head_dim)
        return pairs + 3 * 2 * 2 * n * (n + 2) * self.hidden_size


_PUBLISHED = dict(
    hidden_size=3584, intermediate_size=9216, num_heads=32, num_kv_heads=32,
    num_layers=40, vocab_size=131072, max_seq_len=262144, rope_theta=10000,
    first_k_dense_replace=2, q_lora_rank=768, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64,
                  "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"},
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, num_experts=64, moe_top_k=4,
    moe_num_shared_experts=1, moe_intermediate_size=1024,
    routed_scaling_factor=2.0)


def xing4_config(size: str = "29b-a4b", **overrides) -> Xing4Config:
    presets = {
        # a leading dense layer and four routed ones under the scan; the
        # published router (the agreement check's mask depends on the
        # share of experts near the boundary) and the published number of
        # streams; YaRN from 32 positions so that 128 lie past it
        "tiny": dict(hidden_size=64, intermediate_size=128, num_heads=4,
                     num_kv_heads=4, num_layers=5, vocab_size=512,
                     max_seq_len=128, rope_theta=10000,
                     first_k_dense_replace=1, q_lora_rank=24,
                     kv_lora_rank=32, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=16,
                     rope_scaling={"beta_fast": 32, "beta_slow": 1,
                                   "factor": 64, "mscale": 1,
                                   "mscale_all_dim": 1,
                                   "original_max_position_embeddings": 32,
                                   "type": "yarn"},
                     hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
                     mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
                     num_experts=64, moe_top_k=4, moe_num_shared_experts=1,
                     moe_intermediate_size=32, routed_scaling_factor=2.0),
        "29b-a4b": _PUBLISHED,
    }
    base = dict(norm_type="rmsnorm", activation="swiglu",
                position_embedding="none", use_bias=False,
                tie_embeddings=False, norm_eps=1e-6,
                moe_router_activation="sigmoid", moe_norm_topk=True,
                router_aux_loss_coef=0.0)
    base.update(presets[size])
    base.update(overrides)
    return Xing4Config(**base)


@register_model("xing4_0")
class Xing4(LatentAttention, RoutedStackOfKinds):
    def __init__(self, config: Xing4Config | None = None,
                 size: str | None = None, **overrides):
        if config is not None and (size is not None or overrides):
            raise ValueError(
                "pass either an explicit config or size/overrides, not both")
        c = config or xing4_config(size or "29b-a4b", **overrides)
        if (c.moe_router_activation != "sigmoid" or c.tie_embeddings
                or c.use_bias or c.hc_mult < 2 or c.q_lora_rank <= 0
                or c.rope_scaling.get("type", "yarn") != "yarn"):
            raise NotImplementedError(
                "Xing4 has a sigmoid router with a selection bias, an "
                "untied head, no bias in a projection, two residual "
                "streams or more, a low-rank query and a plain or YaRN "
                "rotary table")
        if c.held_experts > c.num_experts:
            raise ValueError(
                f"{c.held_experts} experts held of the router's "
                f"{c.num_experts}")
        super().__init__(c)
        self._rope = L.latent_rotary_tables(*L.rotary_embedding(
            c.max_seq_len, c.qk_rope_head_dim, c.rope_theta,
            scaling=c.rope_table_scaling()))

    def optimizer_frozen(self) -> str:
        """Leaves the optimizer leaves alone (the engine zeroes their
        updates): the router's selection bias moves by ``after_step``."""
        return r"router_bias$"

    def after_step(self, params, stats):
        """The trainer's half of the bias-corrected router on the step's
        updated weights (``RoutedStackOfKinds._balanced``), and the step's
        largest Sinkhorn residual of any layer beside its metrics (summed
        over the micro-batches as every statistic is: an upper bound where
        a step has several)."""
        worst, routed = [], {}
        for group, slots in stats.items():
            routed[group] = {}
            for slot, counts in slots.items():
                counts = dict(counts)
                worst.append(jnp.max(counts.pop("mhc_residual")))
                if counts:
                    routed[group][slot] = counts
        params, metrics = self._balanced(params, routed)
        return params, {**metrics, "mhc_sinkhorn_residual":
                        jnp.max(jnp.stack(worst))}

    @staticmethod
    def record_step_metrics(reg, metrics: dict) -> None:
        """The routed families' recorder, and gauge
        ``ds_mhc_sinkhorn_residual``: the largest reading of any step."""
        metrics = dict(metrics)
        residual = float(metrics.pop("mhc_sinkhorn_residual"))
        RoutedStackOfKinds.record_step_metrics(reg, metrics)
        g = reg.gauge("ds_mhc_sinkhorn_residual",
                      "largest |rowsum - 1| or |colsum - 1| of any H_res "
                      "of any step: what the Sinkhorn iterations leave")
        g.set(max(g.value(), residual))

    # ---------------- init ----------------
    def _init_mhc(self, key, lead_shape):
        """One sublayer's ``phi``, ``b``, ``alpha``. ``phi`` is
        normal(0, (n C)^-1/2), so that ``xv phi`` has unit deviation at
        any width; ``b_res`` favours the identity by 1 on its diagonal."""
        c = self.config
        n, dt = c.hc_mult, c.param_dtype
        k_phi, k_b = jax.random.split(key)
        width = n * (n + 2)
        b = mhc.expand_alpha(jnp.asarray(c.mhc_b_std), n) * jax.random.normal(
            k_b, (*lead_shape, width))
        b = b.at[..., 2 * n:].add(jnp.eye(n).reshape(-1))
        return {
            "phi": _dense_init(k_phi, (*lead_shape, n * c.hidden_size, width),
                               (n * c.hidden_size) ** -0.5, dt),
            "b": b.astype(dt),
            "alpha": jnp.broadcast_to(
                jnp.asarray(c.mhc_alpha_init, dt), (*lead_shape, 3)),
        }

    def _init_layer(self, key, kind, lead_shape=()):
        c = self.config
        dt = c.param_dtype
        d = c.hidden_size
        std = 0.02
        resid_std = std / (2 * c.num_layers) ** 0.5
        ks = iter(jax.random.split(key, 20))

        def w(shape, scale=std):
            return _dense_init(next(ks), (*lead_shape, *shape), scale, dt)

        def ones(shape):
            return jnp.ones((*lead_shape, *shape), dt)

        p = {
            "ln1_scale": ones((d,)), "ln2_scale": ones((d,)),
            "hc1": self._init_mhc(next(ks), lead_shape),
            "hc2": self._init_mhc(next(ks), lead_shape),
            "mla": self._init_mla(w, ones, resid_std),
        }
        if kind[1] == "dense":
            f = c.intermediate_size
            p["mlp"] = {"w_gate": w((d, f)), "w_up": w((d, f)),
                        "w_down": w((f, d), resid_std)}
        else:
            f = c.moe_intermediate_size or c.intermediate_size
            e = c.held_experts
            fs = f * c.moe_num_shared_experts
            p["moe"] = {
                # logits of unit variance at any width, and a drawn bias so
                # that selection and weighting differ, as Kimi-Linear's
                "router": w((d, c.num_experts), d ** -0.5),
                "router_bias": w((c.num_experts,), 0.01),
                "experts": {"w_gate": w((e, d, f)), "w_up": w((e, d, f)),
                            "w_down": w((e, f, d), resid_std)},
            }
            if fs:
                p["moe"]["shared"] = {
                    "w_gate": w((d, fs)), "w_up": w((d, fs)),
                    "w_down": w((fs, d), resid_std)}
        return p

    def init(self, rng: jax.Array):
        c = self.config
        dt = c.param_dtype
        d, v = c.hidden_size, c.vocab_size
        keys = jax.random.split(rng, 3)
        return {
            "embed": {"tokens": _dense_init(keys[1], (v, d), 0.02, dt)},
            "layers": self._init_layers(keys[0]),
            "final_norm": {"scale": jnp.ones((d,), dt)},
            "lm_head": _dense_init(keys[2], (d, v), 0.02, dt),
        }

    # ---------------- the sublayers ----------------
    def _routed(self, p, h):
        from ..moe.sharded_moe import moe_ffn_held
        c = self.config
        return moe_ffn_held(
            h, p["router"], p["router_bias"], p["experts"], p.get("shared"),
            k=c.moe_top_k, renormalise=c.moe_norm_topk,
            scaling=float(c.routed_scaling_factor))

    def _sublayer(self, hc, x, f):
        """``f`` -> (y, counts) through one sublayer's stream passes:
        (X', counts, the Sinkhorn residual). ``x`` [B, S, n C]: the
        streams side by side in the last axis."""
        c = self.config
        b, s, _ = x.shape
        xs = x.reshape(b, s, c.hc_mult, c.hidden_size)
        with jax.named_scope("ds.mhc"):
            u, h_post, h_res, residual, xs = mhc.mhc_pre(
                xs, hc["phi"], hc["b"], hc["alpha"], eps=c.hc_eps,
                clamp=(float(c.mhc_h_res_clamp_min),
                       float(c.mhc_h_res_clamp_max)),
                iters=c.hc_sinkhorn_iters)
        y, counts = f(u)
        with jax.named_scope("ds.mhc"):
            out = mhc.mhc_post(xs, y, h_post, h_res)
        return out.reshape(x.shape), counts, residual

    # ---------------- one layer, the stack ----------------
    def _one_layer(self, p, x, attn_fn):
        """x [B, S, n C] -> (x, counts): a routed layer's counts, and
        every layer's ``mhc_residual``."""
        c = self.config

        def attention(u):
            with jax.named_scope("ds.attn"):
                h = L.rms_norm(u, p["ln1_scale"], c.norm_eps)
                return self._mla(p["mla"], h, attn_fn), {}

        def channel(u):
            if "mlp" in p:
                with jax.named_scope("ds.mlp"):
                    h = L.rms_norm(u, p["ln2_scale"], c.norm_eps)
                    return self._mlp(p["mlp"], h)[0], {}
            return self._routed(
                p["moe"], L.rms_norm(u, p["ln2_scale"], c.norm_eps))

        x, _, r1 = self._sublayer(p["hc1"], x, attention)
        x, counts, r2 = self._sublayer(p["hc2"], x, channel)
        return x, {**counts, "mhc_residual": jnp.maximum(r1, r2)}

    def _mixers(self, attn_fn, act_sharding):
        return attn_fn

    def _layer_stack(self, layers, x, pin, *, attn_fn, positions,
                     act_sharding=None):
        """The stack between the spread of the embedding over the streams
        and their fold. The carry is [B, S, n C], the streams side by side
        in the last axis (a [B, S, n, C] array's second-minor axis of 4
        would be padded to a tile of 16 rows in HBM), so ``pin`` and the
        remat boundary are the other families'."""
        b, s, c = x.shape
        with jax.named_scope("ds.mhc_spread"):
            x = pin(jnp.tile(x, (1, 1, self.config.hc_mult)))
        x, stats = super()._layer_stack(
            layers, x, pin, attn_fn=attn_fn, positions=positions,
            act_sharding=act_sharding)
        with jax.named_scope("ds.mhc_fold"):
            x = jnp.sum(x.reshape(b, s, -1, c).astype(jnp.float32),
                        axis=2).astype(x.dtype)
        return x, stats

    # ---------------- sharding ----------------
    def partition_rules(self):
        """Tensor-parallel rules by head / FFN / expert dimension; the
        leading axis of a ``period`` stack is the scan's and stays whole.
        ``phi`` stays whole: its rows follow the streams."""
        def both(pattern, *spec):
            return [(rf"layers/period/.*{pattern}", P(None, *spec)),
                    (rf"layers/(lead|tail)/.*{pattern}", P(*spec))]

        rules = [(r"embed/tokens", P("tp", None))]
        for pattern, spec in [
                (r"mla/(wq_b|w_kvb)$", (None, "tp")),
                (r"mla/wo$", ("tp", None)),
                (r"experts/(w_up|w_gate)$", ("ep", None, "tp")),
                (r"experts/w_down$", ("ep", "tp", None)),
                (r"(mlp|shared)/(w_up|w_gate)$", (None, "tp")),
                (r"(mlp|shared)/w_down$", ("tp", None))]:
            rules += both(pattern, *spec)
        return rules + [(r"lm_head$", P(None, "tp"))]

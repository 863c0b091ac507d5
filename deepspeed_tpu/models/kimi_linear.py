"""Kimi-Linear family: a stack of more than one kind of layer.

``Kimi-Linear-48B-A3B`` (moonshotai, ``config.json``): 27 pre-norm layers
whose token mixer is KDA linear attention (``ops/kda.py``) in three of
four and latent attention without positions (MLA, NoPE) in the fourth,
and whose channel mixer is a dense SwiGLU in the first layer and 256
sigmoid-routed experts of width 1024 (top 8, one shared expert) after it.

    x <- x + Mix_l(rmsnorm(x));  x <- x + Ch_l(rmsnorm(x))

**KDA** (H heads of dk = dv = 128)::

    q = l2norm(silu(conv4(x Wq))) / sqrt(dk);  k = l2norm(silu(conv4(x Wk)))
    v = silu(conv4(x Wv));   beta = sigmoid(x Wb)            per head
    g = -exp(A_log[h]) * softplus((x Wf1) Wf2 + dt_bias)     per head, channel
    o = chunk_kda(q, k, v, g, beta)
    y = (rmsnorm_head(o) * sigmoid((x Wg1) Wg2 + b_g)) Wo

``conv4`` is a causal depthwise convolution of width 4 along the sequence;
with the SiLU and the l2 norm it is one pass of ``ops.layers.short_conv``.
The gated norm of ``o`` (the head's RMSNorm rounded to bf16, the gate's
sigmoid with its bias, the product) is one pass of
``ops.layers.gated_norm``, which reads ``o`` a head where the scan's
kernel wrote it.
**MLA** (``mla_use_nope``: neither part of q or k is rotated)::

    q = x Wq  as H x (nope + rope);   [c, k_pe] = x Wkva  (kv_lora + rope)
    [k_nope, v] = rmsnorm(c) Wkvb  as H x (nope + v);  k_h = [k_nope_h, k_pe]
    y = softmax_causal(q k^T / sqrt(nope + rope)) v  Wo

In training the latent is expanded and the layer runs as H-head attention
with a key of 192 and a value of 128 through the flash kernels.
**Routed layers** are ``moe.sharded_moe.moe_ffn_held``: a float32 sigmoid
router over all experts with a selection bias, renormalised top-k times
``routed_scaling_factor``, the experts HELD here (the first
``moe_held_experts``: one chip's share under expert parallelism) through
the dropless grouped dispatch, plus the shared expert. There is no
auxiliary loss: the router is bias-corrected. ``optimizer_frozen`` keeps
the optimizer off the bias; ``loss(with_stats=True)`` also returns every
routed layer's load by expert, and ``after_step`` (the engine calls it
with the updated weights) moves each bias against its expert's load.

**The stack** is ``models/stack.py``'s: parameters stacked by kind
(``lead`` the leading dense layers, ``period``, ``tail``), a layer's kind
read from the keys it holds.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops import layers as L
from .base import mean_context, register_model
from .stack import (LatentAttention, RoutedStackConfig, RoutedStackOfKinds,
                    mla_params)
from .transformer import _dense_init


@jax.custom_vjp
def _together(xs):
    """``xs`` as they are; differentiated, none of their cotangents moves
    on before all of them are there (an optimization barrier, which costs
    nothing on the device). XLA's scheduler puts a weight-gradient matmul
    off for as long as memory allows, and its [S, H d] operand stays alive
    meanwhile: with the gated norm a kernel pair, ``_kda``'s ``y`` and
    ``dgate`` of a layer lived through the scan's backward, and the tail
    layer's through the period's whole loop (the Kimi cell's step compiled
    to 13.05 GiB for the parent's 12.88; with the two ties of ``_kda``
    12.49: AOT for a v5e, PR 55)."""
    return xs


_together.defvjp(lambda xs: (xs, None),
                 lambda _, ds: (jax.lax.optimization_barrier(ds),))


@dataclasses.dataclass
class KimiLinearConfig(RoutedStackConfig):
    # 1-based layer numbers as the published config gives them
    kda_layers: tuple = ()          # KDA linear attention (ops/kda.py)
    full_attn_layers: tuple = ()    # latent attention (MLA)
    first_k_dense_replace: int = 0  # leading layers whose FFN is dense
    kda_num_heads: int = 0
    kda_head_dim: int = 128
    kda_conv_size: int = 4
    kda_gate_rank: int = 128        # width of the low-rank decay / output
    #                                 gate maps (the head width)
    kda_head_groups: int = 1        # run the KDA heads in this many groups,
    #                                 one after the other (ops/kda.py): the
    #                                 chunked form's operands live a group
    #                                 at a time
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mla_use_nope: bool = False      # no rotation on either part of q, k

    def __post_init__(self):
        super().__post_init__()
        self.kda_layers = tuple(self.kda_layers)
        self.full_attn_layers = tuple(self.full_attn_layers)

    @property
    def linear_attn_config(self) -> dict:
        """The published ``linear_attn_config`` group of the model as
        built (lists, as JSON has them)."""
        return {"full_attn_layers": list(self.full_attn_layers),
                "head_dim": self.kda_head_dim,
                "kda_layers": list(self.kda_layers),
                "num_heads": self.kda_num_heads,
                "short_conv_kernel_size": self.kda_conv_size}

    def layer_kinds(self) -> list[tuple[str, str]]:
        """(token mixer, channel mixer) of each layer, ``kda`` | ``mla``
        and ``dense`` | ``moe``."""
        kinds = []
        for n in range(1, self.num_layers + 1):
            if (n in self.kda_layers) == (n in self.full_attn_layers):
                raise ValueError(
                    f"layer {n} is in both or neither of kda_layers "
                    f"{self.kda_layers} and full_attn_layers "
                    f"{self.full_attn_layers}")
            kinds.append(("kda" if n in self.kda_layers else "mla",
                          "dense" if n <= self.first_k_dense_replace
                          or self.num_experts <= 0 else "moe"))
        return kinds

    def lead_layers(self) -> int:
        return self.first_k_dense_replace

    def _kind_params(self) -> dict:
        """Parameters of each kind of mixer, as ``KimiLinear._init_layer``
        builds them; ``expert`` is ONE routed expert, ``moe`` everything
        of a routed layer but its routed experts."""
        d = self.hidden_size
        h, dk, r = self.kda_num_heads, self.kda_head_dim, self.kda_gate_rank
        inner = h * dk
        kda = (3 * d * inner + 3 * self.kda_conv_size * inner   # q, k, v
               + d * r + r * inner + inner + h                   # decay
               + d * h                                           # beta
               + d * r + r * inner + inner                       # out gate
               + dk + inner * d)                                 # norm, wo
        expert = self._expert_params()
        return {"kda": kda, "mla": mla_params(self),
                "dense": 3 * d * self.intermediate_size,
                "expert": expert,
                "moe": (d * self.num_experts + self.num_experts
                        + expert * self.moe_num_shared_experts)}

    def _layer_params(self, kind) -> int:
        per = self._kind_params()
        mixer, channel = kind
        return (per[mixer] + 2 * self.hidden_size + per[channel]
                + (self._held_params() if channel == "moe" else 0))

    def _layer_idle_params(self, kind) -> float:
        return self._idle_held_params() if kind[1] == "moe" else 0

    def _layer_mixer_flops(self, kind, seq_len, causal) -> float:
        """Latent attention multiplies a key of qk width and a value of v
        width a visible pair (2 matmuls, x3 for training); a KDA head
        reads, corrects and writes its [dk, dv] state once a token (3
        products of 2 dk dv FLOPs, x3 for training)."""
        if kind[0] == "kda":
            return 18 * self.kda_num_heads * self.kda_head_dim ** 2
        return 6 * self.num_heads * mean_context(seq_len, causal) * (
            self.qk_nope_head_dim + self.qk_rope_head_dim + self.v_head_dim)


_PUBLISHED = dict(
    hidden_size=2304, intermediate_size=9216, num_heads=32, num_kv_heads=32,
    num_layers=27, vocab_size=163840, max_seq_len=16384,
    kda_layers=tuple(n for n in range(1, 27) if n % 4),
    full_attn_layers=(4, 8, 12, 16, 20, 24, 27), first_k_dense_replace=1,
    kda_num_heads=32, kda_head_dim=128, kda_conv_size=4, kda_gate_rank=128,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, mla_use_nope=True, num_experts=256, moe_top_k=8,
    moe_num_shared_experts=1, moe_intermediate_size=1024,
    routed_scaling_factor=2.446)


def kimi_linear_config(size: str = "48b-a3b",
                       **overrides) -> KimiLinearConfig:
    presets = {
        "tiny": dict(hidden_size=64, intermediate_size=128, num_heads=4,
                     num_kv_heads=4, num_layers=5, vocab_size=512,
                     max_seq_len=128, kda_layers=(1, 2, 3, 5),
                     full_attn_layers=(4,), first_k_dense_replace=1,
                     kda_num_heads=4, kda_head_dim=16, kda_conv_size=4,
                     kda_gate_rank=16, kv_lora_rank=32, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=16, mla_use_nope=True,
                     # the published router: the agreement check's mask
                     # depends on the share of experts near the boundary
                     num_experts=256, moe_top_k=8, moe_num_shared_experts=1,
                     moe_intermediate_size=32, routed_scaling_factor=2.446),
        "48b-a3b": _PUBLISHED,
    }
    base = dict(norm_type="rmsnorm", activation="swiglu",
                position_embedding="none", use_bias=False,
                tie_embeddings=False, norm_eps=1e-5,
                moe_router_activation="sigmoid", moe_norm_topk=True,
                router_aux_loss_coef=0.0)
    base.update(presets[size])
    base.update(overrides)
    return KimiLinearConfig(**base)


@register_model("kimi_linear")
class KimiLinear(LatentAttention, RoutedStackOfKinds):
    def __init__(self, config: KimiLinearConfig | None = None,
                 size: str | None = None, **overrides):
        if config is not None and (size is not None or overrides):
            raise ValueError(
                "pass either an explicit config or size/overrides, not both")
        config = config or kimi_linear_config(size or "48b-a3b", **overrides)
        if not config.mla_use_nope:
            raise NotImplementedError(
                "KimiLinear's latent attention is NoPE (mla_use_nope)")
        if config.moe_router_activation != "sigmoid":
            raise ValueError("KimiLinear's router is sigmoid")
        if config.tie_embeddings:
            raise ValueError("KimiLinear's head is untied")
        if config.held_experts > config.num_experts:
            raise ValueError(
                f"{config.held_experts} experts held of the router's "
                f"{config.num_experts}")
        super().__init__(config)

    def optimizer_frozen(self) -> str:
        """Leaves the optimizer leaves alone (the engine zeroes their
        updates): the router's selection bias moves by ``after_step``."""
        return r"router_bias$"

    def after_step(self, params, stats):
        """The trainer's half of the bias-corrected router, run by the
        engine on the step's updated weights: every routed layer's
        selection bias moves by ``BIAS_UPDATE_RATE`` against its
        experts' load in the step (``balance_bias``). ``stats`` is what
        ``loss(with_stats=True)`` returned beside the loss, summed over
        the step's micro-batches. Returns (params, metrics): the rows
        routed to the experts held here and the rows they computed
        (equal, or rows were dropped), over ``moe_held_calls`` routed
        layers of ``moe_held_experts`` each."""
        return self._balanced(params, stats)

    # ---------------- init ----------------
    def _init_layer(self, key, kind, lead_shape=()):
        c = self.config
        dt = c.param_dtype
        d = c.hidden_size
        std = 0.02
        resid_std = std / (2 * c.num_layers) ** 0.5
        ks = iter(jax.random.split(key, 24))

        def w(shape, scale=std):
            return _dense_init(next(ks), (*lead_shape, *shape), scale, dt)

        def ones(shape):
            return jnp.ones((*lead_shape, *shape), dt)

        p = {"ln1_scale": ones((d,)), "ln2_scale": ones((d,))}
        mixer, channel = kind
        if mixer == "kda":
            h, dk, r = c.kda_num_heads, c.kda_head_dim, c.kda_gate_rank
            inner = h * dk
            conv = lambda: jax.random.uniform(  # noqa: E731
                next(ks), (*lead_shape, c.kda_conv_size, inner),
                minval=-0.5, maxval=0.5).astype(dt)
            # decay init: A = U(1, 16); dt = exp(U(log 1e-3, log 0.1)),
            # dt_bias its inverse softplus
            step = jnp.exp(jax.random.uniform(
                next(ks), (*lead_shape, inner),
                minval=np.log(1e-3), maxval=np.log(0.1)))
            p["kda"] = {
                "wq": w((d, inner)), "wk": w((d, inner)), "wv": w((d, inner)),
                "conv_q": conv(), "conv_k": conv(), "conv_v": conv(),
                "w_f1": w((d, r)), "w_f2": w((r, inner)),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                "A_log": jnp.log(jax.random.uniform(
                    next(ks), (*lead_shape, h), minval=1.0,
                    maxval=16.0)).astype(dt),
                "w_b": w((d, h)),
                "w_g1": w((d, r)), "w_g2": w((r, inner)),
                "b_g": jnp.zeros((*lead_shape, inner), dt),
                "o_norm": ones((dk,)),
                "wo": w((inner, d), resid_std),
            }
        else:
            p["mla"] = self._init_mla(w, ones, resid_std)
        if channel == "dense":
            f = c.intermediate_size
            p["mlp"] = {"w_gate": w((d, f)), "w_up": w((d, f)),
                        "w_down": w((f, d), resid_std)}
        else:
            f = c.moe_intermediate_size or c.intermediate_size
            e = c.held_experts
            fs = f * c.moe_num_shared_experts
            p["moe"] = {
                # logits of unit variance at any width (0.0208 at the
                # published 2304): the scores' spread, and with it the
                # share of experts near the top-k boundary, is the same
                # at the tiny preset as at the real one
                "router": w((d, c.num_experts), d ** -0.5),
                # drawn, so that selection (scores + bias) and weighting
                # (scores) differ, small beside the scores' spread (0.2)
                # so that the load stays balanced; after_step moves it
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
        keys = jax.random.split(rng, 4)
        return {
            "embed": {"tokens": _dense_init(keys[1], (v, d), 0.02, dt)},
            "layers": self._init_layers(keys[0]),
            "final_norm": {"scale": jnp.ones((d,), dt)},
            "lm_head": _dense_init(keys[2], (d, v), 0.02, dt),
        }

    # ---------------- the mixers ----------------
    def _kda(self, p, h, kda_fn, conv_fn, norm_fn):
        """One KDA mixer on the normed ``h``. The projections carry ds.kda
        alone (what kind "matmul" finds). q, k and v are each ONE pass of
        ``conv_fn`` (``ops.layers.short_conv``, scope ds.conv) over their
        projection: the convolution, SiLU and, for q and k, the head's l2
        norm, written in the [B, S, H d] layout the scan's kernels read.
        What else lies before the scan (beta, the decay and ``g``) is
        ds.mix_pre; what lies after it, the gated norm, is ONE pass of
        ``norm_fn`` (``ops.layers.gated_norm``, which opens ds.mix_post)
        over the scan's ``o`` as ``kda_fn`` hands it over and the gate's
        pre-activation as its projection wrote it."""
        c = self.config
        b, s, _ = h.shape
        nh, dk = c.kda_num_heads, c.kda_head_dim
        f32 = jnp.float32
        heads = lambda x: x.reshape(b, s, nh, dk)  # noqa: E731
        pre = lambda: jax.named_scope("ds.mix_pre")  # noqa: E731
        q = heads(conv_fn(h @ p["wq"], p["conv_q"], norm_width=dk,
                          norm_scale=dk ** -0.5))
        k = heads(conv_fn(h @ p["wk"], p["conv_k"], norm_width=dk))
        v = heads(conv_fn(h @ p["wv"], p["conv_v"]))
        beta = h @ p["w_b"]
        with pre():
            beta = jax.nn.sigmoid(beta.astype(f32))
        decay = (h @ p["w_f1"]) @ p["w_f2"]
        with pre():
            decay = decay.astype(f32) + p["dt_bias"].astype(f32)
            g = -jnp.exp(p["A_log"].astype(f32))[:, None] \
                * heads(jax.nn.softplus(decay))
        o = kda_fn(q, k, v, g, beta, head_groups=c.kda_head_groups)
        # dgate is used up (by w_g2's gradient and the low-rank map's
        # cotangent) before do moves on into the scan's backward
        o, low, w_g2 = _together((o, h @ p["w_g1"], p["w_g2"]))
        gate = low @ w_g2
        # the published arithmetic: the norm returns o's dtype before the
        # product (``L.rms_norm``), the gate is a sigmoid with a bias
        o = norm_fn(o, gate, p["o_norm"], p["b_g"], act="sigmoid",
                    eps=c.norm_eps, round_norm=True)
        # ... and y (by wo's gradient) before dy moves on into the norm's
        y, wo = _together((o, p["wo"]))
        return y @ wo

    def _routed(self, p, h):
        from ..moe.sharded_moe import moe_ffn_held
        c = self.config
        return moe_ffn_held(
            h, p["router"], p["router_bias"], p["experts"], p.get("shared"),
            k=c.moe_top_k, renormalise=c.moe_norm_topk,
            scaling=c.routed_scaling_factor)

    # ---------------- one layer, the stack ----------------
    def _mix(self, p, x, attn_fn, kda_fn, conv_fn, norm_fn):
        with jax.named_scope("ds.kda" if "kda" in p else "ds.mla"):
            h = L.rms_norm(x, p["ln1_scale"], self.config.norm_eps)
            if "kda" in p:
                return x + self._kda(p["kda"], h, kda_fn, conv_fn, norm_fn)
            return x + self._mla(p["mla"], h, attn_fn)

    def _channel(self, p, x):
        """(x, counts): a routed layer's counts, nothing of a dense one."""
        h = L.rms_norm(x, p["ln2_scale"], self.config.norm_eps)
        if "mlp" in p:
            with jax.named_scope("ds.mlp"):
                return x + self._mlp(p["mlp"], h)[0], {}
        y, counts = self._routed(p["moe"], h)
        return x + y, counts

    def _one_layer(self, p, x, mixers):
        return self._channel(p, self._mix(p, x, *mixers))

    def _mixers(self, attn_fn, act_sharding):
        """(attention, KDA, short convolution): on a mesh of more than one
        device the KDA and the convolution's kernels run per shard of
        ``act_sharding``. On one device the scan hands ``o`` over as its
        kernel wrote it, the heads' stack (``chunk_kda(by_head=True)``):
        the gated norm reads either form."""
        from ..ops.kda import chunk_kda, sharded_chunk_kda
        if act_sharding is None:
            return (attn_fn, functools.partial(chunk_kda, by_head=True),
                    L.short_conv)
        return (attn_fn, sharded_chunk_kda(act_sharding),
                L.sharded_short_conv(act_sharding))

    def _layer_fns(self, attn_fn, act_sharding):
        """``_mixers`` and the gated norm behind the scan, per shard where
        they are."""
        return (*self._mixers(attn_fn, act_sharding),
                L.gated_norm if act_sharding is None
                else L.sharded_gated_norm(act_sharding))

    # ---------------- sharding ----------------
    def partition_rules(self):
        """Tensor-parallel rules by head / FFN / expert dimension; the
        leading axis of a ``period`` stack is the scan's and stays
        whole."""
        def both(pattern, *spec):
            return [(rf"layers/period/.*{pattern}", P(None, *spec)),
                    (rf"layers/(lead|tail)/.*{pattern}", P(*spec))]

        rules = [(r"embed/tokens", P("tp", None))]
        for pattern, spec in [
                (r"(kda|mla)/(wq|wk|wv|w_f2|w_g2|w_kvb)$", (None, "tp")),
                (r"(kda|mla)/wo$", ("tp", None)),
                (r"experts/(w_up|w_gate)$", ("ep", None, "tp")),
                (r"experts/w_down$", ("ep", "tp", None)),
                (r"(mlp|shared)/(w_up|w_gate)$", (None, "tp")),
                (r"(mlp|shared)/w_down$", ("tp", None))]:
            rules += both(pattern, *spec)
        return rules + [(r"lm_head$", P(None, "tp"))]

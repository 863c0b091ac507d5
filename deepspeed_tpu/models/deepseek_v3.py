"""DeepSeek-V3 family (``model_type`` ``deepseek_v3``): a plain pre-norm
stack whose every layer is rotated latent attention, a dense SwiGLU in the
leading layers and bias-corrected sigmoid-routed experts beside shared
experts after them.

``kanana-2-30b-a3b`` (kakaocorp ``kanana-2-30b-a3b-instruct-2601``,
``config.json``): 48 layers of hidden 2048, every one latent attention
(MLA: ``q_lora_rank`` null, a direct query; ``kv_lora_rank`` 512; 32 heads
of ``qk_nope_head_dim`` 128 + ``qk_rope_head_dim`` 64, ``v_head_dim`` 128;
``rope_theta`` 1e6, ``rope_interleave``, no scaling) followed by a dense
SwiGLU of 6144 in layer 0 (``first_k_dense_replace`` 1) and by 128 routed
experts of 768 (top 6, sigmoid scores with the ``noaux_tc`` selection bias,
``n_group`` 1, renormalised, times ``routed_scaling_factor`` 2.448) beside
2 shared experts in the others; an untied head over 128256;
``max_position_embeddings`` 32768. A layer (``h = rmsnorm(x)``, eps 1e-6,
``x <- x + f(h)`` twice)::

    q = h Wq  as H x (nope + rope)            (no query latent, no query norm)
    [c, k_pe] = h Wkva  (kv_lora + rope);  [k_nope, v] = rmsnorm(c) Wkvb
    q_pe, k_pe: the pairs (x_2i, x_2i+1) laid out as halves, then rotated
        at theta_i = theta^(-2i / rope), positions 0..S-1; k_pe is ONE
        head, shared by all H
    y = softmax_causal((q_nope k_nope^T + q_pe k_pe^T) (nope + rope)^-1/2) v Wo
    leading layers: SwiGLU of intermediate_size
    the others: s = sigmoid(h Wr) float32; top k of s + b;
        w = s_sel / (sum s_sel + 1e-20) x routed_scaling_factor;
        sum_e w_e SwiGLU_e(h) + SwiGLU_shared(h)

The attention is ``models/stack.py`` ``LatentAttention`` (Kimi-Linear's and
Xing4.0's too); the ``n_shared_experts`` shared experts are ONE SwiGLU of
their summed width (what two experts that see every token add up to); a
routed layer is ``moe.sharded_moe.moe_ffn_held`` as Kimi-Linear's calls
it. ``optimizer_frozen`` keeps the optimizer off the selection bias ``b``,
which gets no gradient, and ``after_step`` moves it against the load
(``RoutedStackOfKinds._balanced``). At the published context a head's row
of 32768 keys of 192 is longer than the flash kernels hold: they run it in
two spans (``ops/pallas/flash_attention.py`` ``segments``).

Serving, the pipeline and the multi-token prediction module are not here
(``ROADMAP.md`` queue 2 A).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import layers as L
from .base import mean_context, register_model
from .stack import (LatentAttention, RoutedStackConfig, RoutedStackOfKinds,
                    mla_params)
from .transformer import _dense_init


@dataclasses.dataclass
class DeepseekV3Config(RoutedStackConfig):
    # key names as published
    first_k_dense_replace: int = 0  # leading layers whose FFN is dense
    q_lora_rank: int = 0            # 0 (published: null): a direct query
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = True    # the rotated channels are the
    #                                 checkpoint's pairs (x_2i, x_2i+1)
    rope_scaling: dict | None = None

    def __post_init__(self):
        super().__post_init__()
        self.q_lora_rank = self.q_lora_rank or 0

    def layer_kinds(self) -> list[tuple[str, str]]:
        """(token mixer, channel mixer) of each layer: ``mla`` and
        ``dense`` | ``routed``."""
        return [("mla", "dense" if i < self.first_k_dense_replace
                 or self.num_experts <= 0 else "routed")
                for i in range(self.num_layers)]

    def lead_layers(self) -> int:
        return self.first_k_dense_replace

    def _layer_params(self, kind) -> int:
        """As ``DeepseekV3._init_layer`` builds a layer: the mixer with its
        latent norm, the layer's two norms and the channel mixer (the
        router, its bias, the shared experts and the experts held)."""
        d = self.hidden_size
        if kind[1] == "dense":
            ff = 3 * d * self.intermediate_size
        else:
            ff = ((d + 1) * self.num_experts
                  + self._expert_params() * self.moe_num_shared_experts
                  + self._held_params())
        return mla_params(self) + 2 * d + ff

    def _layer_idle_params(self, kind) -> float:
        return self._idle_held_params() if kind[1] == "routed" else 0

    def _layer_mixer_flops(self, kind, seq_len, causal) -> float:
        """Beside the 6 N: a key of qk width and a value of v width a
        visible pair and head; x3 for training."""
        return 6 * self.num_heads * mean_context(seq_len, causal) * (
            self.qk_nope_head_dim + self.qk_rope_head_dim + self.v_head_dim)


_KANANA_2_30B = dict(
    hidden_size=2048, intermediate_size=6144, num_heads=32, num_kv_heads=32,
    num_layers=48, vocab_size=128256, max_seq_len=32768, rope_theta=1000000,
    first_k_dense_replace=1, q_lora_rank=0, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_interleave=True, rope_scaling=None, num_experts=128, moe_top_k=6,
    moe_num_shared_experts=2, moe_intermediate_size=768,
    routed_scaling_factor=2.448)


def deepseek_v3_config(size: str = "kanana-2-30b-a3b",
                       **overrides) -> DeepseekV3Config:
    presets = {
        # a leading dense layer and four routed ones under the scan; the
        # published router (the agreement check's mask depends on the
        # share of experts near the boundary) and two shared experts
        "tiny": dict(hidden_size=64, intermediate_size=128, num_heads=4,
                     num_kv_heads=4, num_layers=5, vocab_size=512,
                     max_seq_len=128, rope_theta=1000000,
                     first_k_dense_replace=1, q_lora_rank=0,
                     kv_lora_rank=32, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=16,
                     rope_interleave=True, rope_scaling=None,
                     num_experts=128, moe_top_k=6, moe_num_shared_experts=2,
                     moe_intermediate_size=32, routed_scaling_factor=2.448),
        "kanana-2-30b-a3b": _KANANA_2_30B,
    }
    base = dict(norm_type="rmsnorm", activation="swiglu",
                position_embedding="none", use_bias=False,
                tie_embeddings=False, norm_eps=1e-6,
                moe_router_activation="sigmoid", moe_norm_topk=True,
                router_aux_loss_coef=0.0)
    base.update(presets[size])
    base.update(overrides)
    return DeepseekV3Config(**base)


@register_model("deepseek_v3")
class DeepseekV3(LatentAttention, RoutedStackOfKinds):
    def __init__(self, config: DeepseekV3Config | None = None,
                 size: str | None = None, **overrides):
        if config is not None and (size is not None or overrides):
            raise ValueError(
                "pass either an explicit config or size/overrides, not both")
        c = config or deepseek_v3_config(size or "kanana-2-30b-a3b",
                                         **overrides)
        if (c.moe_router_activation != "sigmoid" or c.tie_embeddings
                or c.use_bias or c.rope_scaling):
            raise NotImplementedError(
                "DeepseekV3 has a sigmoid router with a selection bias, an "
                "untied head, no bias in a projection and a plain rotary "
                "table (rope_scaling null)")
        if c.held_experts > c.num_experts:
            raise ValueError(
                f"{c.held_experts} experts held of the router's "
                f"{c.num_experts}")
        super().__init__(c)
        self._rope_pairs = c.rope_interleave
        self._rope = L.latent_rotary_tables(
            *L.rotary_embedding(c.max_seq_len, c.qk_rope_head_dim,
                                c.rope_theta), pairs=self._rope_pairs)

    def optimizer_frozen(self) -> str:
        """Leaves the optimizer leaves alone (the engine zeroes their
        updates): the router's selection bias moves by ``after_step``."""
        return r"router_bias$"

    def after_step(self, params, stats):
        """The trainer's half of the bias-corrected router on the step's
        updated weights (``RoutedStackOfKinds._balanced``)."""
        return self._balanced(params, stats)

    # ---------------- init ----------------
    def _init_layer(self, key, kind, lead_shape=()):
        c = self.config
        dt = c.param_dtype
        d = c.hidden_size
        std = 0.02
        resid_std = std / (2 * c.num_layers) ** 0.5
        ks = iter(jax.random.split(key, 16))

        def w(shape, scale=std):
            return _dense_init(next(ks), (*lead_shape, *shape), scale, dt)

        def ones(shape):
            return jnp.ones((*lead_shape, *shape), dt)

        p = {"ln1_scale": ones((d,)), "ln2_scale": ones((d,)),
             "mla": self._init_mla(w, ones, resid_std)}
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

    # ---------------- one layer ----------------
    def _routed(self, p, h):
        from ..moe.sharded_moe import moe_ffn_held
        c = self.config
        return moe_ffn_held(
            h, p["router"], p["router_bias"], p["experts"], p.get("shared"),
            k=c.moe_top_k, renormalise=c.moe_norm_topk,
            scaling=float(c.routed_scaling_factor))

    def _one_layer(self, p, x, attn_fn):
        """x [B, S, C] -> (x, counts): a routed layer's counts, nothing of
        a dense one."""
        c = self.config
        with jax.named_scope("ds.attn"):
            h = L.rms_norm(x, p["ln1_scale"], c.norm_eps)
            x = x + self._mla(p["mla"], h, attn_fn)
        h = L.rms_norm(x, p["ln2_scale"], c.norm_eps)
        if "mlp" in p:
            with jax.named_scope("ds.mlp"):
                return x + self._mlp(p["mlp"], h)[0], {}
        y, counts = self._routed(p["moe"], h)
        return x + y, counts

    def _mixers(self, attn_fn, act_sharding):
        return attn_fn

    # ---------------- sharding ----------------
    def partition_rules(self):
        """Tensor-parallel rules by head / FFN / expert dimension; the
        leading axis of a ``period`` stack is the scan's and stays whole."""
        def both(pattern, *spec):
            return [(rf"layers/period/.*{pattern}", P(None, *spec)),
                    (rf"layers/(lead|tail)/.*{pattern}", P(*spec))]

        rules = [(r"embed/tokens", P("tp", None))]
        for pattern, spec in [
                (r"mla/(wq|w_kvb)$", (None, "tp")),
                (r"mla/wo$", ("tp", None)),
                (r"experts/(w_up|w_gate)$", ("ep", None, "tp")),
                (r"experts/w_down$", ("ep", "tp", None)),
                (r"(mlp|shared)/(w_up|w_gate)$", (None, "tp")),
                (r"(mlp|shared)/w_down$", ("tp", None))]:
            rules += both(pattern, *spec)
        return rules + [(r"lm_head$", P(None, "tp"))]

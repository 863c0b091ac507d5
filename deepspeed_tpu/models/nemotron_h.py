"""Nemotron-H family (``model_type`` ``nemotron_h``): a pre-norm stack whose
every layer is ONE sublayer, a Mamba-2 mixer OR a routed feed-forward part
OR a grouped-query attention layer, by ``hybrid_override_pattern``.

``3-super-120b-a12b`` (nvidia ``NVIDIA-Nemotron-3-Super-120B-A12B-BF16``,
``config.json``): 88 layers of hidden 4096 by the pattern
``MEMEMEM*EMEM...`` (40 ``M``, 40 ``E``, 8 ``*``), an untied head over
131072. A layer (``h = rmsnorm(x, w_l)``, eps 1e-5, ``x <- x + Mix_l(h)``)::

    M  Mamba-2 (``models/stack.py`` ``Mamba2``): 128 heads of 64, state 128,
       8 groups of heads sharing B and C, conv 4, chunk 128; the gated norm
       takes its mean of squares over each GROUP's H P / G channels
    *  q = h Wq (32 heads of 128), k, v = h Wk, h Wv (2 heads); causal
       softmax at 128^-1/2; NO rotation; Wo
    E  LatentMoE: s = sigmoid(h Wr) float32; the top 22 of s + b over all
       512 (one group); w_j = 5 s_j / (sum_chosen s + 1e-20);
       u = h W_dn (1024 wide);  r = sum_j w_j relu(u W1_j)^2 W2_j  over the
       chosen experts HELD here (non-gated, 1024 -> 2688 -> 1024);
       out = r W_up + relu(h V1)^2 V2     (one shared expert of 5376 on h)

The routed layer is ``moe.sharded_moe.moe_ffn_held`` with the ``relu2``
body and its ``latent`` projections: the router and the shared expert read
the normed hidden state, the routed experts the latent, and a token none of
whose chosen experts is held gets the shared expert alone. ``W_up`` has no
bias, so the shares of a layer add up. ``optimizer_frozen`` keeps the
optimizer off the selection bias ``b``, which gets no gradient, and
``after_step`` moves it against the load
(``RoutedStackOfKinds._balanced``).

A chip's share of a mixer is a share of its HEADS: ``mamba_num_heads`` and
``n_groups``, ``num_heads`` and ``num_kv_heads`` are what is held here
(the inner width is ``mamba_num_heads x mamba_head_dim`` as held: a
share's is not ``expand x hidden``), and the layer gives the partial sum
its heads give through their rows of ``W_out`` / ``Wo``.

Serving, the pipeline and the multi-token prediction module
(``num_nextn_predict_layers``; the config says nothing of how its two
inputs are joined) are not here (``ROADMAP.md`` queue 2 A).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import layers as L
from .base import mean_context, register_model
from .stack import (Mamba2, MambaShape, RoutedStackConfig, RoutedStackOfKinds,
                    grouped_query_attention)
from .transformer import _dense_init

# a character of ``hybrid_override_pattern`` -> the key a layer's weights
# lie under
PATTERN_KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


@dataclasses.dataclass
class NemotronHConfig(RoutedStackConfig):
    # key names as published
    hybrid_override_pattern: str = ""   # M | E | * a layer
    mamba_num_heads: int = 0        # Mamba heads HELD here
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8               # groups of Mamba heads held here: B, C
    #                                 and the gated norm a group
    conv_kernel: int = 4
    chunk_size: int = 128
    expand: int = 2                 # the uncut model's inner / hidden; not
    #                                 checked: a share's is another
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    moe_latent_size: int = 0        # the width the routed experts work in
    moe_shared_expert_intermediate_size: int = 0
    n_group: int = 1                # the router's expert groups
    topk_group: int = 1

    def layer_kinds(self) -> list[str]:
        return [PATTERN_KINDS[ch] for ch in self.hybrid_override_pattern]

    def mamba_shape(self) -> MambaShape:
        return MambaShape(
            heads=self.mamba_num_heads, head_dim=self.mamba_head_dim,
            state=self.ssm_state_size, groups=self.n_groups,
            conv=self.conv_kernel, conv_bias=self.use_conv_bias,
            chunk=self.chunk_size, norm_groups=self.n_groups)

    def _expert_params(self) -> int:
        """ONE routed expert: two matrices between the latent and its
        width, no gate."""
        return 2 * self.moe_latent_size * self.moe_intermediate_size

    def _layer_params(self, kind) -> int:
        """As ``NemotronH._init_layer`` builds a layer: ONE sublayer and
        its norm."""
        d = self.hidden_size
        if kind == "mamba":
            return d + self.mamba_shape().params(d)
        if kind == "attn":
            return d + 2 * d * self.head_dim * (self.num_heads
                                                + self.num_kv_heads)
        return (d + (d + 1) * self.num_experts          # router and bias
                + 2 * d * self.moe_latent_size          # W_dn, W_up
                + 2 * d * self.moe_shared_expert_intermediate_size
                * self.moe_num_shared_experts + self._held_params())

    def _layer_idle_params(self, kind) -> float:
        return self._idle_held_params() if kind == "moe" else 0

    def _layer_mixer_flops(self, kind, seq_len, causal) -> float:
        if kind == "mamba":
            return self.mamba_shape().state_flops
        if kind == "attn":
            return 12 * self.num_heads * self.head_dim * mean_context(
                seq_len, causal)
        return 0


_PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                      "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
_3_SUPER = dict(
    hidden_size=4096, intermediate_size=2688, num_heads=32, num_kv_heads=2,
    attn_head_dim=128, num_layers=88, vocab_size=131072, max_seq_len=262144,
    rope_theta=10000, hybrid_override_pattern=_PUBLISHED_PATTERN,
    mamba_num_heads=128, mamba_head_dim=64, ssm_state_size=128, n_groups=8,
    conv_kernel=4, chunk_size=128, expand=2, num_experts=512, moe_top_k=22,
    moe_num_shared_experts=1, moe_intermediate_size=2688,
    moe_latent_size=1024, moe_shared_expert_intermediate_size=5376,
    routed_scaling_factor=5)


def nemotron_h_config(size: str = "3-super-120b-a12b",
                      **overrides) -> NemotronHConfig:
    presets = {
        # two periods of a routed and a Mamba layer under the scan and an
        # attention layer behind them; the published router (the agreement
        # check's mask depends on the share of experts near the boundary)
        "tiny": dict(hidden_size=64, intermediate_size=32, num_heads=4,
                     num_kv_heads=2, attn_head_dim=16, num_layers=5,
                     vocab_size=512, max_seq_len=128, rope_theta=10000,
                     hybrid_override_pattern="EMEM*", mamba_num_heads=8,
                     mamba_head_dim=16, ssm_state_size=16, n_groups=2,
                     conv_kernel=4, chunk_size=32, expand=2, num_experts=512,
                     moe_top_k=22, moe_num_shared_experts=1,
                     moe_intermediate_size=32, moe_latent_size=16,
                     moe_shared_expert_intermediate_size=96,
                     routed_scaling_factor=5),
        "3-super-120b-a12b": _3_SUPER,
    }
    base = dict(norm_type="rmsnorm", activation="relu2",
                position_embedding="none", use_bias=False,
                tie_embeddings=False, norm_eps=1e-5,
                moe_router_activation="sigmoid", moe_norm_topk=True,
                router_aux_loss_coef=0.0)
    base.update(presets[size])
    base.update(overrides)
    return NemotronHConfig(**base)


@register_model("nemotron_h")
class NemotronH(Mamba2, RoutedStackOfKinds):
    def __init__(self, config: NemotronHConfig | None = None,
                 size: str | None = None, **overrides):
        if config is not None and (size is not None or overrides):
            raise ValueError(
                "pass either an explicit config or size/overrides, not both")
        c = config or nemotron_h_config(size or "3-super-120b-a12b",
                                        **overrides)
        pattern = c.hybrid_override_pattern
        if len(pattern) != c.num_layers or set(pattern) - set(PATTERN_KINDS):
            raise ValueError(
                f"NemotronH needs {c.num_layers} characters of "
                f"{sorted(PATTERN_KINDS)} in hybrid_override_pattern, not "
                f"{pattern!r}")
        if (c.moe_router_activation != "sigmoid" or c.tie_embeddings
                or c.use_bias or c.mamba_proj_bias
                or c.activation != "relu2" or c.moe_num_shared_experts != 1
                or (c.n_group, c.topk_group) != (1, 1)):
            raise NotImplementedError(
                "NemotronH has a sigmoid router with a selection bias over "
                "one group of experts, relu2 experts beside one shared "
                "expert, an untied head and no bias in a projection")
        if c.held_experts > c.num_experts:
            raise ValueError(
                f"{c.held_experts} experts held of the router's "
                f"{c.num_experts}")
        if c.mamba_num_heads % c.n_groups or c.num_heads % c.num_kv_heads:
            raise ValueError(
                f"{c.mamba_num_heads} Mamba heads in {c.n_groups} groups, "
                f"{c.num_heads} query heads on {c.num_kv_heads} key-value "
                f"heads: a group serves a whole number of heads")
        super().__init__(c)

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
        ks = iter(jax.random.split(key, 12))

        def w(shape, scale=std):
            return _dense_init(next(ks), (*lead_shape, *shape), scale, dt)

        def ones(shape):
            return jnp.ones((*lead_shape, *shape), dt)

        p = {"ln1_scale": ones((d,))}
        if kind == "mamba":
            p["mamba"] = self._init_mamba(w, ones, ks, lead_shape, resid_std)
        elif kind == "attn":
            nh, nkv, hd = c.num_heads, c.num_kv_heads, c.head_dim
            p["attn"] = {"wq": w((d, nh * hd)), "wk": w((d, nkv * hd)),
                         "wv": w((d, nkv * hd)),
                         "wo": w((nh * hd, d), resid_std)}
        else:
            lat, f = c.moe_latent_size, c.moe_intermediate_size
            fs = c.moe_shared_expert_intermediate_size
            e = c.held_experts
            p["moe"] = {
                # logits of unit variance at any width, and a drawn bias so
                # that selection and weighting differ, as Kimi-Linear's
                "router": w((d, c.num_experts), d ** -0.5),
                "router_bias": w((c.num_experts,), 0.01),
                # W2 and W_up at the plain deviation: the routed branch is
                # their product, and at the rescaled one a share of its
                # experts would add a hundredth of the shared expert
                "latent": {"w_dn": w((d, lat)), "w_up": w((lat, d))},
                "experts": {"w_up": w((e, lat, f)), "w_down": w((e, f, lat))},
                "shared": {"w_up": w((d, fs)),
                           "w_down": w((fs, d), resid_std)},
            }
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
        y, counts = moe_ffn_held(
            h, p["router"], p["router_bias"], p["experts"], p["shared"],
            k=c.moe_top_k, renormalise=c.moe_norm_topk,
            scaling=float(c.routed_scaling_factor), body="relu2",
            latent=p["latent"])
        return y, self._held_blocks(counts, h.shape[0] * h.shape[1])

    def _one_layer(self, p, x, mixers):
        """x [B, S, C] -> (x, counts): a routed layer's counts, nothing of
        a mixer."""
        c = self.config
        attn_fn, ssd_fn, conv_fn = mixers
        if "mamba" in p:
            with jax.named_scope("ds.mamba"):
                h = L.rms_norm(x, p["ln1_scale"], c.norm_eps)
                return x + self._mamba(p["mamba"], h, ssd_fn, conv_fn), {}
        if "attn" in p:
            with jax.named_scope("ds.attn"):
                h = L.rms_norm(x, p["ln1_scale"], c.norm_eps)
                return x + grouped_query_attention(
                    p["attn"], h, attn_fn, heads=c.num_heads,
                    kv_heads=c.num_kv_heads, head_dim=c.head_dim), {}
        h = L.rms_norm(x, p["ln1_scale"], c.norm_eps)
        y, counts = self._routed(p["moe"], h)
        return x + y, counts

    # ---------------- sharding ----------------
    def partition_rules(self):
        """Tensor-parallel rules by head / FFN / expert dimension; the
        leading axis of a ``period`` stack is the scan's and stays whole. A
        Mamba mixer stays whole (the three parts of its fused input
        projection split at different widths), and so do the latent's two
        projections."""
        def both(pattern, *spec):
            return [(rf"layers/period/.*{pattern}", P(None, *spec)),
                    (rf"layers/(lead|tail)/.*{pattern}", P(*spec))]

        rules = [(r"embed/tokens", P("tp", None))]
        for pattern, spec in [
                (r"attn/(wq|wk|wv)$", (None, "tp")),
                (r"attn/wo$", ("tp", None)),
                (r"experts/w_up$", ("ep", None, "tp")),
                (r"experts/w_down$", ("ep", "tp", None)),
                (r"shared/w_up$", (None, "tp")),
                (r"shared/w_down$", ("tp", None))]:
            rules += both(pattern, *spec)
        return rules + [(r"lm_head$", P(None, "tp"))]

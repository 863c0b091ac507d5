"""Granite 4.0-H family: Mamba-2 state-space layers with a grouped-query
attention layer among every few, a SwiGLU in every layer, and the four
muP multipliers.

``granite-4.0-h-micro`` (ibm-granite, ``config.json``, ``model_type``
``granitemoehybrid`` with ``num_local_experts`` 0): 40 pre-norm layers of
hidden 2048, ``layer_types`` nine ``mamba`` to one ``attention``::

    x0 = embed[tokens] * embedding_multiplier
    x <- x + residual_multiplier * Mix_l(rmsnorm(x))
    x <- x + residual_multiplier * (silu(h Wg) * (h Wu)) Wd,  h = rmsnorm(x)
    logits = rmsnorm(x_L) embed^T / logits_scaling            (tied head)

**mamba** (Mamba-2: H heads of P, state N, G groups, ``ops/ssd.py``)::

    [z | xBC | dt] = h W_in          widths H P | H P + 2 G N | H
    [x | B | C] = silu(conv4(xBC) + b_conv)          causal, depthwise:
                                          ops.layers.short_conv, one pass
    dt = softplus(dt + dt_bias);   A = -exp(A_log)           per head
    y = chunk_ssd(x, dt, A, B, C) + D x
    out = (rmsnorm(y * silu(z)) * w) W_out       the norm over all H P

(``models/stack.py`` ``Mamba2``, which ``models/nemotron_h.py`` shares.)

**attention**: q ``num_heads``, k and v ``num_kv_heads`` heads, no bias, no
positions (``position_embedding_type`` "nope"), causal over the whole
sequence at the softmax scale ``attention_multiplier`` (1/64 at a head of
64, not 1/8). The flash kernels apply ``head_dim ** -0.5`` and take no
scale, so q is scaled by the ratio before the call (0.125: a power of two,
exact in bf16). The logits' divisor scales the final hidden state the same
way (1/8), so the chunked loss keeps its signature.

The stack is ``models/stack.py``'s; serving is not here (a recurrent
state has no place in ``inference/`` yet).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import layers as L
from .base import mean_context, register_model
from .stack import (Mamba2, MambaShape, StackConfig, StackOfKinds,
                    grouped_query_attention)
from .transformer import _dense_init


@dataclasses.dataclass
class GraniteHybridConfig(StackConfig):
    # key names as published
    layer_types: tuple | list = ()  # "mamba" | "attention" a layer
    mamba_n_heads: int = 0
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1         # groups of heads sharing B and C
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    mamba_expand: int = 2           # n_heads * d_head = expand * hidden_size
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # the four muP multipliers: 1 (None) where a model has none
    embedding_multiplier: float = 1.0   # x0 = embed[tokens] * this
    residual_multiplier: float = 1.0    # x + this * sublayer(norm(x))
    logits_scaling: float = 1.0         # logits / this
    attention_multiplier: float | None = None   # the softmax scale; None =
    #                                             head_dim ** -0.5

    def __post_init__(self):
        super().__post_init__()
        self.layer_types = list(self.layer_types)   # as JSON has it

    def layer_kinds(self) -> list[str]:
        return list(self.layer_types)

    def mamba_shape(self) -> MambaShape:
        """The mixer from the published keys: one gated norm over all H P
        channels, whatever the groups of B and C."""
        return MambaShape(
            heads=self.mamba_n_heads, head_dim=self.mamba_d_head,
            state=self.mamba_d_state, groups=self.mamba_n_groups,
            conv=self.mamba_d_conv, conv_bias=self.mamba_conv_bias,
            chunk=self.mamba_chunk_size)

    def _layer_params(self, kind) -> int:
        """As ``GraniteHybrid._init_layer`` builds a layer: the mixer, a
        SwiGLU and two norms."""
        d = self.hidden_size
        if kind == "mamba":
            mixer = self.mamba_shape().params(d)
        else:
            mixer = 2 * d * self.head_dim * (self.num_heads
                                             + self.num_kv_heads)
        return mixer + 3 * d * self.intermediate_size + 2 * d

    def _layer_mixer_flops(self, kind, seq_len, causal) -> float:
        """An attention layer multiplies a key and a value of head_dim a
        visible pair; a Mamba head's state: ``MambaShape.state_flops``."""
        if kind == "mamba":
            return self.mamba_shape().state_flops
        return 12 * self.num_heads * self.head_dim * mean_context(
            seq_len, causal)


_PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
_PUBLISHED = dict(
    hidden_size=2048, intermediate_size=8192, num_heads=32, num_kv_heads=8,
    num_layers=40, vocab_size=100352, max_seq_len=131072,
    layer_types=_PERIOD * 4, mamba_n_heads=64, mamba_d_head=64,
    mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4,
    mamba_chunk_size=256, mamba_expand=2, embedding_multiplier=12,
    attention_multiplier=0.015625, residual_multiplier=0.22,
    logits_scaling=8)


def granite_hybrid_config(size: str = "4.0-h-micro",
                          **overrides) -> GraniteHybridConfig:
    presets = {
        "tiny": dict(hidden_size=64, intermediate_size=128, num_heads=4,
                     num_kv_heads=2, num_layers=5, vocab_size=512,
                     max_seq_len=128,
                     layer_types=["mamba", "mamba", "attention", "mamba",
                                  "mamba"],
                     mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                     mamba_n_groups=1, mamba_d_conv=4, mamba_chunk_size=32,
                     mamba_expand=2, embedding_multiplier=12,
                     attention_multiplier=0.03125, residual_multiplier=0.22,
                     logits_scaling=8),
        "4.0-h-micro": _PUBLISHED,
    }
    base = dict(norm_type="rmsnorm", activation="swiglu",
                position_embedding="nope", use_bias=False,
                tie_embeddings=True, norm_eps=1e-5)
    base.update(presets[size])
    base.update(overrides)
    return GraniteHybridConfig(**base)


@register_model("granite_hybrid")
class GraniteHybrid(Mamba2, StackOfKinds):
    def __init__(self, config: GraniteHybridConfig | None = None,
                 size: str | None = None, **overrides):
        if config is not None and (size is not None or overrides):
            raise ValueError(
                "pass either an explicit config or size/overrides, not both")
        c = config or granite_hybrid_config(size or "4.0-h-micro",
                                            **overrides)
        if len(c.layer_types) != c.num_layers or set(c.layer_types) - {
                "mamba", "attention"}:
            raise ValueError(
                f"GraniteHybrid needs {c.num_layers} layer_types of 'mamba' "
                f"| 'attention', not {c.layer_types}")
        if c.mamba_n_heads * c.mamba_d_head != c.mamba_expand * c.hidden_size:
            raise ValueError(
                f"{c.mamba_n_heads} Mamba heads of {c.mamba_d_head} are not "
                f"mamba_expand {c.mamba_expand} x hidden {c.hidden_size}")
        if (c.mamba_proj_bias or c.use_bias or c.num_experts
                or not c.tie_embeddings):
            raise NotImplementedError(
                "GraniteHybrid has no projection bias, no experts and a "
                "tied head")
        super().__init__(c)

    # ---------------- init ----------------
    def _init_layer(self, key, kind, lead_shape=()):
        c = self.config
        dt = c.param_dtype
        d, f = c.hidden_size, c.intermediate_size
        std = 0.02
        resid_std = std / (2 * c.num_layers) ** 0.5
        ks = iter(jax.random.split(key, 12))

        def w(shape, scale=std):
            return _dense_init(next(ks), (*lead_shape, *shape), scale, dt)

        def ones(shape):
            return jnp.ones((*lead_shape, *shape), dt)

        p = {"ln1_scale": ones((d,)), "ln2_scale": ones((d,)),
             "mlp": {"w_gate": w((d, f)), "w_up": w((d, f)),
                     "w_down": w((f, d), resid_std)}}
        if kind == "mamba":
            p["mamba"] = self._init_mamba(w, ones, ks, lead_shape, resid_std)
        else:
            nh, nkv, hd = c.num_heads, c.num_kv_heads, c.head_dim
            p["attn"] = {"wq": w((d, nh * hd)), "wk": w((d, nkv * hd)),
                         "wv": w((d, nkv * hd)),
                         "wo": w((nh * hd, d), resid_std)}
        return p

    def init(self, rng: jax.Array):
        c = self.config
        dt = c.param_dtype
        keys = jax.random.split(rng, 2)
        return {
            "embed": {"tokens": _dense_init(
                keys[1], (c.vocab_size, c.hidden_size), 0.02, dt)},
            "layers": self._init_layers(keys[0]),
            "final_norm": {"scale": jnp.ones((c.hidden_size,), dt)},
        }

    # ---------------- the pieces the multipliers touch ----------------
    def embed(self, params, tokens, positions=None):
        return (super().embed(params, tokens, positions)
                * self.config.embedding_multiplier)

    def _project_vocab(self, params, x):
        return super()._project_vocab(
            params, x * (1.0 / self.config.logits_scaling))

    def _chunked_ce(self, params, x, targets):
        return super()._chunked_ce(
            params, x * (1.0 / self.config.logits_scaling), targets)

    # ---------------- the mixers ----------------
    def _attention(self, p, h, attn_fn):
        c = self.config
        scale = c.attention_multiplier
        return grouped_query_attention(
            p, h, attn_fn, heads=c.num_heads, kv_heads=c.num_kv_heads,
            head_dim=c.head_dim,
            # attn_fn applies head_dim ** -0.5
            q_scale=None if scale is None else scale * c.head_dim ** 0.5)

    def _residual(self, x, y):
        """x + residual_multiplier * y, in float32 and rounded once (0.22
        is no bf16 number: multiplied in bf16 it is 0.2197)."""
        f32 = jnp.float32
        return (x.astype(f32) + self.config.residual_multiplier
                * y.astype(f32)).astype(x.dtype)

    def _one_layer(self, p, x, mixers):
        c = self.config
        attn_fn, ssd_fn, conv_fn = mixers
        if "mamba" in p:
            with jax.named_scope("ds.mamba"):
                h = L.rms_norm(x, p["ln1_scale"], c.norm_eps)
                x = self._residual(
                    x, self._mamba(p["mamba"], h, ssd_fn, conv_fn))
        else:
            with jax.named_scope("ds.attn"):
                h = L.rms_norm(x, p["ln1_scale"], c.norm_eps)
                x = self._residual(x, self._attention(p["attn"], h, attn_fn))
        with jax.named_scope("ds.mlp"):
            h = L.rms_norm(x, p["ln2_scale"], c.norm_eps)
            x = self._residual(x, self._mlp(p["mlp"], h)[0])
        return x, {}

    # ---------------- sharding ----------------
    def partition_rules(self):
        """Tensor-parallel rules by head / FFN dimension; the leading axis
        of a ``period`` stack is the scan's and stays whole. A Mamba
        mixer stays whole (the three parts of its fused input projection
        split at different widths)."""
        def both(pattern, *spec):
            return [(rf"layers/period/.*{pattern}", P(None, *spec)),
                    (rf"layers/(lead|tail)/.*{pattern}", P(*spec))]

        rules = [(r"embed/tokens", P("tp", None))]
        for pattern, spec in [
                (r"attn/(wq|wk|wv)$", (None, "tp")),
                (r"attn/wo$", ("tp", None)),
                (r"mlp/(w_up|w_gate)$", (None, "tp")),
                (r"mlp/w_down$", ("tp", None))]:
            rules += both(pattern, *spec)
        return rules

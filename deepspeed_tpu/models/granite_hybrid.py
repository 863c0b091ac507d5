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
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops import layers as L
from .base import mean_context, register_model
from .stack import StackConfig, StackOfKinds
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

    def _layer_params(self, kind) -> int:
        """As ``GraniteHybrid._init_layer`` builds a layer: the mixer, a
        SwiGLU and two norms."""
        d, h = self.hidden_size, self.mamba_n_heads
        if kind == "mamba":
            inner = h * self.mamba_d_head
            conv = inner + 2 * self.mamba_n_groups * self.mamba_d_state
            mixer = (d * (inner + conv + h)          # z | xBC | dt
                     + (self.mamba_d_conv + self.mamba_conv_bias) * conv
                     + 3 * h + inner + inner * d)    # A, D, dt_bias, norm
        else:
            mixer = 2 * d * self.head_dim * (self.num_heads
                                             + self.num_kv_heads)
        return mixer + 3 * d * self.intermediate_size + 2 * d

    def _layer_mixer_flops(self, kind, seq_len, causal) -> float:
        """An attention layer multiplies a key and a value of head_dim a
        visible pair; a Mamba head writes and reads its [P, N] state once
        a token (2 products of 2 P N FLOPs); x3 training."""
        if kind == "mamba":
            return 12 * self.mamba_n_heads * self.mamba_d_head \
                * self.mamba_d_state
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
class GraniteHybrid(StackOfKinds):
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
            h = c.mamba_n_heads
            inner = h * c.mamba_d_head
            conv = inner + 2 * c.mamba_n_groups * c.mamba_d_state
            # decay init (Mamba-2's): A = U(1, 16) a head; dt =
            # exp(U(log 1e-3, log 0.1)), dt_bias its inverse softplus
            step = jnp.exp(jax.random.uniform(
                next(ks), (*lead_shape, h), minval=np.log(1e-3),
                maxval=np.log(0.1)))
            p["mamba"] = {
                "w_in": w((d, inner + conv + h)),
                "conv_w": jax.random.uniform(
                    next(ks), (*lead_shape, c.mamba_d_conv, conv),
                    minval=-0.5, maxval=0.5).astype(dt),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                "A_log": jnp.log(jax.random.uniform(
                    next(ks), (*lead_shape, h), minval=1.0,
                    maxval=16.0)).astype(dt),
                "D": ones((h,)),
                "norm": ones((inner,)),
                "w_out": w((inner, d), resid_std),
            }
            if c.mamba_conv_bias:
                p["mamba"]["conv_b"] = jax.random.uniform(
                    next(ks), (*lead_shape, conv), minval=-0.5,
                    maxval=0.5).astype(dt)
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
    def _mamba(self, p, h, ssd_fn, conv_fn):
        c = self.config
        b, s, _ = h.shape
        nh, hd, g, n = (c.mamba_n_heads, c.mamba_d_head, c.mamba_n_groups,
                        c.mamba_d_state)
        inner = nh * hd
        f32 = jnp.float32
        proj = h @ p["w_in"]
        z = proj[..., :inner]
        # the convolution and the SiLU: one pass (scope ds.conv)
        xbc = conv_fn(proj[..., inner:2 * inner + 2 * g * n], p["conv_w"],
                      p.get("conv_b"))
        with jax.named_scope("ds.mix_pre"):
            dt = jax.nn.softplus(
                proj[..., 2 * inner + 2 * g * n:].astype(f32)
                + p["dt_bias"].astype(f32))
            x = xbc[..., :inner].reshape(b, s, nh, hd)
            B = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
            C = xbc[..., inner + g * n:].reshape(b, s, g, n)
        y = ssd_fn(x, dt, -jnp.exp(p["A_log"].astype(f32)), B, C,
                   chunk=min(c.mamba_chunk_size, s))
        with jax.named_scope("ds.mix_post"):
            y = y.astype(f32) + x.astype(f32) * p["D"].astype(f32)[:, None]
            y = y.reshape(b, s, inner) * jax.nn.silu(z.astype(f32))
            y = L.rms_norm(y, p["norm"], c.norm_eps).astype(h.dtype)
        return y @ p["w_out"]

    def _attention(self, p, h, attn_fn):
        c = self.config
        b, s, _ = h.shape
        nh, nkv, hd = c.num_heads, c.num_kv_heads, c.head_dim
        q = (h @ p["wq"]).reshape(b, s, nh, hd)
        k = (h @ p["wk"]).reshape(b, s, nkv, hd)
        v = (h @ p["wv"]).reshape(b, s, nkv, hd)
        if c.attention_multiplier is not None:
            # attn_fn applies head_dim ** -0.5
            q = q * (c.attention_multiplier * hd ** 0.5)
        return attn_fn(q, k, v, causal=True).reshape(b, s, nh * hd) @ p["wo"]

    def _mixers(self, attn_fn, act_sharding):
        """(attention, scan, short convolution): the scan's and the
        convolution's kernels run per shard of ``act_sharding`` where the
        mesh has more than one device."""
        from ..ops.ssd import chunk_ssd, sharded_chunk_ssd
        if act_sharding is None:
            return attn_fn, chunk_ssd, L.short_conv
        return (attn_fn, sharded_chunk_ssd(act_sharding),
                L.sharded_short_conv(act_sharding))

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

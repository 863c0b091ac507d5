"""Mellum 2 family: window and full attention layers mixed, every layer
routed.

``Mellum2-12B-A2.5B-Instruct`` (JetBrains, ``config.json``, ``model_type``
``mellum``): 28 pre-norm layers of hidden 2304 whose ``layer_types`` are
three ``sliding_attention`` to one ``full_attention``, each followed by 64
softmax-routed experts of width 896 (top 8, renormalised, no shared
expert)::

    x <- x + Attn_t(rmsnorm(x)) Wo;   x <- x + Routed(rmsnorm(x))
    logits = rmsnorm(x_L) W_head                         (untied head)

**Attention** (both kinds): 32 query heads and 4 key/value heads of
``head_dim`` 128 (NOT hidden / heads: ``attn_head_dim``), no bias, rotary
on the whole head, softmax at ``head_dim ** -0.5``, causal. A kind has its
own window and its own rotary table (``rope_parameters``, one section a
kind; ``ops/layers.py`` ``rotary_embedding``):

- ``sliding_attention``: the last ``sliding_window`` (1024) positions, the
  row's own among them; plain rotary at ``rope_theta`` 500,000.
- ``full_attention``: every earlier position; YaRN (factor 16 over an
  original context of 8192) with its ``attention_factor`` on cos and sin.

**Routed layer** (``moe.sharded_moe.moe_ffn_held`` with the ``softmax``
router): float32 softmax over all ``num_experts`` logits, the top
``moe_top_k``, their probabilities divided by their sum, and the experts
HELD here (the first ``moe_held_experts``: one chip's share under expert
parallelism) through the dropless dispatch. No selection bias, no scaling,
no shared expert, and no auxiliary term: the published config has no
coefficient for one, so ``after_step`` changes no weight and only hands
the engine the layers' counts (``RoutedStackOfKinds._held_metrics``).

Not in the published config and not built: a q/k norm, a multi-token
prediction head. ``intermediate_size`` is unused (every layer is sparse).

**The stack** is ``models/stack.py``'s: the three window layers of a
period under one scan where periods repeat, a layer's kind read from the
key its attention weights lie under (``swa`` | ``full``). Serving and the
pipeline are not here (``StackOfKinds._one_kind_only``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops import layers as L
from .base import mean_context, register_model
from .stack import (ATTENTION_KINDS as _KINDS, RoutedStackConfig,
                    RoutedStackOfKinds, WindowAndFullAttention)
from .transformer import _dense_init

_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}
_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
_PUBLISHED = dict(
    hidden_size=2304, intermediate_size=7168, num_heads=32, num_kv_heads=4,
    attn_head_dim=128, num_layers=28, vocab_size=98304, max_seq_len=131072,
    layer_types=_PERIOD * 7, sliding_window=1024, rope_parameters=_ROPE,
    num_experts=64, moe_top_k=8, moe_intermediate_size=896)


@dataclasses.dataclass
class MellumConfig(RoutedStackConfig):
    # key names as published
    layer_types: tuple | list = ()  # "sliding_attention" (sliding_window
    #                                 holds for this kind alone) |
    #                                 "full_attention", a layer
    rope_parameters: dict = dataclasses.field(default_factory=dict)
    #                                 a rotary table a kind of layer_types:
    #                                 {kind: {rope_type, rope_theta, ...}}
    #                                 (ops/layers.py rotary_embedding)

    def __post_init__(self):
        super().__post_init__()
        self.layer_types = list(self.layer_types)   # as JSON has it

    def layer_kinds(self) -> list[str]:
        return list(self.layer_types)

    def _layer_params(self, kind) -> int:
        """As ``Mellum._init_layer`` builds a layer of either kind:
        grouped-query attention at ``head_dim``, two norms, the router
        over ``num_experts`` and the experts held here."""
        d = self.hidden_size
        return (2 * d * self.head_dim * (self.num_heads + self.num_kv_heads)
                + 2 * d + d * self.num_experts + self._held_params())

    def _layer_idle_params(self, kind) -> float:
        return self._idle_held_params()

    def _layer_mixer_flops(self, kind, seq_len, causal) -> float:
        """A visible pair multiplies a key and a value of head_dim a head
        (x3 training); the window bounds the first kind alone."""
        window = self.sliding_window if kind == "sliding_attention" else None
        return 12 * self.num_heads * self.head_dim * mean_context(
            seq_len, causal, window)


def mellum_config(size: str = "12b-a2.5b", **overrides) -> MellumConfig:
    presets = {
        # a head of 32 on a hidden size of 64 (not 64 / 4), a window a
        # quarter of the sequence, YaRN at the published factor over an
        # original context of 64 (low 0, high 5 of 16 pairs), and the
        # published router
        "tiny": dict(hidden_size=64, intermediate_size=128, num_heads=4,
                     num_kv_heads=2, attn_head_dim=32, num_layers=4,
                     vocab_size=512, max_seq_len=128, layer_types=_PERIOD,
                     sliding_window=32,
                     rope_parameters={
                         "full_attention": {
                             "rope_type": "yarn", "rope_theta": 10000,
                             "factor": 16,
                             "original_max_position_embeddings": 64,
                             "beta_fast": 8, "beta_slow": 1,
                             "attention_factor": 1.2772588722239782},
                         "sliding_attention": {"rope_type": "default",
                                               "rope_theta": 10000}},
                     num_experts=64, moe_top_k=8, moe_intermediate_size=32),
        "12b-a2.5b": _PUBLISHED,
    }
    base = dict(norm_type="rmsnorm", activation="swiglu",
                # a table a kind, built here by rope_parameters: DecoderLM
                # builds its one table for "rope" alone and adds no
                # positions for a name it does not know
                position_embedding="rope_by_kind", use_bias=False,
                tie_embeddings=False, norm_eps=1e-6,
                moe_router_activation="softmax", moe_norm_topk=True,
                router_aux_loss_coef=0.0)
    base.update(presets[size])
    base.update(overrides)
    return MellumConfig(**base)


@register_model("mellum")
class Mellum(WindowAndFullAttention, RoutedStackOfKinds):
    def __init__(self, config: MellumConfig | None = None,
                 size: str | None = None, **overrides):
        if config is not None and (size is not None or overrides):
            raise ValueError(
                "pass either an explicit config or size/overrides, not both")
        c = config or mellum_config(size or "12b-a2.5b", **overrides)
        if (c.moe_router_activation != "softmax" or c.tie_embeddings
                or c.moe_num_shared_experts or c.use_bias
                or c.num_experts <= 0):
            raise NotImplementedError(
                "Mellum has a softmax router over its experts, no shared "
                "expert, no bias and an untied head")
        super().__init__(c)
        self._check_attention_kinds()
        self._ropes = self._rope_tables()

    def after_step(self, params, stats):
        """No weight moves after the optimizer's update (no selection bias
        to balance); the routed layers' counts of the step become the
        ``moe_held_*`` metrics the engine feeds the registry from."""
        return params, self._held_metrics(stats)

    # ---------------- init ----------------
    def _init_layer(self, key, kind, lead_shape=()):
        c = self.config
        dt = c.param_dtype
        d, f, hd = c.hidden_size, c.moe_intermediate_size, c.head_dim
        nh, nkv, e = c.num_heads, c.num_kv_heads, c.held_experts
        std = 0.02
        resid_std = std / (2 * c.num_layers) ** 0.5
        ks = iter(jax.random.split(key, 8))

        def w(shape, scale=std):
            return _dense_init(next(ks), (*lead_shape, *shape), scale, dt)

        def ones(shape):
            return jnp.ones((*lead_shape, *shape), dt)

        return {
            "ln1_scale": ones((d,)), "ln2_scale": ones((d,)),
            # scores of deviation 8, not 0.9: attention that selects, as
            # trained heads do (see init)
            _KINDS[kind]: {"wq": w((d, nh * hd), 3 * std),
                           "wk": w((d, nkv * hd), 3 * std),
                           "wv": w((d, nkv * hd)),
                           "wo": w((nh * hd, d), resid_std)},
            "moe": {
                # logits of unit variance at any width, as the other
                # routed family draws them: the share of positions near
                # the top-k boundary is the same at the tiny preset
                "router": w((d, c.num_experts), d ** -0.5),
                "experts": {"w_gate": w((e, d, f)), "w_up": w((e, d, f)),
                            "w_down": w((e, f, d), resid_std)}},
        }

    def init(self, rng: jax.Array):
        """Seeded weights under which a router sees what it sees in a
        trained model: its own token. At normal(0, 0.02) throughout, a
        layer's output is several times its input's embedding and the
        scores are near level, so attention hands every position the same
        mean of a thousand random values, the next layer leans on it, and
        from the second layer on every token picks nearly the same experts
        (largest load 7 of a possible 8 times the even load). So the
        embedding rows are normal(0, 1) and the query and key projections
        normal(0, 0.06); the rest is normal(0, 0.02) with the residual
        outputs at 0.02 / sqrt(2 layers). Loads then read within a quarter
        of even, and the layers still make 60% of the logits."""
        c = self.config
        dt = c.param_dtype
        d, v = c.hidden_size, c.vocab_size
        keys = jax.random.split(rng, 3)
        return {
            "embed": {"tokens": _dense_init(keys[1], (v, d), 1.0, dt)},
            "layers": self._init_layers(keys[0]),
            "final_norm": {"scale": jnp.ones((d,), dt)},
            "lm_head": _dense_init(keys[2], (d, v), 0.02, dt),
        }

    # ---------------- one layer, the stack ----------------
    def _attention(self, p, h, kind, attn):
        c = self.config
        b, s, _ = h.shape
        nh, nkv, hd = c.num_heads, c.num_kv_heads, c.head_dim
        q = (h @ p["wq"]).reshape(b, s, nh, hd)
        k = (h @ p["wk"]).reshape(b, s, nkv, hd)
        v = (h @ p["wv"]).reshape(b, s, nkv, hd)
        a = L.rotary_attention(attn, q, k, v, self._ropes[kind])
        return a.reshape(b, s, nh * hd) @ p["wo"]

    def _one_layer(self, p, x, mixers):
        from ..moe import sharded_moe
        c = self.config
        kind = "swa" if "swa" in p else "full"
        with jax.named_scope(f"ds.attn_{kind}"):
            h = L.rms_norm(x, p["ln1_scale"], c.norm_eps)
            x = x + self._attention(p[kind], h, kind, mixers[kind])
        h = L.rms_norm(x, p["ln2_scale"], c.norm_eps)
        moe = p["moe"]
        # a share without its peers leaves the routing alone in the
        # backward (``moe_ffn_held``): the whole layer trains its router
        y, counts = sharded_moe.moe_ffn_held(
            h, moe["router"], None, moe["experts"], None, k=c.moe_top_k,
            renormalise=c.moe_norm_topk, router="softmax",
            router_grad=c.held_experts == c.num_experts)
        counts = self._held_blocks(counts, h.shape[0] * h.shape[1])
        return x + y, counts

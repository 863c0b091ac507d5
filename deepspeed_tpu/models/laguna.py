"""Laguna family: window and full attention layers mixed AT TWO WIDTHS, a
sigmoid gate a head on the attention's output, half the head rotated in the
full layers, and softmax-routed experts beside a shared one.

``Laguna-S-2.1`` (poolside, ``config.json``, ``model_type`` ``laguna``): 48
pre-norm layers of hidden 3072 whose ``layer_types`` are one
``full_attention`` to three ``sliding_attention`` (window 512), over 8
key/value heads of ``head_dim`` 128 everywhere. **The query heads differ by
the kind of layer** (``num_attention_heads_per_layer``): 48 in a full layer
(6 a key head, 6144 wide), 72 in a window layer (9 a key head, 9216 wide:
three times the hidden size). Layer 0 carries a dense SwiGLU of 12288
(``mlp_layer_types``), the 47 others 256 routed experts of 1024 (top 10)
beside one shared expert of 1024. For a layer of kind t with H_t heads::

    h = rmsnorm(x)
    q = h W_q  as H_t x 128;   k = h W_k, v = h W_v  as 8 x 128
    q, k rotated by the kind's table (``rope_parameters[t]``) over its
    ROTATED width: a window layer the whole head, plain, theta 1e4; a full
    layer channels [0, 64) under YaRN (``partial_rotary_factor`` 0.5; cos
    and sin times ``attention_factor``), channels [64, 128) as they are
    a_i = softmax_j(q_i . k_{i // r} / sqrt(128)) v_{i // r},  r = H_t / 8,
          j <= row, and row - j < sliding_window in a window layer
    g = sigmoid(h W_g),  W_g [hidden, H_t], float32 logits   (``gating``
          ``per-head``: ONE number a head and a token)
    x <- x + concat_i(g_i a_i) W_o
    h2 = rmsnorm(x);  s = softmax_float32(h2 W_r) over all num_experts
    top moe_top_k of s, over their sum, times routed_scaling_factor (2.5)
    x <- x + sum_{e chosen, e held here} w_e SwiGLU_e(h2) + SwiGLU_shared(h2)
    logits = rmsnorm(x_L) W_head                            (untied head)

**Routed layer**: ``moe.sharded_moe.moe_ffn_held`` with the ``softmax``
router, renormalised, scaled, with the ungated shared expert, over the
experts HELD here (the first ``moe_held_experts``: one chip's share under
expert parallelism). No selection bias and no auxiliary term (the config
has no coefficient): ``after_step`` moves no weight and hands the engine
the layers' counts and, a kind of layer, the step's mean gate (gauge
``ds_attn_gate_mean{kind}``).

What ``config.json`` does not settle (the router's scoring function, where
the gate is read and multiplied, which half is rotated) is listed under
``assumed`` in ``benchmark/configs/laguna-s-2.1-ep32-zero3-1chip.json``.

**The stack** is ``models/stack.py``'s: a layer's kind is (attention kind,
``dense`` | ``sparse``), read from the keys it holds (``swa`` | ``full``
and ``mlp`` | ``moe``); the leading dense layers are unrolled; what the
window-and-full families share is ``WindowAndFullAttention``. Serving and
the pipeline are not here (``StackOfKinds._one_kind_only``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops import layers as L
from .base import mean_context, register_model
from .stack import (ATTENTION_KINDS, RoutedStackConfig, RoutedStackOfKinds,
                    WindowAndFullAttention)
from .transformer import _dense_init

_ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1},
}
_PERIOD = ["full_attention"] + ["sliding_attention"] * 3
_HEADS = {"full_attention": 48, "sliding_attention": 72}
_PUBLISHED = dict(
    hidden_size=3072, intermediate_size=12288, num_heads=48, num_kv_heads=8,
    attn_head_dim=128, num_layers=48, vocab_size=100352,
    max_seq_len=1048576, layer_types=_PERIOD * 12,
    mlp_layer_types=["dense"] + ["sparse"] * 47,
    num_attention_heads_per_layer=[_HEADS[t] for t in _PERIOD * 12],
    sliding_window=512, rope_parameters=_ROPE, num_experts=256,
    moe_top_k=10, moe_intermediate_size=1024,
    shared_expert_intermediate_size=1024, routed_scaling_factor=2.5)


@dataclasses.dataclass
class LagunaConfig(RoutedStackConfig):
    # key names as published
    layer_types: tuple | list = ()  # "sliding_attention" (sliding_window
    #                                 holds for this kind alone) |
    #                                 "full_attention", a layer
    mlp_layer_types: tuple | list = ()  # "dense" | "sparse" a layer
    #                                 (empty: every layer sparse)
    num_attention_heads_per_layer: tuple | list = ()  # query heads a
    #                                 layer, ONE count a kind of layer_types
    #                                 (empty: num_heads in every layer)
    rope_parameters: dict = dataclasses.field(default_factory=dict)
    #                                 a rotary table a kind of layer_types:
    #                                 {kind: {rope_type, rope_theta,
    #                                 partial_rotary_factor, ...}}
    #                                 (ops/layers.py rotary_embedding)
    shared_expert_intermediate_size: int = 0  # the always-on expert's
    #                                 width (0: none)

    def __post_init__(self):
        super().__post_init__()
        n = self.num_layers
        self.layer_types = list(self.layer_types)   # as JSON has it
        self.mlp_layer_types = list(self.mlp_layer_types) or ["sparse"] * n
        self.num_attention_heads_per_layer = list(
            self.num_attention_heads_per_layer) or [self.num_heads] * n

    @property
    def kind_heads(self) -> dict:
        """{layer_types entry: its query heads}: ONE count a kind (the
        layers of a kind are stacked under one scan)."""
        heads = {}
        for t, h in zip(self.layer_types,
                        self.num_attention_heads_per_layer):
            if heads.setdefault(t, h) != h:
                raise ValueError(
                    f"num_attention_heads_per_layer gives {t} layers "
                    f"{heads[t]} and {h} query heads: one count a kind")
        return heads

    @property
    def gating(self) -> str:
        """The published key: the attention output's gate is one sigmoid
        a head and a token, the one form built."""
        return "per-head"

    @property
    def gating_types(self) -> list:
        """The published list: the same gate in every layer."""
        return ["per_head"] * self.num_layers

    @property
    def mlp_only_layers(self) -> list:
        """The published list: the layers whose FFN is dense."""
        return [i for i, t in enumerate(self.mlp_layer_types)
                if t == "dense"]

    def layer_kinds(self) -> list[tuple[str, str]]:
        """(attention kind, ``dense`` | ``sparse``) of each layer."""
        return list(zip(self.layer_types, self.mlp_layer_types))

    def lead_layers(self) -> int:
        """The leading dense layers."""
        sparse = [i for i, t in enumerate(self.mlp_layer_types)
                  if t != "dense"]
        return sparse[0] if sparse else len(self.mlp_layer_types)

    def _shared_params(self) -> int:
        return 3 * self.hidden_size * self.shared_expert_intermediate_size

    def _layer_params(self, kind) -> int:
        """As ``Laguna._init_layer`` builds a layer: grouped-query
        attention at THE KIND'S head count with its gate, two norms, and
        the dense SwiGLU or the router over ``num_experts``, the shared
        expert and the experts held here."""
        d, hd = self.hidden_size, self.head_dim
        nh = self.kind_heads[kind[0]]
        attn = 2 * d * hd * (nh + self.num_kv_heads) + d * nh
        if kind[1] == "dense":
            ff = 3 * d * self.intermediate_size
        else:
            ff = (d * self.num_experts + self._shared_params()
                  + self._held_params())
        return attn + 2 * d + ff

    def _layer_idle_params(self, kind) -> float:
        return self._idle_held_params() if kind[1] == "sparse" else 0

    def _layer_mixer_flops(self, kind, seq_len, causal) -> float:
        """A visible pair multiplies a key and a value of head_dim a head
        of THE KIND (x3 training); the window bounds the first kind
        alone."""
        window = (self.sliding_window if kind[0] == "sliding_attention"
                  else None)
        return 12 * self.kind_heads[kind[0]] * self.head_dim * mean_context(
            seq_len, causal, window)


def laguna_config(size: str = "s-2.1", **overrides) -> LagunaConfig:
    tiny_rope = {
        "full_attention": {
            "rope_theta": 10000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 64, "beta_slow": 1,
            "beta_fast": 8, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
    tiny_types = ["full_attention"] + ["sliding_attention"] * 3 + [
        "full_attention"]
    presets = {
        # a leading dense full layer, three window layers under one scan
        # and a routed full layer; a head of 32 on a hidden size of 64 over
        # 2 key heads, 3 query heads a key head in a window layer (192
        # wide: three times the hidden size, as published) and 2 in a full
        # one; half of a full layer's head rotated under YaRN from an
        # original context of 64 (8 pairs: low 0, high 3), a window a
        # quarter of the sequence; the published router
        "tiny": dict(hidden_size=64, intermediate_size=128, num_heads=4,
                     num_kv_heads=2, attn_head_dim=32, num_layers=5,
                     vocab_size=512, max_seq_len=128,
                     layer_types=tiny_types,
                     mlp_layer_types=["dense"] + ["sparse"] * 4,
                     num_attention_heads_per_layer=[
                         {"full_attention": 4, "sliding_attention": 6}[t]
                         for t in tiny_types],
                     sliding_window=32, rope_parameters=tiny_rope,
                     num_experts=256, moe_top_k=10, moe_intermediate_size=32,
                     shared_expert_intermediate_size=32,
                     routed_scaling_factor=2.5),
        "s-2.1": _PUBLISHED,
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
    if ("layer_types" in overrides
            and "num_attention_heads_per_layer" not in overrides):
        # a cut of the layers keeps the preset's head count a kind
        heads = dict(zip(presets[size]["layer_types"],
                         presets[size]["num_attention_heads_per_layer"]))
        base["num_attention_heads_per_layer"] = [
            heads.get(t, base["num_heads"]) for t in base["layer_types"]]
    return LagunaConfig(**base)


@register_model("laguna")
class Laguna(WindowAndFullAttention, RoutedStackOfKinds):
    _more_rules = ((r"(mlp|shared)/(w_up|w_gate)$", (None, "tp")),
                   (r"(mlp|shared)/w_down$", ("tp", None)))

    def __init__(self, config: LagunaConfig | None = None,
                 size: str | None = None, **overrides):
        if config is not None and (size is not None or overrides):
            raise ValueError(
                "pass either an explicit config or size/overrides, not both")
        c = config or laguna_config(size or "s-2.1", **overrides)
        if (c.moe_router_activation != "softmax" or c.tie_embeddings
                or c.use_bias or c.num_experts <= 0):
            raise NotImplementedError(
                "Laguna has a softmax router over its experts, no bias and "
                "an untied head")
        n = c.num_layers
        if (len(c.mlp_layer_types) != n
                or set(c.mlp_layer_types) - {"dense", "sparse"}
                or len(c.num_attention_heads_per_layer) != n):
            raise ValueError(
                f"Laguna needs {n} mlp_layer_types of 'dense' | 'sparse' "
                f"and {n} num_attention_heads_per_layer, not "
                f"{c.mlp_layer_types} and {c.num_attention_heads_per_layer}")
        super().__init__(c)
        self._check_attention_kinds()
        for t, nh in c.kind_heads.items():
            if nh % c.num_kv_heads:
                raise ValueError(
                    f"{nh} query heads of a {t} layer over "
                    f"{c.num_kv_heads} key heads")
        self._ropes = self._rope_tables()
        self._heads = {ATTENTION_KINDS[t]: nh
                       for t, nh in c.kind_heads.items()}

    def after_step(self, params, stats):
        """No weight moves after the optimizer's update (no selection bias
        to balance). The routed layers' counts of the step become the
        ``moe_held_*`` metrics, and every layer's gate sums the step's
        mean gate a kind of layer (``attn_gate_mean_<kind>``: device
        scalars; sums and counts, so micro-batches weigh as their
        tokens)."""
        routed = {group: {} for group in stats}
        gates = {}
        for group, slots in stats.items():
            for slot, counts in slots.items():
                counts = dict(counts)
                for kind in ATTENTION_KINDS.values():
                    if f"gate_{kind}" in counts:
                        total = jnp.sum(counts.pop(f"gate_{kind}").reshape(
                            -1, 2), axis=0)
                        gates[kind] = gates.get(kind, 0.0) + total
                if counts:
                    routed[group][slot] = counts
        metrics = self._held_metrics(routed)
        metrics.update({f"attn_gate_mean_{kind}": total[0] / total[1]
                        for kind, total in gates.items()})
        return params, metrics

    @staticmethod
    def record_step_metrics(reg, metrics: dict) -> None:
        """The routed families' recorder, and gauge
        ``ds_attn_gate_mean{kind}``: the last finished step's mean of
        ``sigmoid(h W_g)`` over tokens, heads and layers of a kind."""
        metrics = dict(metrics)
        g = reg.gauge("ds_attn_gate_mean",
                      "the step's mean attention-output gate a head, over "
                      "tokens, heads and the layers of a kind")
        for kind in ATTENTION_KINDS.values():
            mean = metrics.pop(f"attn_gate_mean_{kind}", None)
            if mean is not None:
                g.set(float(mean), kind=kind)
        RoutedStackOfKinds.record_step_metrics(reg, metrics)

    # ---------------- init ----------------
    def _init_layer(self, key, kind, lead_shape=()):
        c = self.config
        dt = c.param_dtype
        d, hd, nkv = c.hidden_size, c.head_dim, c.num_kv_heads
        nh = c.kind_heads[kind[0]]
        std = 0.02
        resid_std = std / (2 * c.num_layers) ** 0.5
        ks = iter(jax.random.split(key, 16))

        def w(shape, scale=std):
            return _dense_init(next(ks), (*lead_shape, *shape), scale, dt)

        def ones(shape):
            return jnp.ones((*lead_shape, *shape), dt)

        def swiglu(f):
            return {"w_gate": w((d, f)), "w_up": w((d, f)),
                    "w_down": w((f, d), resid_std)}

        p = {
            "ln1_scale": ones((d,)), "ln2_scale": ones((d,)),
            # scores of deviation 8, not 0.9: attention that selects, as
            # trained heads do (models/mellum.py init); the gate's logits
            # of unit deviation, so that a head's gate lies anywhere in
            # (0.1, 0.9) and a missing or widened gate is seen
            ATTENTION_KINDS[kind[0]]: {
                "wq": w((d, nh * hd), 3 * std),
                "wk": w((d, nkv * hd), 3 * std),
                "wv": w((d, nkv * hd)),
                "wg": w((d, nh), d ** -0.5),
                "wo": w((nh * hd, d), resid_std)},
        }
        if kind[1] == "dense":
            p["mlp"] = swiglu(c.intermediate_size)
            return p
        f, e = c.moe_intermediate_size, c.held_experts
        p["moe"] = {
            # logits of unit variance at any width, as the other routed
            # families draw them
            "router": w((d, c.num_experts), d ** -0.5),
            "experts": {"w_gate": w((e, d, f)), "w_up": w((e, d, f)),
                        "w_down": w((e, f, d), resid_std)}}
        if c.shared_expert_intermediate_size:
            p["moe"]["shared"] = swiglu(c.shared_expert_intermediate_size)
        return p

    def init(self, rng: jax.Array):
        """Seeded weights under which a router sees its own token, as
        ``models/mellum.py`` ``init`` has them and for its reason: the
        embedding rows normal(0, 1), the query and key projections
        normal(0, 0.06), the rest normal(0, 0.02) with the residual
        outputs at 0.02 / sqrt(2 layers); the gate's projection
        normal(0, hidden_size^-1/2)."""
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
        """(the attention's output behind W_o, the gate's [sum, count]):
        the projections and the reshape at the kind's head count, the
        rotation by the kind's table over the kind's rotated width, the
        mixer of the kind, the gate a head."""
        c = self.config
        b, s, _ = h.shape
        nkv, hd = c.num_kv_heads, c.head_dim
        nh = self._heads[kind]
        q = (h @ p["wq"]).reshape(b, s, nh, hd)
        k = (h @ p["wk"]).reshape(b, s, nkv, hd)
        v = (h @ p["wv"]).reshape(b, s, nkv, hd)
        a = L.rotary_attention(attn, q, k, v, self._ropes[kind])
        with jax.named_scope("ds.attn_gate"):
            g = jax.nn.sigmoid(jnp.matmul(
                h, p["wg"], preferred_element_type=jnp.float32))
            a = (a.astype(jnp.float32) * g[..., None]).astype(h.dtype)
            gate = jnp.stack([jnp.sum(g), jnp.float32(g.size)])
        return a.reshape(b, s, nh * hd) @ p["wo"], gate

    def _one_layer(self, p, x, mixers):
        from ..moe import sharded_moe
        c = self.config
        kind = "swa" if "swa" in p else "full"
        with jax.named_scope(f"ds.attn_{kind}"):
            h = L.rms_norm(x, p["ln1_scale"], c.norm_eps)
            y, gate = self._attention(p[kind], h, kind, mixers[kind])
            x = x + y
        counts = {f"gate_{kind}": gate}
        if "mlp" in p:
            with jax.named_scope("ds.mlp"):
                h = L.rms_norm(x, p["ln2_scale"], c.norm_eps)
                return x + self._mlp(p["mlp"], h)[0], counts
        h = L.rms_norm(x, p["ln2_scale"], c.norm_eps)
        moe = p["moe"]
        # a share without its peers leaves the routing alone in the
        # backward (``moe_ffn_held``): the whole layer trains its router
        y, held = sharded_moe.moe_ffn_held(
            h, moe["router"], None, moe["experts"], moe.get("shared"),
            k=c.moe_top_k, renormalise=c.moe_norm_topk,
            scaling=float(c.routed_scaling_factor), router="softmax",
            router_grad=c.held_experts == c.num_experts)
        held = self._held_blocks(held, h.shape[0] * h.shape[1])
        return x + y, {**counts, **held}

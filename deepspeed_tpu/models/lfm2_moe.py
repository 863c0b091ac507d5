"""LFM2-MoE family: gated short-convolution layers with a grouped-query
attention layer among every few, a dense SwiGLU in the leading layers and
bias-corrected sigmoid-routed experts, with no shared expert, after them.

``LFM2-24B-A2B`` (LiquidAI, ``config.json``, ``model_type`` ``lfm2_moe``):
40 pre-norm layers of hidden 2048; ``layer_types`` is ``conv conv
full_attention``, nine times ``conv conv conv full_attention``, ``conv``
(30 to 10: a period of four at 3 : 1); the first ``num_dense_layers`` (2,
both conv) carry a dense SwiGLU of 11776, the 38 others 64 routed experts
of 1536 (top 4). ``norm`` is a plain RMSNorm ``x rsqrt(mean x^2 + eps) w``,
``w`` from 1, as the published modeling code (``modeling_lfm2_moe.py``)
has every line below::

    x <- x + Op_l(norm(x, w_op));   x <- x + FF_l(norm(x, w_ffn))
    logits = norm(x_L, w_emb) E^T     ("embedding_norm" is applied LAST;
                                       the head is the table E)

**Gated short convolution** (``conv`` layers; C = hidden channels, n =
``conv_L_cache`` taps)::

    [B | Cg | X] = h W_in                    (C -> 3 C, three equal column
                                              runs, in this order)
    u = B * X                                (elementwise)
    c_t = sum_{i<n} w[i] u_{t-(n-1)+i}       (causal, depthwise, zeros
                                              before the start, NO bias)
    y = (Cg * c) W_out                       (C -> C)

There is no activation anywhere in it: the two gates are linear. The gates
and the taps are ONE pass of ``ops.layers.gated_short_conv`` over the
projection's output.

**Attention** (``full_attention`` layers; H query heads on Hkv key heads
of D = hidden / H)::

    q = norm_D(h W_q, w_q);  k = norm_D(h W_k, w_k)    (QK-norm, plain w)
    q, k rotated over the WHOLE head (rotate-half pairs (i, i + D / 2) at
    ``rope_theta``), after the norm;   v = h W_v
    y = softmax_causal(q k^T / sqrt(D)) v W_o          no bias, no window,
                                                       no gate

**Dense FF** (layers below ``num_dense_layers``): ``W_2 (silu(W_1 h) *
W_3 h)`` of ``intermediate_size``. **Routed FF** (all others,
``moe.sharded_moe.moe_ffn_held`` with the ``sigmoid`` router and no shared
expert): ``s = sigmoid(h W_r)`` in float32 over all ``num_experts``; the
top ``moe_top_k`` of ``s + b`` (``b`` the expert bias: SELECTION only, no
gradient); the weights are ``s`` at the chosen experts over their sum,
times ``routed_scaling_factor``; the sum of the chosen SwiGLU experts of
``moe_intermediate_size`` HELD here (the first ``moe_held_experts``: one
chip's share under expert parallelism). Nothing else is added: a token
whose experts all lie on other chips gets nothing from the layer. There is
no auxiliary term (the config has no coefficient): ``optimizer_frozen``
keeps the optimizer off ``b``, and ``after_step`` moves it against its
expert's load in the step (``balance_bias``). Departure: the published
renormalisation divides by ``(sum + 1e-6)``, ``sigmoid_top_k`` by ``(sum +
1e-20)``: under 1e-6 of a weight.

**The stack** is ``models/stack.py``'s: a layer's kind is (mixer, channel),
read from the keys it holds (``conv`` | ``attn`` and ``mlp`` | ``moe``);
the leading dense layers are unrolled. Serving and the pipeline are not
here (``StackOfKinds._one_kind_only``): a convolution's tail has no cache
in ``inference/``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import layers as L
from .base import mean_context, register_model
from .stack import RoutedStackConfig, RoutedStackOfKinds
from .transformer import _dense_init


@dataclasses.dataclass
class Lfm2MoeConfig(RoutedStackConfig):
    # key names as published
    layer_types: tuple | list = ()  # "conv" | "full_attention" a layer
    num_dense_layers: int = 0       # leading layers whose FF is dense
    conv_L_cache: int = 3           # taps of the short convolution
    conv_bias: bool = False
    use_expert_bias: bool = True    # the router's selection bias
    # not published
    qk_norm_init: float = 1.0       # what w_q and w_k start from (init)

    def __post_init__(self):
        super().__post_init__()
        self.layer_types = list(self.layer_types)   # as JSON has it

    @property
    def rope_parameters(self) -> dict:
        """The published ``rope_parameters`` group of the model as built."""
        return {"rope_theta": self.rope_theta, "rope_type": "default"}

    def layer_kinds(self) -> list[tuple[str, str]]:
        """(token mixer, channel mixer) of each layer, ``conv`` | ``attn``
        and ``dense`` | ``moe``."""
        return [("conv" if t == "conv" else "attn",
                 "dense" if i < self.num_dense_layers else "moe")
                for i, t in enumerate(self.layer_types)]

    def lead_layers(self) -> int:
        return self.num_dense_layers

    def _layer_params(self, kind) -> int:
        """As ``Lfm2Moe._init_layer`` builds a layer: the mixer, two norms
        and the channel mixer (the router, its bias and the experts held)."""
        d = self.hidden_size
        mixer, channel = kind
        if mixer == "conv":
            mix = 4 * d * d + self.conv_L_cache * d     # in, taps, out
        else:
            hd = self.head_dim
            mix = (2 * d * hd * (self.num_heads + self.num_kv_heads)
                   + 2 * hd)
        if channel == "dense":
            ff = 3 * d * self.intermediate_size
        else:
            ff = (d + 1) * self.num_experts + self._held_params()
        return mix + 2 * d + ff

    def _layer_idle_params(self, kind) -> float:
        return self._idle_held_params() if kind[1] == "moe" else 0

    def _layer_mixer_flops(self, kind, seq_len, causal) -> float:
        """An attention layer multiplies a key and a value of head_dim a
        visible pair and head; a conv layer's taps and gates are a few
        products a channel, whatever the sequence length; x3 training."""
        if kind[0] == "conv":
            return 6 * (self.conv_L_cache + 2) * self.hidden_size
        return 12 * self.num_heads * self.head_dim * mean_context(
            seq_len, causal)


_PERIOD = ["conv", "conv", "conv", "full_attention"]
_PUBLISHED = dict(
    hidden_size=2048, intermediate_size=11776, num_heads=32, num_kv_heads=8,
    num_layers=40, vocab_size=65536, max_seq_len=128000, rope_theta=1000000,
    layer_types=_PERIOD[1:] + _PERIOD * 9 + ["conv"], num_dense_layers=2,
    conv_L_cache=3, num_experts=64, moe_top_k=4, moe_intermediate_size=1536,
    routed_scaling_factor=1)


def lfm2_moe_config(size: str = "24b-a2b", **overrides) -> Lfm2MoeConfig:
    presets = {
        # a leading dense conv layer and one period, with the attention
        # layer first as it follows the published leading layers; a head
        # of 16 with 2 query heads a key head; the published router (the
        # agreement check's mask depends on the share of experts near the
        # boundary)
        "tiny": dict(hidden_size=64, intermediate_size=128, num_heads=4,
                     num_kv_heads=2, num_layers=5, vocab_size=512,
                     max_seq_len=128, rope_theta=10000,
                     layer_types=["conv", "full_attention", "conv", "conv",
                                  "conv"],
                     num_dense_layers=1, conv_L_cache=3, num_experts=64,
                     moe_top_k=4, moe_intermediate_size=32,
                     routed_scaling_factor=1),
        "24b-a2b": _PUBLISHED,
    }
    base = dict(norm_type="rmsnorm", activation="swiglu",
                position_embedding="rope", use_bias=False,
                tie_embeddings=True, norm_eps=1e-5,
                moe_router_activation="sigmoid", moe_norm_topk=True,
                router_aux_loss_coef=0.0)
    base.update(presets[size])
    base.update(overrides)
    return Lfm2MoeConfig(**base)


@register_model("lfm2_moe")
class Lfm2Moe(RoutedStackOfKinds):
    def __init__(self, config: Lfm2MoeConfig | None = None,
                 size: str | None = None, **overrides):
        if config is not None and (size is not None or overrides):
            raise ValueError(
                "pass either an explicit config or size/overrides, not both")
        c = config or lfm2_moe_config(size or "24b-a2b", **overrides)
        if len(c.layer_types) != c.num_layers or set(c.layer_types) - {
                "conv", "full_attention"}:
            raise ValueError(
                f"Lfm2Moe needs {c.num_layers} layer_types of 'conv' | "
                f"'full_attention', not {c.layer_types}")
        if (c.moe_router_activation != "sigmoid" or not c.use_expert_bias
                or not c.tie_embeddings or c.use_bias or c.conv_bias
                or c.num_experts <= 0 or c.moe_num_shared_experts):
            raise NotImplementedError(
                "Lfm2Moe has a sigmoid router with an expert bias and no "
                "shared expert, no bias in a projection or a convolution "
                "and a tied head")
        if c.held_experts > c.num_experts:
            raise ValueError(
                f"{c.held_experts} experts held of the router's "
                f"{c.num_experts}")
        super().__init__(c)

    def optimizer_frozen(self) -> str:
        """Leaves the optimizer leaves alone (the engine zeroes their
        updates): the expert bias moves by ``after_step``."""
        return r"router_bias$"

    def after_step(self, params, stats):
        """The trainer's half of the bias-corrected router, on the step's
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

        def full(shape, value=1.0):
            return jnp.full((*lead_shape, *shape), value, dt)

        p = {"ln1_scale": full((d,)), "ln2_scale": full((d,))}
        mixer, channel = kind
        if mixer == "conv":
            n = c.conv_L_cache
            p["conv"] = {
                "w_in": w((d, 3 * d)),
                # a depthwise Conv1d's default: U(-1, 1) / sqrt(fan-in n)
                "taps": jax.random.uniform(
                    next(ks), (*lead_shape, n, d), minval=-n ** -0.5,
                    maxval=n ** -0.5).astype(dt),
                "w_out": w((d, d), resid_std),
            }
        else:
            nh, nkv, hd = c.num_heads, c.num_kv_heads, c.head_dim
            p["attn"] = {
                "wq": w((d, nh * hd)), "wk": w((d, nkv * hd)),
                "wv": w((d, nkv * hd)),
                "q_norm": full((hd,), c.qk_norm_init),
                "k_norm": full((hd,), c.qk_norm_init),
                "wo": w((nh * hd, d), resid_std),
            }
        if channel == "dense":
            f = c.intermediate_size
            p["mlp"] = {"w_gate": w((d, f)), "w_up": w((d, f)),
                        "w_down": w((f, d), resid_std)}
        else:
            f, e = c.moe_intermediate_size, c.held_experts
            p["moe"] = {
                # logits of unit variance at any width, as the other
                # routed families draw them
                "router": w((d, c.num_experts), d ** -0.5),
                # drawn, so that selection (scores + bias) and weighting
                # (scores) differ, small beside the scores' spread;
                # after_step moves it
                "router_bias": w((c.num_experts,), 0.01),
                "experts": {"w_gate": w((e, d, f)), "w_up": w((e, d, f)),
                            "w_down": w((e, f, d), resid_std)},
            }
        return p

    def init(self, rng: jax.Array):
        """Seeded weights: every matrix normal(0, 0.02), THE TABLE AMONG
        THEM (it is the head), the residual outputs at 0.02 / sqrt(2
        layers), the router ``hidden_size ** -0.5``. The routed families
        with an untied head draw their embedding rows normal(0, 1) so that
        a router sees its own token (``models/mellum.py`` ``init``); under
        a tied head that makes a position's own token its largest logit by
        45 deviations, a loss of hidden_size and a step that learns one
        common direction to undo it (``PERF.md`` section 6, PR 54). Here
        the leading layer's convolution and dense SwiGLU, both functions
        of the last few tokens alone, are what the first router sees."""
        c = self.config
        keys = jax.random.split(rng, 2)
        return {
            "embed": {"tokens": _dense_init(
                keys[1], (c.vocab_size, c.hidden_size), 0.02,
                c.param_dtype)},
            "layers": self._init_layers(keys[0]),
            "final_norm": {"scale": jnp.ones((c.hidden_size,),
                                             c.param_dtype)},
        }

    # ---------------- the mixers ----------------
    def _conv(self, p, h, conv_fn):
        """One gated short convolution on the normed ``h``: the input
        projection (ds.gconv_in), the gates and the taps as ONE pass of
        ``conv_fn`` over its output (scope ds.gconv_mix, opened by the
        kernels' caller), the output projection (ds.gconv_out)."""
        with jax.named_scope("ds.gconv_in"):
            bcx = h @ p["w_in"]
        y = conv_fn(bcx, p["taps"])
        with jax.named_scope("ds.gconv_out"):
            return y @ p["w_out"]

    def _attention(self, p, h, attn_fn):
        c = self.config
        b, s, _ = h.shape
        nh, nkv, hd = c.num_heads, c.num_kv_heads, c.head_dim
        q = (h @ p["wq"]).reshape(b, s, nh, hd)
        k = (h @ p["wk"]).reshape(b, s, nkv, hd)
        v = (h @ p["wv"]).reshape(b, s, nkv, hd)
        with jax.named_scope("ds.qk_norm"):
            q = L.rms_norm(q, p["q_norm"], c.norm_eps)
            k = L.rms_norm(k, p["k_norm"], c.norm_eps)
        a = L.rotary_attention(attn_fn, q, k, v, self._rotary, causal=True)
        return a.reshape(b, s, nh * hd) @ p["wo"]

    def _routed(self, p, h):
        """(out, counts) of a routed layer: a share without its peers
        leaves the routing alone in the backward (``moe_ffn_held``); the
        whole layer trains its router."""
        from ..moe import sharded_moe
        c = self.config
        y, counts = sharded_moe.moe_ffn_held(
            h, p["router"], p["router_bias"], p["experts"], None,
            k=c.moe_top_k, renormalise=c.moe_norm_topk,
            scaling=float(c.routed_scaling_factor), router="sigmoid",
            router_grad=c.held_experts == c.num_experts)
        # the blocks the dispatch swept, from the load and its own rule
        block = sharded_moe.held_block(h.shape[0] * h.shape[1], c.moe_top_k,
                                       c.num_experts)
        blocks = jnp.sum(-(-counts["load"][:c.held_experts] // block))
        return y, {**counts, "blocks": blocks, "block": jnp.int32(block)}

    # ---------------- one layer, the stack ----------------
    def _one_layer(self, p, x, mixers):
        c = self.config
        attn_fn, conv_fn = mixers
        if "conv" in p:
            with jax.named_scope("ds.gconv"):
                h = L.rms_norm(x, p["ln1_scale"], c.norm_eps)
                x = x + self._conv(p["conv"], h, conv_fn)
        else:
            with jax.named_scope("ds.attn"):
                h = L.rms_norm(x, p["ln1_scale"], c.norm_eps)
                x = x + self._attention(p["attn"], h, attn_fn)
        if "mlp" in p:
            with jax.named_scope("ds.mlp"):
                h = L.rms_norm(x, p["ln2_scale"], c.norm_eps)
                return x + self._mlp(p["mlp"], h)[0], {}
        y, counts = self._routed(
            p["moe"], L.rms_norm(x, p["ln2_scale"], c.norm_eps))
        return x + y, counts

    def _mixers(self, attn_fn, act_sharding):
        """(attention, gated short convolution): on a mesh of more than one
        device the convolution's kernels run per shard of
        ``act_sharding``."""
        if act_sharding is None:
            return attn_fn, L.gated_short_conv
        return attn_fn, L.sharded_gated_short_conv(act_sharding)

    # ---------------- sharding ----------------
    def partition_rules(self):
        """Tensor-parallel rules by head / FFN / expert dimension; the
        leading axis of a ``period`` stack is the scan's and stays whole.
        A conv mixer stays whole (its input projection's columns are three
        runs of channels)."""
        def both(pattern, *spec):
            return [(rf"layers/period/.*{pattern}", P(None, *spec)),
                    (rf"layers/(lead|tail)/.*{pattern}", P(*spec))]

        rules = [(r"embed/tokens", P("tp", None))]
        for pattern, spec in [
                (r"attn/(wq|wk|wv)$", (None, "tp")),
                (r"attn/wo$", ("tp", None)),
                (r"experts/(w_up|w_gate)$", ("ep", None, "tp")),
                (r"experts/w_down$", ("ep", "tp", None)),
                (r"mlp/(w_up|w_gate)$", (None, "tp")),
                (r"mlp/w_down$", ("tp", None))]:
            rules += both(pattern, *spec)
        return rules

"""Qwen3-Next family: Gated DeltaNet and gated softmax attention layers
mixed, every layer routed.

``Qwen3-Next-80B-A3B-Instruct`` (Qwen, ``config.json``, ``model_type``
``qwen3_next``): 48 pre-norm layers of hidden 2048; ``full_attention_
interval`` 4 makes a period of three ``linear_attention`` layers (Gated
DeltaNet) and one ``full_attention`` layer (gated softmax attention); each
is followed by 512 softmax-routed experts of width 512 (top 10,
renormalised) beside one shared expert behind its own sigmoid gate. Every
RMSNorm over the hidden size or an attention head is ``x rsqrt(mean x^2 +
eps) (1 + w)`` with ``w`` from 0 (``_norm``)::

    x <- x + Mix_l(norm(x, w1));   x <- x + Experts_l(norm(x, w2))
    logits = norm(x_L, w) W_head                          (untied head)

**Gated DeltaNet** (``linear_num_key_heads`` Hk key heads, ``linear_num_
value_heads`` Hv value heads, of ``linear_key_head_dim`` dk and
``linear_value_head_dim`` dv)::

    [q | k | v | z] = h W_qkvz   (Hk dk + Hk dk + Hv dv + Hv dv)
    [b | a] = h W_ba             (Hv + Hv)
    q = l2norm(silu(conv4(q))) / sqrt(dk);  k = l2norm(silu(conv4(k)))
    v = silu(conv4(v));   key head j serves value heads j Hv/Hk ...
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   a value head
    o = chunk_kda(q, k, v, g, beta)      the delta rule with a gate a HEAD
    y = (rmsnorm_dv(o) w_o * silu(z)) W_out      (w_o a plain weight from 1)

``conv4`` is one causal depthwise convolution (``linear_conv_kernel_dim``
taps, no bias) over the channels of ``[q | k | v]``; with the SiLU and the
l2 norm it is a pass of ``ops.layers.short_conv`` each. The scan is
``ops/kda.py``'s, which takes ``g`` [B, S, Hv] and q, k at their Hk heads
(its kernels read a key head where each of its value heads needs it).

**Gated attention** (``num_heads`` H query and ``num_kv_heads`` key/value
heads of ``attn_head_dim`` D, NOT hidden / heads)::

    [q | gate] = h W_q  as H heads of 2 D: a head's first D are its query,
                        its last D its gate
    q = norm_D(q, w_q);  k = norm_D(h W_k, w_k)        (QK-norm, (1 + w))
    q, k rotated on their first ``rotary_pct`` D dimensions (rotate-half
    pairs (i, i + rot / 2) at ``rope_theta``), the rest untouched
    y = (softmax_causal(q k^T / sqrt(D)) v * sigmoid(gate)) W_o

**Routed layer** (``moe.sharded_moe.moe_ffn_held``, ``softmax`` router):
float32 softmax over all ``num_experts`` logits, the top ``moe_top_k``,
their probabilities divided by their sum, the experts HELD here (the first
``moe_held_experts``: one chip's share under expert parallelism) through
the dropless dispatch, plus the shared expert of ``shared_expert_
intermediate_size`` times ``sigmoid(h w_s)``. No auxiliary term (the
published config has no coefficient for one) and no multi-token prediction
module (no key for it): ``after_step`` changes no weight and hands the
engine the layers' counts (``RoutedStackOfKinds._held_metrics``).

**The stack** is ``models/stack.py``'s: whole periods under one scan, a
layer's kind read from the key its mixer's weights lie under (``gdn`` |
``attn``). Serving and the pipeline are not here (``StackOfKinds.
_one_kind_only``): a Gated DeltaNet layer's state and its convolution's
tail have no cache in ``inference/``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import layers as L
from .base import mean_context, register_model
from .stack import RoutedStackConfig, RoutedStackOfKinds
from .transformer import _dense_init


@dataclasses.dataclass
class Qwen3NextConfig(RoutedStackConfig):
    # key names as published
    full_attention_interval: int = 4    # every n-th layer is full_attention,
    #                                     the others linear_attention
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    shared_expert_intermediate_size: int = 0    # 0: no shared expert
    decoder_sparse_step: int = 1        # 1: every layer is routed
    mlp_only_layers: tuple | list = ()  # layers with a dense FFN: none
    # not published
    qk_norm_init: float = 0.0       # what w_q and w_k start from (init)

    def __post_init__(self):
        super().__post_init__()
        self.mlp_only_layers = list(self.mlp_only_layers)   # as JSON has it

    def layer_kinds(self) -> list[str]:
        n = self.full_attention_interval
        return ["full_attention" if (i + 1) % n == 0 else "linear_attention"
                for i in range(self.num_layers)]

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.rotary_pct)

    def _layer_params(self, kind) -> int:
        """As ``Qwen3Next._init_layer`` builds a layer of either kind."""
        d = self.hidden_size
        if kind == "linear_attention":
            key = self.linear_num_key_heads * self.linear_key_head_dim
            hv = self.linear_num_value_heads
            val = hv * self.linear_value_head_dim
            mixer = (d * (2 * key + 2 * val) + d * 2 * hv          # qkvz, ba
                     + self.linear_conv_kernel_dim * (2 * key + val)
                     + 2 * hv + self.linear_value_head_dim + val * d)
        else:
            hd = self.head_dim
            mixer = (d * hd * (2 * self.num_heads + 2 * self.num_kv_heads)
                     + 2 * hd + self.num_heads * hd * d)
        fs = self.shared_expert_intermediate_size
        return (mixer + 2 * d + d * self.num_experts + self._held_params()
                + (3 * d * fs + d if fs else 0))

    def _layer_idle_params(self, kind) -> float:
        return self._idle_held_params()

    def _layer_mixer_flops(self, kind, seq_len, causal) -> float:
        """A Gated DeltaNet value head reads, corrects and writes its
        [dk, dv] state once a token (3 products of 2 dk dv FLOPs, x3 for
        training); a visible pair of the attention layer multiplies a key
        and a value of head_dim a head."""
        if kind == "linear_attention":
            return (18 * self.linear_num_value_heads
                    * self.linear_key_head_dim * self.linear_value_head_dim)
        return 12 * self.num_heads * self.head_dim * mean_context(
            seq_len, causal)


_PUBLISHED = dict(
    hidden_size=2048, intermediate_size=5120, num_heads=16, num_kv_heads=2,
    attn_head_dim=256, num_layers=48, vocab_size=151936, max_seq_len=262144,
    rope_theta=10000000, rotary_pct=0.25, full_attention_interval=4,
    linear_num_key_heads=16, linear_num_value_heads=32,
    linear_key_head_dim=128, linear_value_head_dim=128,
    linear_conv_kernel_dim=4, num_experts=512, moe_top_k=10,
    moe_intermediate_size=512, shared_expert_intermediate_size=512)


def qwen3_next_config(size: str = "80b-a3b", **overrides) -> Qwen3NextConfig:
    presets = {
        # one period; 2 key heads serving 4 value heads of 16; a head of 32
        # on a hidden size of 64 with 8 of its 32 dimensions rotated; the
        # published router (the agreement check's mask depends on the share
        # of experts near the boundary)
        "tiny": dict(hidden_size=64, intermediate_size=128, num_heads=4,
                     num_kv_heads=2, attn_head_dim=32, num_layers=4,
                     vocab_size=512, max_seq_len=128, rope_theta=10000,
                     rotary_pct=0.25, full_attention_interval=4,
                     linear_num_key_heads=2, linear_num_value_heads=4,
                     linear_key_head_dim=16, linear_value_head_dim=16,
                     linear_conv_kernel_dim=4, num_experts=512,
                     moe_top_k=10, moe_intermediate_size=32,
                     shared_expert_intermediate_size=32),
        "80b-a3b": _PUBLISHED,
    }
    base = dict(norm_type="rmsnorm", activation="swiglu",
                # rotated here, a part of the head: DecoderLM builds its one
                # table for "rope" alone and adds no positions for a name
                # it does not know
                position_embedding="rope_partial", use_bias=False,
                tie_embeddings=False, norm_eps=1e-6,
                moe_router_activation="softmax", moe_norm_topk=True,
                router_aux_loss_coef=0.0)
    base.update(presets[size])
    base.update(overrides)
    return Qwen3NextConfig(**base)


@register_model("qwen3_next")
class Qwen3Next(RoutedStackOfKinds):
    def __init__(self, config: Qwen3NextConfig | None = None,
                 size: str | None = None, **overrides):
        if config is not None and (size is not None or overrides):
            raise ValueError(
                "pass either an explicit config or size/overrides, not both")
        c = config or qwen3_next_config(size or "80b-a3b", **overrides)
        if (c.moe_router_activation != "softmax" or c.tie_embeddings
                or c.use_bias or c.num_experts <= 0
                or c.decoder_sparse_step != 1 or c.mlp_only_layers):
            raise NotImplementedError(
                "Qwen3Next has a softmax router over its experts in every "
                "layer (decoder_sparse_step 1, no mlp_only_layers), no "
                "bias and an untied head")
        if c.held_experts > c.num_experts:
            raise ValueError(
                f"{c.held_experts} experts held of the router's "
                f"{c.num_experts}")
        hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
        if hk <= 0 or hv % hk:
            raise ValueError(f"{hv} value heads on {hk} key heads")
        if c.rotary_dim <= 0 or c.rotary_dim % 2:
            raise ValueError(
                f"rotary_pct {c.rotary_pct} of a head of {c.head_dim}")
        super().__init__(c)
        self._rope = L.rotary_embedding(c.max_seq_len, c.rotary_dim,
                                        c.rope_theta)
        self._rotary = L.rotary_tables(*self._rope, c.head_dim)

    def after_step(self, params, stats):
        """No weight moves after the optimizer's update (no selection bias
        to balance); the routed layers' counts of the step become the
        ``moe_held_*`` metrics the engine feeds the registry from."""
        return params, self._held_metrics(stats)

    def _norm(self, x, scale, bias=None):
        """The family's RMSNorm: ``x rsqrt(mean x^2 + eps) (1 + w)``."""
        return L.rms_norm(x, 1.0 + scale.astype(jnp.float32),
                          self.config.norm_eps)

    # ---------------- init ----------------
    def _init_layer(self, key, kind, lead_shape=()):
        c = self.config
        dt = c.param_dtype
        d, f, fs = (c.hidden_size, c.moe_intermediate_size,
                    c.shared_expert_intermediate_size)
        e = c.held_experts
        std = 0.02
        resid_std = std / (2 * c.num_layers) ** 0.5
        ks = iter(jax.random.split(key, 16))

        def w(shape, scale=std):
            return _dense_init(next(ks), (*lead_shape, *shape), scale, dt)

        def full(shape, value):
            return jnp.full((*lead_shape, *shape), value, dt)

        p = {"ln1_scale": full((d,), 0.0), "ln2_scale": full((d,), 0.0)}
        if kind == "linear_attention":
            hv, dv = c.linear_num_value_heads, c.linear_value_head_dim
            key_w = c.linear_num_key_heads * c.linear_key_head_dim
            val_w = hv * dv
            p["gdn"] = {
                "w_qkvz": w((d, 2 * key_w + 2 * val_w)),
                "w_ba": w((d, 2 * hv)),
                "conv": jax.random.uniform(
                    next(ks), (*lead_shape, c.linear_conv_kernel_dim,
                               2 * key_w + val_w),
                    minval=-0.5, maxval=0.5).astype(dt),
                # the published modeling code's: A = U(0, 16), dt_bias = 1
                "A_log": jnp.log(jax.random.uniform(
                    next(ks), (*lead_shape, hv), minval=1e-4,
                    maxval=16.0)).astype(dt),
                "dt_bias": full((hv,), 1.0),
                "o_norm": full((dv,), 1.0),
                "wo": w((val_w, d), resid_std),
            }
        else:
            nh, nkv, hd = c.num_heads, c.num_kv_heads, c.head_dim
            p["attn"] = {
                "wq": w((d, nh * 2 * hd)),
                "wk": w((d, nkv * hd)), "wv": w((d, nkv * hd)),
                "q_norm": full((hd,), c.qk_norm_init),
                "k_norm": full((hd,), c.qk_norm_init),
                "wo": w((nh * hd, d), resid_std),
            }
        p["moe"] = {
            # logits of unit variance at any width, as the other routed
            # families draw them
            "router": w((d, c.num_experts), d ** -0.5),
            "experts": {"w_gate": w((e, d, f)), "w_up": w((e, d, f)),
                        "w_down": w((e, f, d), resid_std)},
        }
        if fs:
            p["moe"]["shared"] = {"w_gate": w((d, fs)), "w_up": w((d, fs)),
                                  "w_down": w((fs, d), resid_std)}
            p["moe"]["shared_gate"] = w((d, 1))
        return p

    def init(self, rng: jax.Array):
        """Seeded weights as ``models/mellum.py`` ``init`` draws them and
        for its reason (a router has to see its own token): embedding rows
        normal(0, 1), the rest normal(0, 0.02) with the residual outputs
        at 0.02 / sqrt(2 layers), the router ``hidden_size ** -0.5``. The
        scale of the query and key projections is normed away here (the
        l2 norm of a Gated DeltaNet head, QK-norm), so what makes the
        attention layer select is ``qk_norm_init``: ``w_q`` and ``w_k``
        start there (0 as published; the benchmark's configuration sets 2:
        scores of deviation 9 where 0 gives 1, ``PERF.md`` section 6)."""
        c = self.config
        dt = c.param_dtype
        d, v = c.hidden_size, c.vocab_size
        keys = jax.random.split(rng, 3)
        return {
            "embed": {"tokens": _dense_init(keys[1], (v, d), 1.0, dt)},
            "layers": self._init_layers(keys[0]),
            "final_norm": {"scale": jnp.zeros((d,), dt)},
            "lm_head": _dense_init(keys[2], (d, v), 0.02, dt),
        }

    # ---------------- the mixers ----------------
    def _gdn(self, p, h, kda_fn, conv_fn, norm_fn):
        """One Gated DeltaNet mixer on the normed ``h``. The projections
        carry ds.gdn alone (what kind "matmul" finds); q, k and v are each
        ONE pass of ``conv_fn`` (scope ds.conv) over their columns of the
        projection; beta and the gate are ds.mix_pre, the gated norm ONE
        pass of ``norm_fn`` (``ops.layers.gated_norm``, which opens
        ds.mix_post) over the scan's ``o`` as ``kda_fn`` hands it over and
        ``z`` as the projection wrote it."""
        c = self.config
        b, s, _ = h.shape
        hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
        f32 = jnp.float32
        kw, vw = hk * dk, hv * dv
        w, conv = p["w_qkvz"], p["conv"]
        cols = lambda x, lo, n: x[..., lo:lo + n]  # noqa: E731
        q = conv_fn(h @ cols(w, 0, kw), cols(conv, 0, kw), norm_width=dk,
                    norm_scale=dk ** -0.5).reshape(b, s, hk, dk)
        k = conv_fn(h @ cols(w, kw, kw), cols(conv, kw, kw),
                    norm_width=dk).reshape(b, s, hk, dk)
        v = conv_fn(h @ cols(w, 2 * kw, vw),
                    cols(conv, 2 * kw, vw)).reshape(b, s, hv, dv)
        z = h @ cols(w, 2 * kw + vw, vw)
        ba = h @ p["w_ba"]
        with jax.named_scope("ds.mix_pre"):
            beta = jax.nn.sigmoid(ba[..., :hv].astype(f32))
            g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
                ba[..., hv:].astype(f32) + p["dt_bias"].astype(f32))
        # q and k at their hk key heads: the scan reads a key head for the
        # hv / hk value heads it serves. One head group: the step fits
        # whole (12.77 GiB, AOT for a v5e)
        o = kda_fn(q, k, v, g, beta)
        # the published arithmetic: float32 from o to the last cast, SiLU
        o = norm_fn(o, z, p["o_norm"], act="silu", eps=c.norm_eps)
        return o @ p["wo"]

    def _attention(self, p, h, attn):
        c = self.config
        b, s, _ = h.shape
        nh, nkv, hd = c.num_heads, c.num_kv_heads, c.head_dim
        qg = (h @ p["wq"]).reshape(b, s, nh, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:].reshape(b, s, nh * hd)
        k = (h @ p["wk"]).reshape(b, s, nkv, hd)
        v = (h @ p["wv"]).reshape(b, s, nkv, hd)
        with jax.named_scope("ds.qk_norm"):
            q = self._norm(q, p["q_norm"])
            k = self._norm(k, p["k_norm"])
        # the whole head with its table of ``rotary_dim`` channels
        a = L.rotary_attention(attn, q, k, v, self._rotary, causal=True
                               ).reshape(b, s, nh * hd)
        a = (a.astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(h.dtype)
        return a @ p["wo"]

    # ---------------- one layer, the stack ----------------
    def _one_layer(self, p, x, mixers):
        from ..moe import sharded_moe
        c = self.config
        attn_fn, kda_fn, conv_fn, norm_fn = mixers
        if "gdn" in p:
            with jax.named_scope("ds.gdn"):
                h = self._norm(x, p["ln1_scale"])
                x = x + self._gdn(p["gdn"], h, kda_fn, conv_fn, norm_fn)
        else:
            with jax.named_scope("ds.attn_gated"):
                h = self._norm(x, p["ln1_scale"])
                x = x + self._attention(p["attn"], h, attn_fn)
        h = self._norm(x, p["ln2_scale"])
        moe = p["moe"]
        # a share without its peers leaves the routing alone in the
        # backward (``moe_ffn_held``): the whole layer trains its router
        y, counts = sharded_moe.moe_ffn_held(
            h, moe["router"], None, moe["experts"], moe.get("shared"),
            k=c.moe_top_k, renormalise=c.moe_norm_topk, router="softmax",
            router_grad=c.held_experts == c.num_experts,
            shared_gate=moe.get("shared_gate"))
        # the blocks the dispatch swept, from the load and its own rule
        block = sharded_moe.held_block(h.shape[0] * h.shape[1], c.moe_top_k,
                                       c.num_experts)
        blocks = jnp.sum(-(-counts["load"][:c.held_experts] // block))
        return x + y, {**counts, "blocks": blocks,
                       "block": jnp.int32(block)}

    def _mixers(self, attn_fn, act_sharding):
        """(attention, the delta rule's scan, short convolution): on a mesh
        of more than one device the scan's and the convolution's kernels
        run per shard of ``act_sharding``. On one device the scan hands
        ``o`` over as its kernel wrote it, the heads' stack
        (``chunk_kda(by_head=True)``): the gated norm reads either form."""
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
        """Tensor-parallel rules by head / expert dimension; the leading
        axis of a ``period`` stack is the scan's and stays whole. The
        Gated DeltaNet input projection's columns are four runs of heads,
        so it stays whole."""
        def both(pattern, *spec):
            return [(rf"layers/period/.*{pattern}", P(None, *spec)),
                    (rf"layers/(lead|tail)/.*{pattern}", P(*spec))]

        rules = [(r"embed/tokens", P("tp", None))]
        for pattern, spec in [
                (r"attn/(wq|wk|wv)$", (None, "tp")),
                (r"(attn|gdn)/wo$", ("tp", None)),
                (r"experts/(w_up|w_gate)$", ("ep", None, "tp")),
                (r"experts/w_down$", ("ep", "tp", None)),
                (r"shared/(w_up|w_gate)$", (None, "tp")),
                (r"shared/w_down$", ("tp", None))]:
            rules += both(pattern, *spec)
        return rules + [(r"lm_head$", P(None, "tp"))]

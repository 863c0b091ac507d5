"""A stack of more than one kind of layer: what the families that have one
share (``models/kimi_linear.py``: KDA and latent attention over dense and
routed channels; ``models/granite_hybrid.py``: Mamba-2 and grouped-query
attention; ``models/mellum.py``: window and full attention, each routed).

**The config** (``StackConfig``, ``RoutedStackConfig``): a family's own
dataclass holds its fields, the kinds of its layers and three small tables
a kind; the parameter and FLOP counts are ONE walk over the kinds, here,
with the held share of a routed layer in the one place the routed
families share.

**The model** (``StackOfKinds``, ``RoutedStackOfKinds``): parameters are
stacked by kind: ``layers`` holds ``lead`` (the leading layers, unrolled),
``period`` (the layers of ONE period of the pattern, each stacked over the
whole periods, run under one ``lax.scan``) and ``tail`` (what does not
fill a period, unrolled); a layer's kind is read from the keys it holds.
Every layer is rematted whole. The kinds (one hashable a layer) and the
leading layers are the config's; a family gives three methods:
``_init_layer(key, kind, lead_shape)``, ``_mixers(attn_fn, act_sharding)``
(what its layers call to mix tokens, with the kernels imported there and
not at import) and ``_one_layer(p, x, mixers)`` -> ``(x, counts)``. A
family whose layers call a kernel beside their mixers' (the delta-rule
families' gated norm) hands it over behind them: ``_layer_fns``.

**Window and full attention layers mixed** (``WindowAndFullAttention``):
what ``models/mellum.py`` and ``models/laguna.py`` share: the two kinds'
names, a rotary table a kind, the mixers by window and the partition
rules.

**Latent attention** (``LatentAttention``): the MLA layer of
``models/kimi_linear.py`` (a direct query, unrotated),
``models/xing4.py`` (a low-rank normed query, rotated by halves under
YaRN) and ``models/deepseek_v3.py`` (a direct query, rotated by
interleaved pairs): its weights, their count and the mixer.

**Mamba-2** (``Mamba2``): the state-space mixer of
``models/granite_hybrid.py`` (one group, the gated norm over all
channels) and ``models/nemotron_h.py`` (heads in groups, the gated norm a
group): its weights, their count and the mixer.
"""

from __future__ import annotations

import dataclasses
import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops import layers as L
from .base import ModelConfig
from .transformer import DecoderLM, _remat_policy, _unpack_batch


@dataclasses.dataclass
class StackConfig(ModelConfig):
    """The config of a stack of kinds. A family's subclass holds its own
    fields and says, a kind of layer: ``layer_kinds()`` (one hashable a
    layer, in the order they run), ``_layer_params(kind)`` (a layer's
    parameters, norms and all), ``_layer_idle_params(kind)`` (the part of
    them a token does not run) and ``_layer_mixer_flops(kind, seq_len,
    causal)`` (what its token mixer adds to the 6 N, a token). The counts
    are one walk over the kinds: embedding, head and final norm, plus for
    each layer its kind's."""

    def layer_kinds(self) -> list:
        raise NotImplementedError

    def lead_layers(self) -> int:
        """Leading layers kept out of the repeating pattern."""
        return 0

    def _layer_params(self, kind) -> int:
        raise NotImplementedError

    def _layer_idle_params(self, kind) -> float:
        return 0

    def _layer_mixer_flops(self, kind, seq_len: int, causal: bool) -> float:
        raise NotImplementedError

    def num_params(self) -> int:
        """As the family's ``init`` builds the trees, exactly: an RMSNorm
        stack without biases or learned positions."""
        table = self.vocab_size * self.hidden_size
        return (table * (1 if self.tie_embeddings else 2) + self.hidden_size
                + sum(self._layer_params(k) for k in self.layer_kinds()))

    def num_active_params(self) -> int:
        return int(self.num_params() - sum(
            self._layer_idle_params(k) for k in self.layer_kinds()))

    def _matmul_params(self) -> float:
        """A tied table is the head's matmul; an untied one's gather is
        not a matmul."""
        return self.num_active_params() - (
            0 if self.tie_embeddings else self.vocab_size * self.hidden_size)

    def _mixer_flops(self, seq_len: int, causal: bool) -> float:
        return sum(self._layer_mixer_flops(k, seq_len, causal)
                   for k in self.layer_kinds())


@dataclasses.dataclass
class RoutedStackConfig(StackConfig):
    """... with routed layers (``moe.sharded_moe.moe_ffn_held``): a router
    over ``num_experts`` in front of the experts HELD here."""
    moe_router_activation: str = "softmax"  # softmax | sigmoid (bias-
    #                                 corrected selection)
    routed_scaling_factor: float = 1.0
    moe_intermediate_size: int = 0  # expert width where it differs from
    #                                 the dense FFN's (0 = the same)
    moe_held_experts: int = 0       # experts HELD here of num_experts, the
    #                                 router's width (0 = all): one chip's
    #                                 share under expert parallelism (the
    #                                 first of them)

    @property
    def held_experts(self) -> int:
        """Routed experts held here: all of them unless told a share."""
        return self.moe_held_experts or self.num_experts

    def _expert_params(self) -> int:
        """ONE routed expert, a SwiGLU."""
        return 3 * self.hidden_size * (self.moe_intermediate_size
                                       or self.intermediate_size)

    def _held_params(self) -> int:
        """A routed layer's experts held here."""
        return self.held_experts * self._expert_params()

    def _idle_held_params(self) -> float:
        """... and the part of them a token does not run: it runs the
        ``moe_top_k`` it is routed to times the share of the experts held
        here (what this chip computes for it, under a balanced router)."""
        run = self.moe_top_k * self.held_experts / self.num_experts
        return (self.held_experts - run) * self._expert_params()


def stack_plan(kinds: list, lead: int) -> tuple[int, int, int]:
    """(layers a period, whole periods, layers left over) of the kinds
    after the ``lead`` leading layers: the period whose whole repeats (two
    at least) cover most layers, the shortest such; what follows them is
    left over, and so is everything where nothing repeats."""
    rest = kinds[lead:]
    best = (0, 0)
    for p in range(1, len(rest) // 2 + 1):
        n = 1
        while rest[n * p:(n + 1) * p] == rest[:p]:
            n += 1
        if n >= 2 and n * p > best[0] * best[1]:
            best = (p, n)
    p, n = best
    return p, n, len(rest) - p * n


class StackOfKinds(DecoderLM):
    def __init__(self, config: StackConfig):
        super().__init__(config)
        self.kinds = kinds = config.layer_kinds()
        self.lead = min(config.lead_layers(), len(kinds))
        self.period, self.repeats, self.left = stack_plan(kinds, self.lead)

    def _init_layers(self, key):
        """``layers`` by group, a key a layer in the order they run."""
        lk = iter(jax.random.split(key, len(self.kinds)))
        at = self.lead + self.period * self.repeats
        return {
            "lead": {str(i): self._init_layer(next(lk), self.kinds[i])
                     for i in range(self.lead)},
            "period": {str(j): self._init_layer(
                next(lk), self.kinds[self.lead + j], (self.repeats,))
                for j in range(self.period if self.repeats else 0)},
            "tail": {str(i): self._init_layer(next(lk), self.kinds[at + i])
                     for i in range(self.left)},
        }

    def _attn(self, attn_fn):
        """The attention a layer of the stack calls: full causal."""
        if attn_fn is not None:
            return attn_fn
        if self.config.attn_impl == "flash":
            from ..ops.pallas.flash_attention import flash_attention
            return flash_attention
        return L.dot_product_attention

    def _layer_fns(self, attn_fn, act_sharding):
        """What ``_one_layer`` is handed as ``mixers``: the family's
        ``_mixers``, and behind them whatever else its layers must run per
        shard on a mesh of more than one device."""
        return self._mixers(attn_fn, act_sharding)

    def _layer(self, p, x, mixers, scanned: bool):
        """One layer of the kind its keys name, as (x, counts); rematted
        whole, but for the residuals a kernel declares kept
        (``_remat_policy``). An unrolled layer's checkpoint has to prevent
        CSE, or XLA merges the recomputation with the forward pass and
        keeps every intermediate alive; under the scan the loop boundary
        does that."""
        c = self.config
        layer = lambda p, x: self._one_layer(p, x, mixers)  # noqa: E731
        if not c.remat:
            return layer(p, x)
        return jax.checkpoint(layer, prevent_cse=not scanned,
                              policy=_remat_policy(c.remat_policy))(p, x)

    def _layer_stack(self, layers, x, pin, *, attn_fn, positions,
                     act_sharding=None):
        """(x, stats): ``stats[group][slot]`` are the counts of each layer
        that counts anything (a routed one), a ``period`` slot's stacked
        over the repeats as its parameters are. On a mesh of more than one
        device (``act_sharding``) a family's kernels run per shard."""
        mixers = self._layer_fns(self._attn(attn_fn), act_sharding)
        stats = {"lead": {}, "period": {}, "tail": {}}

        def unrolled(group, n, x):
            for i in range(n):
                x, stats[group][str(i)] = self._layer(
                    layers[group][str(i)], x, mixers, False)
                x = pin(x)
            return x

        x = unrolled("lead", self.lead, x)
        if self.repeats:
            def body(x, slots):
                counts = {}
                for j in range(self.period):
                    x, counts[str(j)] = self._layer(
                        slots[str(j)], x, mixers, True)
                    x = pin(x)
                return x, counts

            x, stats["period"] = jax.lax.scan(body, x, layers["period"])
        x = unrolled("tail", self.left, x)
        return x, {g: {k: v for k, v in slots.items() if v}
                   for g, slots in stats.items()}

    def _loss_and_stats(self, params, batch, *, attn_fn=None,
                        act_sharding=None):
        """(mean cross-entropy, no auxiliary term; the layers' counts)."""
        tokens, targets = _unpack_batch(batch)
        x, stats = self._final_hidden(params, tokens, attn_fn=attn_fn,
                                      act_sharding=act_sharding)
        with jax.named_scope("ds.loss_head"):
            if self.config.loss_chunk > 0:
                ce = self._chunked_ce(params, x, targets)
            else:
                ce = L.cross_entropy_loss(
                    self._project_vocab(params, x), targets)
        return ce, stats

    def loss(self, params, batch, *, attn_fn=None, act_sharding=None):
        return self._loss_and_stats(params, batch, attn_fn=attn_fn,
                                    act_sharding=act_sharding)[0]

    # the serving and pipeline paths assume one kind of layer and a KV cache
    def _one_kind_only(self, *a, **kw):
        raise NotImplementedError(
            f"{type(self).__name__} runs through apply/loss only: latent "
            f"caches and recurrent state are not in inference/, and a "
            f"stack of kinds has no single block()")

    block = block_decode = decode = init_cache = _one_kind_only


class RoutedStackOfKinds(StackOfKinds):
    """A stack of kinds with routed layers that hold a share of their
    experts (``moe.sharded_moe.moe_ffn_held``): the step hands the layers'
    counts back. ``loss(with_stats=True)`` returns them beside the loss,
    and the engine calls the family's ``after_step(params, stats)`` with
    the step's updated weights and takes ``(params, metrics)`` from it;
    ``_held_metrics`` makes the metrics every such family returns,
    ``_held_blocks`` adds the blocks the dispatch swept to a layer's
    counts, and ``_balanced`` is the whole ``after_step`` of one with a
    selection bias."""

    def loss(self, params, batch, *, attn_fn=None, act_sharding=None,
             with_stats: bool = False):
        """Mean cross-entropy (no auxiliary term); ``with_stats`` also
        returns the routed layers' counts, for ``after_step``."""
        ce, stats = self._loss_and_stats(params, batch, attn_fn=attn_fn,
                                         act_sharding=act_sharding)
        return (ce, stats) if with_stats else ce

    @staticmethod
    def record_step_metrics(reg, metrics: dict) -> None:
        """How the engine records what ``after_step`` returned, one step
        behind: the routed families' recorder."""
        from ..moe.dispatch import record_held_expert_counts
        record_held_expert_counts(reg, metrics)

    def _held_blocks(self, counts, tokens: int) -> dict:
        """A routed layer's counts with the blocks the dispatch swept,
        from the load and the dispatch's own rule."""
        from ..moe import sharded_moe
        c = self.config
        block = sharded_moe.held_block(tokens, c.moe_top_k, c.num_experts)
        blocks = jnp.sum(-(-counts["load"][:c.held_experts] // block))
        return {**counts, "blocks": blocks, "block": jnp.int32(block)}

    def _balanced(self, params, stats):
        """The ``after_step`` of a family whose routed layers select with a
        bias (``moe["router_bias"]``): every routed layer's bias moves by
        ``BIAS_UPDATE_RATE`` against its experts' load in the step
        (``balance_bias``). Returns (params, ``_held_metrics``)."""
        from ..moe.sharded_moe import balance_bias
        layers = {g: dict(slots) for g, slots in params["layers"].items()}

        def move_bias(group, slot, counts):
            p = layers[group][slot]
            moe = dict(p["moe"])
            moe["router_bias"] = balance_bias(moe["router_bias"],
                                              counts["load"])
            layers[group][slot] = {**p, "moe": moe}

        metrics = self._held_metrics(stats, each=move_bias)
        return {**params, "layers": layers}, metrics

    def _held_metrics(self, stats, each=None) -> dict:
        """What an ``after_step`` returns of the routed layers' counts of
        one step (``stats``: ``loss(with_stats=True)``'s, summed over the
        micro-batches), as device scalars the engine feeds
        ``record_step_metrics`` with: the rows routed
        to the experts held here (the first ``held_experts``) and the rows
        they computed (equal, or rows were dropped), over
        ``moe_held_calls`` routed layers of ``moe_held_experts`` each.
        Where a family's layers count the blocks the dispatch swept
        (``counts["blocks"]`` of ``counts["block"]`` rows each, from the
        load and ``held_block``) also those, the rows of one, and the
        step's largest and smallest load of ANY of the router's experts
        in one layer. And what the forward sweeps counted of themselves
        (``moe.sharded_moe._held_sweep``), summed over the routed layers:
        ``moe_sweep_trips`` of the chunk loop (``moe_held_calls`` where
        no call took a second), ``moe_sweep_tiles`` with a live row,
        ``moe_sweep_swept`` tiles the trips held, the ``moe_sweep_tile``'s
        rows and the most trips any ONE layer call took
        (``moe_sweep_trips_max``). ``each(group, slot, counts)`` runs first
        for every routed layer (a bias-corrected router's update)."""
        held = self.config.held_experts
        rows = done = blocks = calls = trips = tiles = swept = 0
        block, tile, tops, leasts, most = None, None, [], [], []
        for group, slots in stats.items():
            for slot, counts in slots.items():
                if each is not None:
                    each(group, slot, counts)
                rows += jnp.sum(counts["load"][..., :held])
                done += jnp.sum(counts["done"])
                calls += counts["done"].size
                if "trips" in counts:
                    trips += jnp.sum(counts["trips"])
                    tiles += jnp.sum(counts["tiles"])
                    swept += jnp.sum(counts["swept"])
                    tile = jnp.max(counts["tile"])
                    most.append(jnp.max(counts["trips"]))
                if "blocks" in counts:
                    blocks += jnp.sum(counts["blocks"])
                    block = jnp.max(counts["block"])
                    tops.append(jnp.max(counts["load"]))
                    leasts.append(jnp.min(counts["load"]))
        metrics = {"moe_held_rows": rows, "moe_held_done": done,
                   "moe_held_calls": jnp.int32(calls),
                   "moe_held_experts": jnp.int32(held)}
        if most:
            metrics.update(moe_sweep_trips=trips, moe_sweep_tiles=tiles,
                           moe_sweep_swept=swept, moe_sweep_tile=tile,
                           moe_sweep_trips_max=jnp.max(jnp.stack(most)))
        if tops:
            metrics.update(moe_held_blocks=blocks, moe_held_block=block,
                           moe_load_max=jnp.max(jnp.stack(tops)),
                           moe_load_min=jnp.min(jnp.stack(leasts)))
        return metrics


# a published ``layer_types`` entry -> the key a layer's attention weights
# lie under, the name of its mixer and the tail of its scope ds.attn_<kind>
ATTENTION_KINDS = {"sliding_attention": "swa", "full_attention": "full"}


class WindowAndFullAttention:
    """What the routed stacks whose ``layer_types`` mix ``sliding_attention``
    and ``full_attention`` share (a mixin in front of
    ``RoutedStackOfKinds``): the config's checks, a rotary table a kind
    from ``rope_parameters``, the mixers (a kind's window) and the
    partition rules. The layer itself, its head counts and whatever it
    adds (a gate, a shared expert) are the family's."""

    # partition rules a family adds to the shared ones: (pattern, spec)
    _more_rules: tuple = ()

    def _check_attention_kinds(self):
        """The config names ``num_layers`` kinds of the two, a rotary
        section for each it names, and a window where a layer has one."""
        c, name = self.config, type(self).__name__
        if len(c.layer_types) != c.num_layers or set(c.layer_types) - set(
                ATTENTION_KINDS):
            raise ValueError(
                f"{name} needs {c.num_layers} layer_types of "
                f"{sorted(ATTENTION_KINDS)}, not {c.layer_types}")
        if set(c.layer_types) - set(c.rope_parameters):
            raise ValueError(
                f"rope_parameters has no section for "
                f"{sorted(set(c.layer_types) - set(c.rope_parameters))}")
        if "sliding_attention" in c.layer_types and not c.sliding_window:
            raise ValueError("sliding_attention layers need sliding_window")
        if c.held_experts > c.num_experts:
            raise ValueError(
                f"{c.held_experts} experts held of the router's "
                f"{c.num_experts}")

    def _rope_tables(self) -> dict:
        """{kind: ``RotaryTables``} of the kinds the stack has, each from
        its section of ``rope_parameters`` (``ops/layers.py``
        ``rotary_embedding``: a section's ``partial_rotary_factor`` makes
        the table narrower than the head)."""
        c = self.config
        return {
            ATTENTION_KINDS[t]: L.rotary_tables(*L.rotary_embedding(
                c.max_seq_len, c.head_dim, c.rope_theta,
                scaling=c.rope_parameters[t]), c.head_dim)
            for t in sorted(set(c.layer_types))}

    def _mixers(self, attn_fn, act_sharding):
        """{kind: attention of that kind's window}. ``attn_fn`` is the
        stack's (``_attn``): the flash kernels, plain attention, or what
        the engine bound for a mesh, which has to take a window a call
        (``sharded_flash_attention`` does; the sequence-parallel wrappers
        apply none, and a window layer cannot run full-causal)."""
        c = self.config
        if attn_fn is L.dot_product_attention:
            def plain(q, k, v, window):
                bias = (None if window is None
                        else L.window_bias(q.shape[1], window))
                return attn_fn(q, k, v, causal=True, bias=bias)
            of = lambda w: functools.partial(plain, window=w)  # noqa: E731
        else:
            from ..ops.pallas.flash_attention import flash_attention
            if attn_fn is not flash_attention and not getattr(
                    attn_fn, "applies_window", False):
                raise NotImplementedError(
                    f"{type(self).__name__}'s window layers need an "
                    f"attention that applies a window a call: the "
                    f"sequence-parallel wrappers do not")
            of = lambda w: functools.partial(  # noqa: E731
                attn_fn, causal=True, window=w)
        return {"swa": of(c.sliding_window), "full": of(None)}

    def partition_rules(self):
        """Tensor-parallel rules by head / expert dimension; the leading
        axis of a ``period`` stack is the scan's and stays whole."""
        def both(pattern, *spec):
            return [(rf"layers/period/.*{pattern}", P(None, *spec)),
                    (rf"layers/(lead|tail)/.*{pattern}", P(*spec))]

        rules = [(r"embed/tokens", P("tp", None))]
        for pattern, spec in [
                (r"(swa|full)/(wq|wk|wv)$", (None, "tp")),
                (r"(swa|full)/wo$", ("tp", None)),
                (r"experts/(w_up|w_gate)$", ("ep", None, "tp")),
                (r"experts/w_down$", ("ep", "tp", None)),
                *self._more_rules]:
            rules += both(pattern, *spec)
        return rules + [(r"lm_head$", P(None, "tp"))]


def mla_params(c) -> int:
    """A latent-attention mixer's parameters as ``LatentAttention.
    _init_mla`` builds them, from the published keys of ``c``: a direct
    query (``q_lora_rank`` 0 or absent) or a low-rank one with its norm."""
    d, nh = c.hidden_size, c.num_heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    rank = getattr(c, "q_lora_rank", 0)
    query = d * rank + rank + rank * nh * qk if rank else d * nh * qk
    return (query + d * (c.kv_lora_rank + c.qk_rope_head_dim)
            + c.kv_lora_rank
            + c.kv_lora_rank * nh * (c.qk_nope_head_dim + c.v_head_dim)
            + nh * c.v_head_dim * d)


class LatentAttention:
    """Latent attention (MLA) as the routed stacks that have it share it
    (a mixin in front of ``RoutedStackOfKinds``)::

        q = h Wq                       or   rmsnorm(h Wqa) Wqb    (low rank)
            as H x (nope + rope)
        [c, k_pe] = h Wkva  (kv_lora + rope);  [k_nope, v] = rmsnorm(c) Wkvb
        q_pe, k_pe rotated (``_rope``: by halves, or the checkpoint's
        interleaved pairs laid out as halves first, ``_rope_pairs``; None:
        not rotated);   k_h = [k_nope_h, k_pe]: ONE rotated key for all H
        y = softmax_causal(q k^T (nope + rope)^-1/2 m^2) v Wo

    ``m`` is the config's ``softmax_mscale`` (YaRN's; 1 without): the
    attention kernels' scale is the key width's ``d^-1/2``, so ``m^2``
    rides on the query, on its latent norm's weight where it has one
    (float32 inside the norm, one rounding: ``q`` is linear in it). In
    training the latent is expanded and the layer runs as H-head attention
    with a key of ``nope + rope`` and a value of ``v_head_dim`` through the
    flash kernels: ``ops.layers.latent_attention`` builds their operands,
    or hands the three projections as they lie to an attention that takes
    them (``ops/pallas/rope.py`` ``latent_to_heads``: one pass). The family
    sets ``_rope`` (``ops.layers.latent_rotary_tables`` of
    ``rotary_embedding`` over ``qk_rope_head_dim``) and ``_rope_pairs`` in
    its ``__init__``."""

    _rope = None            # RotaryTables over qk_rope_head_dim, or None
    _rope_pairs = False     # the rotated channels come as interleaved pairs

    def _init_mla(self, w, ones, resid_std: float) -> dict:
        """The mixer's weights, drawn in this order: ``w(shape, scale=)``
        and ``ones(shape)`` are the family's ``_init_layer``'s, and
        ``resid_std`` its residual outputs' deviation."""
        c = self.config
        d, nh = c.hidden_size, c.num_heads
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        rank = getattr(c, "q_lora_rank", 0)
        query = ({"wq_a": w((d, rank)), "q_norm": ones((rank,)),
                  "wq_b": w((rank, nh * qk))} if rank else
                 {"wq": w((d, nh * qk))})
        return {
            **query,
            "w_kva": w((d, c.kv_lora_rank + c.qk_rope_head_dim)),
            "kv_norm": ones((c.kv_lora_rank,)),
            "w_kvb": w((c.kv_lora_rank,
                        nh * (c.qk_nope_head_dim + c.v_head_dim))),
            "wo": w((nh * c.v_head_dim, d), resid_std),
        }

    def _mla_query(self, p, h):
        """[B, S, H (nope + rope)], with the softmax scale's ``m^2``."""
        c = self.config
        m2 = getattr(c, "softmax_mscale", 1.0) ** 2
        if "wq" in p:
            q = h @ p["wq"]
            return q if m2 == 1.0 else q * jnp.asarray(m2, q.dtype)
        cq = L.rms_norm(h @ p["wq_a"], p["q_norm"].astype(jnp.float32) * m2,
                        c.norm_eps)
        return cq @ p["wq_b"]

    def _mla(self, p, h, attn_fn):
        c = self.config
        b, s, _ = h.shape
        nh, nope, rope, dv, r = (c.num_heads, c.qk_nope_head_dim,
                                 c.qk_rope_head_dim, c.v_head_dim,
                                 c.kv_lora_rank)
        rotated = self._rope is not None
        q = self._mla_query(p, h).reshape(b, s, nh, nope + rope)
        kva = h @ p["w_kva"]
        latent = L.rms_norm(kva[..., :r], p["kv_norm"], c.norm_eps)
        # (the shared key's columns are cut where and how each family's
        # program has cut them: tests/test_step_pins.py)
        if not rotated:
            k_pe = kva[..., r:]
        kv = (latent @ p["w_kvb"]).reshape(b, s, nh, nope + dv)
        if rotated:
            k_pe = kva[..., None, r:]
        a = L.latent_attention(attn_fn, q, kv, k_pe, self._rope,
                               pairs=self._rope_pairs, causal=True)
        return a.reshape(b, s, nh * dv) @ p["wo"]


class MambaShape(typing.NamedTuple):
    """A Mamba-2 mixer as a family's config publishes it (its
    ``mamba_shape()``): H heads of P, state N, G groups of heads sharing B and
    C, the convolution's taps and whether it has a bias, the scan's chunk,
    and over how many groups of channels the gated norm takes its mean."""
    heads: int
    head_dim: int
    state: int
    groups: int
    conv: int
    conv_bias: bool
    chunk: int
    norm_groups: int = 1

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """The channels the convolution runs over: x | B | C."""
        return self.inner + 2 * self.groups * self.state

    def params(self, hidden: int) -> int:
        """The mixer's parameters as ``Mamba2._init_mamba`` builds them:
        the input projection z | xBC | dt, the taps (and bias), A, D,
        dt_bias a head, the gated norm's weight, the output projection."""
        return (hidden * (self.inner + self.conv_width + self.heads)
                + (self.conv + self.conv_bias) * self.conv_width
                + 3 * self.heads + self.inner + self.inner * hidden)

    @property
    def state_flops(self) -> float:
        """Beside the 6 N: a head writes and reads its [P, N] state once a
        token (2 products of 2 P N FLOPs); x3 training."""
        return 12 * self.heads * self.head_dim * self.state


def grouped_query_attention(p, h, attn_fn, *, heads: int, kv_heads: int,
                            head_dim: int, q_scale: float | None = None):
    """A grouped-query attention mixer without positions on the normed
    ``h`` [B, S, C]: ``wq`` at ``heads``, ``wk`` and ``wv`` at ``kv_heads``
    heads of ``head_dim``, causal over the whole sequence, ``wo``.
    ``attn_fn`` applies ``head_dim ** -0.5``; ``q_scale`` rides on q where
    a family's softmax scale is another."""
    b, s, _ = h.shape
    q = (h @ p["wq"]).reshape(b, s, heads, head_dim)
    k = (h @ p["wk"]).reshape(b, s, kv_heads, head_dim)
    v = (h @ p["wv"]).reshape(b, s, kv_heads, head_dim)
    if q_scale is not None:
        q = q * q_scale
    return attn_fn(q, k, v, causal=True).reshape(
        b, s, heads * head_dim) @ p["wo"]


class Mamba2:
    """The Mamba-2 mixer as the stacks that have it share it (a mixin in
    front of ``StackOfKinds``; ``ops/ssd.py`` is the scan)::

        [z | xBC | dt] = h W_in          widths H P | H P + 2 G N | H
        [x | B | C] = silu(conv(xBC) + b_conv)       causal, depthwise:
                                          ops.layers.short_conv, one pass
        dt = softplus(dt + dt_bias);   A = -exp(A_log)           per head
        y = chunk_ssd(x, dt, A, B, C) + D x      head h reads group
                                                  h // (H / G)'s B and C
        out = (rmsnorm(y * silu(z)) * w) W_out   the mean of squares over
                                  each of ``norm_groups`` runs of channels

    The family's config says the shape from its own published keys
    (``mamba_shape()``); ``_mixers`` hands a layer the attention, the scan
    and the convolution."""

    def _mixers(self, attn_fn, act_sharding):
        """(attention, scan, short convolution): the scan's and the
        convolution's kernels run per shard of ``act_sharding`` where the
        mesh has more than one device."""
        from ..ops.ssd import chunk_ssd, sharded_chunk_ssd
        if act_sharding is None:
            return attn_fn, chunk_ssd, L.short_conv
        return (attn_fn, sharded_chunk_ssd(act_sharding),
                L.sharded_short_conv(act_sharding))

    def _init_mamba(self, w, ones, ks, lead_shape, resid_std: float) -> dict:
        """The mixer's weights, drawn in this order: ``w(shape, scale=)``
        and ``ones(shape)`` are the family's ``_init_layer``'s, ``ks`` its
        keys. Decay init (Mamba-2's): A = U(1, 16) a head; dt =
        exp(U(log 1e-3, log 0.1)), dt_bias its inverse softplus."""
        m = self.config.mamba_shape()
        d, dt = self.config.hidden_size, self.config.param_dtype
        h = m.heads
        step = jnp.exp(jax.random.uniform(
            next(ks), (*lead_shape, h), minval=np.log(1e-3),
            maxval=np.log(0.1)))
        p = {
            "w_in": w((d, m.inner + m.conv_width + h)),
            "conv_w": jax.random.uniform(
                next(ks), (*lead_shape, m.conv, m.conv_width),
                minval=-0.5, maxval=0.5).astype(dt),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            "A_log": jnp.log(jax.random.uniform(
                next(ks), (*lead_shape, h), minval=1.0,
                maxval=16.0)).astype(dt),
            "D": ones((h,)),
            "norm": ones((m.inner,)),
            "w_out": w((m.inner, d), resid_std),
        }
        if m.conv_bias:
            p["conv_b"] = jax.random.uniform(
                next(ks), (*lead_shape, m.conv_width), minval=-0.5,
                maxval=0.5).astype(dt)
        return p

    def _mamba(self, p, h, ssd_fn, conv_fn):
        m = self.config.mamba_shape()
        eps = self.config.norm_eps
        b, s, _ = h.shape
        nh, hd, g, n, inner = m.heads, m.head_dim, m.groups, m.state, m.inner
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
                   chunk=min(m.chunk, s))
        with jax.named_scope("ds.mix_post"):
            y = y.astype(f32) + x.astype(f32) * p["D"].astype(f32)[:, None]
            y = y.reshape(b, s, inner) * jax.nn.silu(z.astype(f32))
            if m.norm_groups == 1:
                y = L.rms_norm(y, p["norm"], eps).astype(h.dtype)
            else:
                # a group's mean of squares over its own channels
                y = L.rms_norm(y.reshape(b, s, m.norm_groups, -1),
                               p["norm"].reshape(m.norm_groups, -1), eps)
                y = y.reshape(b, s, inner).astype(h.dtype)
        return y @ p["w_out"]

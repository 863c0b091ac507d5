# shardlint: axes=dp,fsdp,zps,ep
"""Expert-parallel MoE dispatch (ISSUE 16; reference:
deepspeed/moe/sharded_moe.py _AllToAll:96 + utils/groups.py expert
groups).

:class:`EpShardedDispatcher` is the training engine's replacement for
the implicit XLA dispatch/combine einsum collectives: a ``shard_map``
over the engine mesh whose body computes the LOCAL partial dispatch
table, routes it through the MoE-shaped hierarchical exchange
(``runtime/comm/moe_alltoall.py`` — fast ``zps`` intra-hop first, slow
``dp``/``fsdp`` inter-hop, optional int8 stochastic-rounded wire), runs
the expert FFN on this shard's ``E/ep x C/token_world`` slots, gathers
and combines. Gating stays global (top_k_gating positions are computed
on the replicated-over-ep logits), so routing semantics are identical
to the einsum path — only the wire changes.

The stochastic wire keys its rounding noise on the training step; the
engine binds the traced step around the loss trace with
:func:`moe_step`, read back at trace time by :func:`current_step`
(contextvar — no model-signature change, no recompile per step since
the step is itself a traced scalar).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..runtime.comm.moe_alltoall import (moe_combine_exchange,
                                         moe_dispatch_exchange)
from ..utils.jax_compat import shard_map

_MOE_STEP: contextvars.ContextVar = contextvars.ContextVar(
    "moe_step", default=None)


@contextlib.contextmanager
def moe_step(step):
    """Bind the (traced) training step for the duration of a loss
    trace; the stochastic dispatch wire folds it into its rounding
    noise so no two steps share wire error (unbiased over time)."""
    token = _MOE_STEP.set(step)
    try:
        yield
    finally:
        _MOE_STEP.reset(token)


def current_step():
    """The bound step as uint32 (0 outside any moe_step scope — eval
    traces, serving)."""
    s = _MOE_STEP.get()
    if s is None:
        return jnp.zeros((), jnp.uint32)
    return jnp.asarray(s).astype(jnp.uint32)


def dispatcher_unsupported_reason(topology, num_experts: int):
    """None when the ep-sharded dispatcher can run on this topology,
    else a human-readable reason (the engine warns and falls back to
    the implicit einsum collectives)."""
    sizes = topology.sizes
    if sizes.get("tp", 1) > 1:
        return ("mesh.tp > 1: expert weights are tp-sharded inside the "
                "dispatcher's expert shard; the explicit exchange only "
                "covers the token axes")
    if sizes.get("sp", 1) > 1:
        return ("mesh.sp > 1: Ulysses/ring resharding conflicts with "
                "the dispatcher's token-axis reduce-scatter layout")
    if sizes.get("pp", 1) > 1:
        return "mesh.pp > 1: pipeline stages wrap the model differently"
    ep = sizes.get("ep", 1)
    if ep > 1 and (num_experts <= 0 or num_experts % ep != 0):
        return (f"num_experts={num_experts} is not divisible by "
                f"mesh.ep={ep}")
    return None


@dataclasses.dataclass(frozen=True)
class EpShardedDispatcher:
    """Callable the engine binds to the model (``moe_dispatcher``
    attr); ``moe_ffn`` hands it the flat tokens plus the global
    combine/dispatch tables and gets the combined output back.

    token_axes: live batch axes in PartitionSpec order — the axes
    tokens are sharded over and the exchange reduces across, split into
    ``slow_axes`` (dp/fsdp inter-hop) and ``fast_axes`` (zps
    intra-hop) for the hierarchical wire.
    """

    mesh: Any
    token_axes: tuple[str, ...]
    slow_axes: tuple[str, ...]
    fast_axes: tuple[str, ...]
    ep_axis: str = "ep"
    wire_dtype: str = "fp32"
    rounding: str = "stochastic"

    @classmethod
    def for_topology(cls, topology, wire_dtype: str = "fp32",
                     rounding: str = "stochastic"):
        live = tuple(a for a in ("dp", "fsdp", "zps")
                     if topology.sizes.get(a, 1) > 1)
        return cls(mesh=topology.mesh, token_axes=live,
                   slow_axes=tuple(a for a in live if a != "zps"),
                   fast_axes=tuple(a for a in live if a == "zps"),
                   wire_dtype=wire_dtype, rounding=rounding)

    @property
    def token_world(self) -> int:
        w = 1
        for a in self.token_axes:
            w *= int(self.mesh.shape[a])
        return w

    def __call__(self, xt: jax.Array, combine: jax.Array,
                 dispatch: jax.Array, experts: dict,
                 expert_fn: Callable) -> jax.Array:
        n, d = xt.shape
        _, e, c = combine.shape
        t = self.token_world
        c_pad = -(-c // t) * t          # capacity multiple of token world
        ep = self.ep_axis
        seed = current_step()

        tok = tuple(self.token_axes) or None
        tok_spec = P(tok, None)
        table_spec = P(tok, ep, None)
        expert_specs = jax.tree.map(
            lambda w: P(ep, *([None] * (w.ndim - 1))), experts)

        def body(xt_l, comb_l, disp_l, seed_l, experts_l):
            # local partial dispatch: slots claimed by LOCAL tokens only
            part = jnp.einsum("nec,nd->ecd", disp_l, xt_l,
                              preferred_element_type=xt_l.dtype)
            if c_pad != c:
                part = jnp.pad(part, ((0, 0), (0, c_pad - c), (0, 0)))
            shard = moe_dispatch_exchange(
                part, self.slow_axes, self.fast_axes, dim=1,
                wire_dtype=self.wire_dtype, rounding=self.rounding,
                seed=seed_l)
            h = expert_fn(shard, experts_l)
            full = moe_combine_exchange(
                h, self.slow_axes, self.fast_axes, dim=1,
                wire_dtype=("bf16" if self.wire_dtype == "bf16"
                            else "fp32"))
            if c_pad != c:
                full = full[:, :c]
            out = jnp.einsum("nec,ecd->nd", comb_l, full)
            # every expert shard combined a disjoint E slice; SUM over
            # ep replicates the block output (activations stay
            # replicated over ep outside the dispatcher)
            return lax.psum(out, ep)

        return shard_map(
            body, mesh=self.mesh,
            in_specs=(tok_spec, table_spec, table_spec, P(),
                      expert_specs),
            out_specs=tok_spec, check_vma=False)(
                xt, combine, dispatch, seed, experts)


def publish_router_metrics(metrics: dict) -> None:
    """Surface top_k_gating's routing metrics through the telemetry
    registry (drop fraction + expert-load spread gauges; capacity is
    static and set at trace time). Uses ``jax.debug.callback`` so the
    values come off-device each executed step; under the layer scan the
    LAST layer's values win (one gauge per metric — documented in
    docs/moe.md). No-op when telemetry is inactive (zero-import probe,
    GL040)."""
    from ..utils.telemetry_probe import active_telemetry
    tel = active_telemetry()
    if tel is None:
        return
    reg = tel.get_registry()
    if reg is None:
        return
    reg.gauge("ds_moe_router_capacity",
              "per-expert capacity slots (static)").set(
                  float(metrics["capacity"]))

    def _emit(drop, load_min, load_max):
        t = active_telemetry()
        r = t.get_registry() if t is not None else None
        if r is None:
            return
        r.gauge("ds_moe_router_drop_fraction",
                "fraction of top-k routing choices dropped at "
                "capacity").set(float(drop))
        r.gauge("ds_moe_router_expert_load_min",
                "min over experts of the top-1 routing "
                "fraction").set(float(load_min))
        r.gauge("ds_moe_router_expert_load_max",
                "max over experts of the top-1 routing "
                "fraction").set(float(load_max))

    load = metrics["expert_load"]
    jax.debug.callback(_emit, metrics["drop_fraction"], jnp.min(load),
                       jnp.max(load))


def record_held_expert_counts(reg, counts: dict) -> None:
    """One finished step's ``moe_*`` metrics (``models/stack.py``
    ``_held_metrics``, returned by a routed family's ``after_step``: device
    scalars the step returns, traced or not) into the telemetry registry,
    on the host: the rows (token, choice) routed to the experts held here,
    the routed-layer calls, the rows routed but not computed (0, or the
    dispatch dropped tokens).
    ``ds_moe_held_rows_total / (ds_moe_held_calls_total x experts held)``
    is the mean tokens a held expert a layer call, over
    ``ds_moe_held_steps_total`` finished steps; the two ``_step_``
    gauges keep the least and the most that mean was in one step. Where
    the step counts its blocks: ``ds_moe_held_blocks_total`` (blocks the
    dispatch swept) and ``ds_moe_held_block_rows`` (rows of one), so the
    share of swept rows that is padding is ``1 - rows / (blocks x block
    rows)``; and the largest and smallest rows ANY of the router's experts
    was sent in one layer of one step (``ds_moe_load_step_max`` /
    ``_min``). Where the sweeps count themselves (``moe_sweep_*``, the
    chunk loop's own bound: ``moe.sharded_moe._held_sweep``):
    ``ds_moe_sweep_trips_total`` (``ds_moe_held_calls_total`` where no call
    took a second), ``ds_moe_sweep_tiles_total{state}`` (row tiles with a
    ``live`` row, which a kernel runs, and tiles ``swept``, which a chunk
    holds, gathers and adds), ``ds_moe_sweep_tile_rows``,
    ``ds_moe_sweep_trips_step_max`` (the most trips one layer call took in
    any step) and ``ds_moe_sweep_extra_trip_steps_total`` (steps in which
    some call took more than one); such a step also leaves ONE host event
    ``moe_extra_trip`` (``trips``, ``calls``) with the active tracer, inside
    the ``step_boundary`` span that follows the NEXT step's dispatch (the
    registry is one step behind); a step without one records no event."""
    rows, done = float(counts["moe_held_rows"]), float(counts["moe_held_done"])
    calls, held = float(counts["moe_held_calls"]), float(
        counts["moe_held_experts"])
    first = reg.get("ds_moe_held_calls_total") is None

    def extreme(name, text, value, pick):
        g = reg.gauge(name, text)
        g.set(value if first else pick(g.value(), value))

    reg.counter("ds_moe_held_rows_total",
                "rows (token, choice) routed to held experts").inc(rows)
    reg.counter("ds_moe_held_calls_total",
                "held-expert layer calls").inc(calls)
    reg.counter("ds_moe_held_steps_total",
                "finished steps whose held-expert counts were recorded"
                ).inc()
    reg.counter("ds_moe_dropped_rows_total",
                "rows routed to a held expert and not computed").inc(
                    rows - done)
    reg.gauge("ds_moe_held_experts",
              "experts held by this chip's share of a routed layer").set(held)
    per = rows / (calls * held)
    extreme("ds_moe_held_tokens_step_min",
            "least mean tokens a held expert a call, of any step", per, min)
    extreme("ds_moe_held_tokens_step_max",
            "most mean tokens a held expert a call, of any step", per, max)
    if "moe_held_blocks" in counts:
        reg.counter("ds_moe_held_blocks_total",
                    "blocks the held dispatch swept").inc(
                        float(counts["moe_held_blocks"]))
        reg.gauge("ds_moe_held_block_rows",
                  "rows a block of the held dispatch").set(
                      float(counts["moe_held_block"]))
        extreme("ds_moe_load_step_max",
                "most rows any expert of the router was sent in one layer "
                "of any step", float(counts["moe_load_max"]), max)
        extreme("ds_moe_load_step_min",
                "least rows any expert of the router was sent in one layer "
                "of any step", float(counts["moe_load_min"]), min)
    if "moe_sweep_trips" in counts:
        trips = float(counts["moe_sweep_trips"])
        reg.counter("ds_moe_sweep_trips_total",
                    "trips of the held sweep's chunk loop").inc(trips)
        tiles = reg.counter("ds_moe_sweep_tiles_total",
                            "row tiles of the held sweep: with a live row "
                            "(a kernel runs them) and swept (a chunk holds "
                            "them)")
        tiles.inc(float(counts["moe_sweep_tiles"]), state="live")
        tiles.inc(float(counts["moe_sweep_swept"]), state="swept")
        reg.gauge("ds_moe_sweep_tile_rows",
                  "rows a row tile of the held sweep").set(
                      float(counts["moe_sweep_tile"]))
        extreme("ds_moe_sweep_trips_step_max",
                "most trips one held-expert layer call took in any step",
                float(counts["moe_sweep_trips_max"]), max)
        extra = reg.counter("ds_moe_sweep_extra_trip_steps_total",
                            "finished steps in which a held-expert layer "
                            "call took more than one trip")
        extra.inc(float(trips > calls))
        if trips > calls:
            from ..utils.telemetry_probe import tel_span
            with tel_span("moe_extra_trip", trips=int(trips),
                          calls=int(calls)):
                pass

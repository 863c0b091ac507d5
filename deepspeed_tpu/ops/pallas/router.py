"""A router's selection as ONE pass over its score tile: a Pallas kernel
pair under one ``jax.custom_vjp``; ``moe/sharded_moe.py`` ``top_k_of`` is
the caller.

``lax.top_k`` of [N, E] at k of E is, on the chip, a copy to
tokens-on-lanes, a FULL stable sort of every row and a copy back; the
chosen experts' scores are then a gather of ``N k`` single float32s, the
step's load a scatter-add of ``N k`` ones and the scores' gradient a
scatter of ``N k`` float32s: 10 ns a (token, choice) pair each, where the
whole selection is one read of the scores (``PERF.md`` section 6, PR 67).
Here the tile stays in VMEM for ``k`` rounds::

    m      = max_e cur[e, n]                         the row's maximum
    idx_j  = min { e : cur[e, n] == m }              the LOWEST index holds it
    w_j    = scores[idx_j, n]                        by compare and select
    cur[idx_j, n] = -inf                             struck out

- **Layout**: experts down the sublanes, tokens along the lanes, [E, N]
  (what XLA's own sort wants): a round's maximum over E is an elementwise
  maximum of E / 8 vector registers and ONE 8-to-1 sublane reduce for 128
  tokens. The operands are [E / 8, 8, N] (the same bytes), so a register is
  ``x[i]`` and an expert's index ``8 i + sublane``. ``idx`` and ``w`` leave
  [k, N], lane-dense.
- **Result**: ``idx`` is ``lax.top_k(select, k)[1]`` element for element,
  rank order and ties (the lower index first) included; ``w`` is
  ``take_along_axis(scores, idx)`` to the bit (a maximum of one value and
  ``-inf``s); ``load`` is the ``bincount`` of ``idx``: a position is struck
  out once, so an expert's load is its ``-inf``s, counted a lane in an
  int32 block that stays in VMEM across the grid and summed over the lanes
  by the caller. ``select`` must hold no ``-inf`` and no NaN (a sigmoid
  with a bias, a softmax: neither can).
- **Backward** (``ds_router_bwd``): ``dscores[e, n] = dw[j, n]`` where
  ``idx[j, n] == e``, else 0: k compare-and-selects into a zero tile (a
  row's k positions are distinct, nothing is summed). The selection gets
  no gradient.
- **Grid**: tiles of ``_TOKENS`` tokens, walked ``_LANES`` at a time in
  registers; a grid step's blocks are ``E x _TOKENS`` float32s, twice
  buffered: 2 MiB an operand at E = 512, no more VMEM than any XLA op
  gets. Each kernel is traced once a shape (``_common._bind``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _bind, _interpret, _nbytes, _registry

_TOKENS = 512       # tokens a grid step, at most
_LANES = 128        # tokens a pass in registers: a register's lanes
_SUB = 8            # experts a register: its sublanes
_STEP = 8192        # tokens from which a router takes the pair
SCOPE = "ds.moe_router"


def runs(tokens: int, experts: int, k: int) -> bool:
    """Whether the pair can take the shape: tokens in whole lane blocks,
    experts in whole sublane tiles."""
    return (tokens % _LANES == 0 and experts % _SUB == 0
            and 0 < k <= experts)


def fits(tokens: int, experts: int, k: int) -> bool:
    """Whether a router takes the pair (``moe/sharded_moe.py``
    ``top_k_of``): a shape it runs at, in whole grid tiles, of a train
    step's worth of tokens (``_STEP``: the least any cell of
    ``benchmark/configs/`` routes; under it both forms are a few launches,
    0.02 against 0.7 ms at 8192 tokens over 64 experts, and interpret mode
    pays k passes over the tile on the CPU: ``PERF.md`` section 6,
    PR 67)."""
    return (runs(tokens, experts, k) and tokens % _TOKENS == 0
            and tokens >= _STEP)


def count_router(form: str, experts: int, k: int):
    """Trace time, host only: gauge ``ds_router_calls`` counts the routers
    this process has built in each form, by the experts routed over and
    the choices a token: ``kernel`` (this module's pair) or ``xla``
    (``lax.top_k`` and its gather)."""
    reg = _registry()
    if reg is not None:
        reg.gauge("ds_router_calls",
                  "routers' selections built so far as a kernel pair "
                  "(form=kernel: ds_router_fwd / ds_router_bwd) or as "
                  "lax.top_k and a gather (form=xla), by experts routed "
                  "over and choices a token"
                  ).inc(1, form=form, experts=str(experts), k=str(k))


def _tokens(n: int) -> int:
    t = _TOKENS
    while n % t:
        t //= 2
    return t


def _fwd_kernel(*refs, k, own):
    """One tile of tokens: ``select`` (and ``scores`` unless ``own``: the
    selection's own values are the weights) [V, 8, T] to ``idx``, ``w``
    [k, T] and the lanes' counts ``load`` [V, 8, 128], summed over the
    grid."""
    if own:
        sel_ref, idx_ref, w_ref, load_ref, cur_ref = refs
    else:
        sel_ref, sc_ref, idx_ref, w_ref, load_ref, cur_ref = refs
    v = sel_ref.shape[0]
    f32, i32 = jnp.float32, jnp.int32
    shape = (v, _SUB, _LANES)
    reg = jax.lax.broadcasted_iota(i32, shape, 0)       # a register's place
    sub = jax.lax.broadcasted_iota(i32, (_SUB, _LANES), 0)
    # the choices' ranks: a group's idx and w are built in registers
    rank = jax.lax.broadcasted_iota(i32, (-(-k // _SUB) * _SUB, _LANES), 0)
    low = jnp.float32(-jnp.inf)

    def top(x):         # [V, 8, L] -> [1, L]
        return jnp.max(jnp.max(x, axis=0), axis=0, keepdims=True)

    @pl.when(pl.program_id(0) == 0)
    def _():
        load_ref[...] = jnp.zeros(shape, i32)

    def group(g, _):
        lanes = pl.ds(pl.multiple_of(g * _LANES, _LANES), _LANES)
        cur = sel_ref[:, :, lanes]
        cur_ref[...] = cur

        def one(j, carry):
            m, idx, w = carry
            cur = cur_ref[...]
            # the lowest expert that holds the maximum: the lowest register
            # a sublane, then the lowest 8 i + sublane of the eight
            at = jnp.min(jnp.where(cur == m[None], reg, v), axis=0)
            first = jnp.min(at * _SUB + sub, axis=0, keepdims=True)
            first = jnp.minimum(first, v * _SUB - 1)
            hit = reg * _SUB == (first - sub)[None]
            if not own:
                m = top(jnp.where(hit, sc_ref[:, :, lanes], low))
            cur = jnp.where(hit, low, cur)
            cur_ref[...] = cur
            return (top(cur), jnp.where(rank == j, first, idx),
                    jnp.where(rank == j, m, w))

        _, idx, w = jax.lax.fori_loop(
            0, k, one, (top(cur), jnp.zeros(rank.shape, i32),
                        jnp.zeros(rank.shape, f32)))
        idx_ref[:, lanes] = idx[:k]
        w_ref[:, lanes] = w[:k]
        load_ref[...] += (cur_ref[...] == low).astype(i32)
        return 0

    jax.lax.fori_loop(0, sel_ref.shape[2] // _LANES, group, 0)


def _bwd_kernel(idx_ref, dw_ref, o_ref, *, k):
    """One tile of tokens: ``idx``, ``dw`` [k, T] to ``dscores``
    [V, 8, T]."""
    v = o_ref.shape[0]
    step = math.gcd(v, 8)       # registers a trip of the inner loop
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUB, _LANES), 0)

    def group(g, _):
        lanes = pl.ds(pl.multiple_of(g * _LANES, _LANES), _LANES)
        # a choice's expert less the sublane: 8 i where register i holds it
        at = [idx_ref[j:j + 1, lanes] - sub for j in range(k)]
        dw = [jnp.broadcast_to(dw_ref[j:j + 1, lanes], (_SUB, _LANES))
              for j in range(k)]

        def registers(b, _):
            for i in (b * step + u for u in range(step)):
                acc = jnp.zeros((_SUB, _LANES), jnp.float32)
                for j in range(k):
                    acc = jnp.where(at[j] == i * _SUB, dw[j], acc)
                o_ref[i, :, lanes] = acc
            return 0

        jax.lax.fori_loop(0, v // step, registers, 0)
        return 0

    jax.lax.fori_loop(0, o_ref.shape[2] // _LANES, group, 0)


def _forward(select, scores, k):
    """select (and scores, or None for the selection's own) [V, 8, N] to
    idx, w [k, N] and load [V, 8, 128]."""
    v, _, n = select.shape
    t = _tokens(n)
    own = scores is None
    tile = pl.BlockSpec((v, _SUB, t), lambda r: (0, 0, r))
    picks = pl.BlockSpec((k, t), lambda r: (0, r))
    out_shape = [jax.ShapeDtypeStruct((k, n), jnp.int32),
                 jax.ShapeDtypeStruct((k, n), jnp.float32),
                 jax.ShapeDtypeStruct((v, _SUB, _LANES), jnp.int32)]
    args = (select,) if own else (select, scores)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, k=k, own=own),
        grid=(n // t,),
        in_specs=[tile] * len(args),
        out_specs=[picks, picks,
                   pl.BlockSpec((v, _SUB, _LANES), lambda r: (0, 0, 0))],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((v, _SUB, _LANES), jnp.float32)],
        # the load's block stays in VMEM across the grid
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=int((6 if own else 8) * k * select.size),
            transcendentals=0,
            bytes_accessed=int(_nbytes(*args, *out_shape))),
        interpret=_interpret(),
        name="ds_router_fwd",
    )
    return _bind(call, SCOPE, ("ds_router_fwd", k, own, t), *args)


def _backward(idx, dw, v):
    """idx, dw [k, N] to dscores [V, 8, N]."""
    k, n = idx.shape
    t = _tokens(n)
    picks = pl.BlockSpec((k, t), lambda r: (0, r))
    out_shape = jax.ShapeDtypeStruct((v, _SUB, n), jnp.float32)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, k=k),
        grid=(n // t,),
        in_specs=[picks, picks],
        out_specs=pl.BlockSpec((v, _SUB, t), lambda r: (0, 0, r)),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * k * v * _SUB * n), transcendentals=0,
            bytes_accessed=int(_nbytes(idx, dw, out_shape))),
        interpret=_interpret(),
        name="ds_router_bwd",
    )
    # the router's scope, opened here too: a custom_vjp's backward function
    # is traced outside the scope its forward was called under
    return _bind(call, SCOPE, ("ds_router_bwd", k, v, t), idx, dw)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _route(select, scores, k, own):
    return tuple(_forward(select, None if own else scores, k))


def _route_fwd(select, scores, k, own):
    out = _route(select, scores, k, own)
    return out, out[0]          # the experts chosen are all it keeps


def _route_bwd(k, own, idx, cts):
    return None, _backward(idx, cts[1], cts[2].shape[0])


_route.defvjp(_route_fwd, _route_bwd)


def top_k_rows(select, scores, k: int):
    """The ``k`` largest of each token's ``select`` [E, N] float32 (experts
    first: tokens on the lanes), the lower index first among equals:
    ``idx`` [k, N] int32 in rank order, ``w`` [k, N] float32 (``scores``
    [E, N] at ``idx``) and ``load`` [E] int32, the tokens that chose each
    expert. ``scores`` None: the weights are ``select``'s own values; the
    weights' gradient goes to the values they are, ``select`` gets none
    for the choice. The module docstring; ``runs`` says which shapes."""
    e, n = select.shape
    own = scores is None
    if not runs(n, e, k) or not (own or scores.shape == select.shape):
        raise ValueError(f"top_k_rows: select {select.shape}, k {k}")
    tiles = lambda a: a.astype(jnp.float32).reshape(  # noqa: E731
        e // _SUB, _SUB, n)
    select = tiles(select)
    idx, w, load = _route(select, select if own else tiles(scores), k, own)
    return idx, w, jnp.sum(load, axis=2).reshape(e)

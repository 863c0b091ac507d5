"""The held experts' dispatch in ``jax.numpy``, as ``moe/sharded_moe.py``
held it until PR 41: a loop over blocks of the sorted row index whose trip
count is data, forward and (one ``custom_vjp``) backward, the reference
of the grouped-matmul kernels ``ds_moe_gmm_fwd`` / ``ds_moe_gmm_bwd``
(``ops/pallas/grouped_matmul.py``). ``gate``, ``up`` and ``h`` are bf16
matmul results here and go through HBM; the backward adds each block's
``dW`` into float32 [E_h, D, F] carries (8.26 MB read and written a weight
a block at 2304 x 896). ``tests/test_grouped_matmul.py`` compares the two;
``tools/moe_kernel_bench.py`` times them side by side on the chip. Nothing
here is the package's: the layout is the loop's own ``argsort`` and
``bincount``, so a fault in the package's sort is not shared."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax



def _held_layout(idx, first: int, n_held: int, block: int):
    """Rows (token, choice) routed to the experts held here, sorted by
    expert and cut into blocks of ``block`` rows that each lie in ONE
    expert's run. Returns ``order`` [N*k + block] (row ids by expert, the
    rows of absent experts last, padded), ``counts`` and ``starts`` [E_h]
    of each held expert's run in it, and ``ends`` [E_h]: the number of
    blocks up to and with the expert's own (the last is the total)."""
    n, k = idx.shape
    local = idx - first
    held = (local >= 0) & (local < n_held)
    e_flat = jnp.where(held, local, n_held).reshape(-1)
    order = jnp.argsort(e_flat, stable=True).astype(jnp.int32)
    counts = jnp.bincount(e_flat, length=n_held + 1)[:n_held].astype(
        jnp.int32)
    starts = jnp.cumsum(counts) - counts
    ends = jnp.cumsum((counts + block - 1) // block)
    order = jnp.concatenate([order, jnp.zeros((block,), jnp.int32)])
    return order, counts, starts, ends


def _swiglu_rows(xg, w_gate, w_up):
    gate = xg @ w_gate
    up = xg @ w_up
    return gate, up, jax.nn.silu(gate) * up


def _block_rows(b, layout, k: int, block: int):
    """Block ``b`` of the layout: its expert, its row ids [block], their
    tokens, and which of the rows are the expert's (the last block of a
    run is part empty)."""
    order, counts, starts, ends = layout
    e = jnp.sum(b >= ends).astype(jnp.int32)
    at = (b - ends[e]) * block + (counts[e] + block - 1) // block * block
    rows = lax.dynamic_slice(order, (starts[e] + at,), (block,))
    valid = jnp.arange(block) < counts[e] - at
    return e, rows, rows // k, valid


def _held_fwd_loop(x, idx, weights, experts, first, block):
    n, d = x.shape
    k = idx.shape[1]
    n_held = experts["w_up"].shape[0]
    layout = _held_layout(idx, first, n_held, block)
    w_flat = weights.reshape(-1)

    def body(b, carry):
        out, done = carry
        e, rows, tokens, valid = _block_rows(b, layout, k, block)
        xg = jnp.where(valid[:, None], x[tokens], 0)
        _, _, h = _swiglu_rows(xg, experts["w_gate"][e], experts["w_up"][e])
        y = (h @ experts["w_down"][e]).astype(jnp.float32)
        scale = jnp.where(valid, w_flat[rows], 0.0)
        return (out.at[tokens].add(y * scale[:, None]),
                done + jnp.sum(valid))

    out, done = lax.fori_loop(
        0, layout[-1][-1], body,
        (jnp.zeros((n, d), jnp.float32), jnp.zeros((), jnp.int32)))
    return out.astype(x.dtype), done


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def held_experts_ffn(x, idx, weights, experts, first, block):
    """``moe.sharded_moe.held_experts_ffn`` as the block loop: each block
    one gather, three matmuls against ONE expert's weights and one
    scatter-add into a float32 [N, D] carry; returns (out [N, D], rows
    computed)."""
    return _held_fwd_loop(x, idx, weights, experts, first, block)


def _held_fwd_rule(x, idx, weights, experts, first, block):
    out = _held_fwd_loop(x, idx, weights, experts, first, block)
    return out, (x, idx, weights, experts)


def _held_bwd_rule(first, block, res, cts):
    """One more sweep over the same blocks: the expert's two input
    matmuls are run again (nothing of the forward sweep is kept but its
    inputs), then the six of the backward."""
    x, idx, weights, experts = res
    dout = cts[0]
    n, d = x.shape
    k = idx.shape[1]
    n_held = experts["w_up"].shape[0]
    f32 = jnp.float32
    layout = _held_layout(idx, first, n_held, block)
    w_flat = weights.reshape(-1)

    def body(b, carry):
        dx, dw, dg, du, dd = carry
        e, rows, tokens, valid = _block_rows(b, layout, k, block)
        xg = jnp.where(valid[:, None], x[tokens], 0)
        gate, up, h = _swiglu_rows(xg, experts["w_gate"][e],
                                   experts["w_up"][e])
        y = h @ experts["w_down"][e]
        dout_g = jnp.where(valid[:, None], dout[tokens], 0)
        dw = dw.at[rows].add(jnp.where(valid, jnp.sum(
            dout_g.astype(f32) * y.astype(f32), axis=-1), 0.0))
        dy = (dout_g.astype(f32)
              * jnp.where(valid, w_flat[rows], 0.0)[:, None]
              ).astype(x.dtype)
        dh = dy @ experts["w_down"][e].T
        sg = jax.nn.sigmoid(gate.astype(f32))
        d_up = (dh * (gate.astype(f32) * sg)).astype(x.dtype)
        d_gate = (dh * up * (sg * (1 + gate.astype(f32) * (1 - sg)))
                  ).astype(x.dtype)
        dxg = (d_gate @ experts["w_gate"][e].T
               + d_up @ experts["w_up"][e].T)
        acc = lambda t, a, b_: t.at[e].add(  # noqa: E731
            jnp.matmul(a.T, b_, preferred_element_type=f32))
        return (dx.at[tokens].add(dxg.astype(f32)), dw,
                acc(dg, xg, d_gate), acc(du, xg, d_up), acc(dd, h, dy))

    zeros = lambda w: jnp.zeros(w.shape, f32)  # noqa: E731
    dx, dw, dg, du, dd = lax.fori_loop(
        0, layout[-1][-1], body,
        (jnp.zeros((n, d), f32), jnp.zeros((n * k + block,), f32),
         zeros(experts["w_gate"]), zeros(experts["w_up"]),
         zeros(experts["w_down"])))
    d_experts = {"w_gate": dg.astype(experts["w_gate"].dtype),
                 "w_up": du.astype(experts["w_up"].dtype),
                 "w_down": dd.astype(experts["w_down"].dtype)}
    return (dx.astype(x.dtype), None,
            dw[:n * k].reshape(n, k).astype(weights.dtype), d_experts)


held_experts_ffn.defvjp(_held_fwd_rule, _held_bwd_rule)

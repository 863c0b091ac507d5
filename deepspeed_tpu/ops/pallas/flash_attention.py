"""Pallas flash attention for TPU (causal, GQA-aware).

TPU-native replacement for the reference's fused attention CUDA kernels
(csrc/transformer/softmax_kernels.cu + inference blocked_flash): one
kernel streams k/v blocks through VMEM with online-softmax accumulation,
never materializing the [S, S] score matrix; a custom VJP recomputes
probabilities blockwise in the backward (flash-attention-2 style).

Design notes (why this beats the stock two-pass kernel at model shapes):

- **One-pass backward**: dq, dk and dv are produced in a single sweep
  over (kv-block, q-block) pairs, so the score matrix is recomputed once
  per block pair instead of twice (the stock dq-then-dkv design runs the
  s/p matmuls in both passes). The TPU Pallas grid executes sequentially
  on the core, so the full [S, D] dq for the current (batch, head) stays
  resident in VMEM as an output block whose index map depends only on
  the batch*head grid axis, accumulating across every step.
- **Inner loop in-kernel**: the grid iterates (bh, block); the opposing
  operand (k/v in forward, q/do in backward) is VMEM-resident for the
  whole row and swept with a `lax.fori_loop` whose trip count starts at
  the causal boundary — no wasted grid steps, and Mosaic pipelines the
  per-block DMAs against the loop body.
- **bf16 MXU operands** with f32 accumulation (`preferred_element_type`);
  p/ds are cast back to the input dtype before their dots (upcasting
  operands to f32 would halve the MXU rate).

Layout: wrapper takes [B, S, H, D] (model convention), kernels run on
[B*H, S, D]. The log-sum-exp is carried as [BH, 1, S] so every block
spec is TPU-legal ((1, 1, bq) blocks). VMEM residency caps the supported
sequence length per head dim (_resident_max_seq); past it the wrapper
falls back to the stock two-pass jax.experimental kernel.

On non-TPU backends the kernels run in Pallas interpret mode (tests), so
the same code path is exercised everywhere.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# k/v (fwd) and q/do/dq (bwd) are VMEM-resident per (batch*head) row, so
# the working set scales with s*d: at 32k x 128 that is ~8M bf16 per
# operand + a 16M f32 dq slab — ~45M total against the raised
# _COMPILER_PARAMS ceiling (v5e/v5p have 128M). The dispatch gates on
# s*d (64k at d=64, 32k at d=128, 16k at d=256). The reason for the
# resident form is one pass over k/v where the stock kernel makes two;
# a run before this round's records read 1.38x the stock kernel's
# training throughput at seq 32768 x d128 (not in the ledger: no cell
# runs that length).
_RESIDENT_MAX_ELEMS = 32768 * 128


def _resident_max_seq(d: int) -> int:
    return _RESIDENT_MAX_ELEMS // max(d, 1)

# the row-resident kernels hold [S, D] slabs (q/do/dq + temps) in VMEM;
# Mosaic's default 16MB scoped-vmem ceiling trips at long seq x D=128 —
# raise it (v5e/v5p have 128MB)
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=100 * 1024 * 1024)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _block(s: int) -> int:
    """Largest of 512/256/128 dividing s (wrapper guarantees s % 128 == 0
    or s <= 128)."""
    for b in (512, 256, 128):
        if s % b == 0:
            return b
    return s


# ---------------------------------------------------------------- forward
def _flash_fwd(q, k, v, *, causal: bool, sc: float,
               window: int | None = None, rep: int = 1):
    """``rep``: GQA group size — q rows are [B*Hq, S, D], k/v rows
    [B*Hkv, S, D]; the kv index maps divide the q-head grid index by
    ``rep`` instead of materializing repeated k/v."""
    bh, s, d = q.shape
    bq = bk = _block(s)
    grid = (bh, s // bq)
    kernel = functools.partial(_fwd_kernel, sc=sc, bq=bq, bk=bk,
                               nk=s // bk, causal=causal, window=window)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s, d), lambda b, i: (b // rep, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s, d), lambda b, i: (b // rep, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
        name="ds_flash_fwd",
    )
    # the scope and the kernel's name are all a device trace shows of this
    # call (telemetry/scopes.py); both are HLO metadata and cost no time
    with jax.named_scope("ds.flash_fwd"):
        o, lse = call(q, k, v)
    return o.astype(q.dtype), lse


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sc, bq, bk, nk,
                causal, window):
    """Online-softmax forward: q block vs the VMEM-resident k/v row.
    ``window`` (Mistral SWA): query r sees keys in (r - window, r] — the
    kv sweep starts at the window's first live block and the in-block
    mask drops the tail."""
    i = pl.program_id(1)
    q = q_ref[0]
    d = q.shape[-1]

    def body(j, carry):
        o_acc, m, l = carry
        kj = k_ref[0, pl.ds(j * bk, bk), :]
        vj = v_ref[0, pl.ds(j * bk, bk), :]
        s = jnp.dot(q, kj.T, preferred_element_type=jnp.float32) * sc
        if causal or window is not None:
            qi = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + i * bq
            ki = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
            live = qi >= ki if causal else (qi == qi)
            if window is not None:
                live &= qi - ki < window
            s = jnp.where(live, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_acc = o_acc * corr + jnp.dot(p.astype(q.dtype), vj,
                                       preferred_element_type=jnp.float32)
        return o_acc, m_new, l

    # causal: q block i attends kv blocks [0, i] (bq == bk); a window
    # additionally floors the sweep at its first live block
    hi = (i + 1) if causal else nk
    lo = (jnp.maximum(0, (i * bq - window + 1) // bk)
          if window is not None else 0)
    o_acc, m, l = jax.lax.fori_loop(
        lo, hi, body,
        (jnp.zeros((bq, d), jnp.float32),
         jnp.full((bq, 1), NEG_INF, jnp.float32),
         jnp.zeros((bq, 1), jnp.float32)))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = o_acc / l
    lse_ref[0, 0, :] = (m + jnp.log(l))[:, 0]


# ---------------------------------------------------------------- backward
def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, sc, bq, bk, nq, causal,
                      window):
    # dk/dv are emitted PER Q-HEAD (summed over the GQA group outside —
    # cheap XLA reduce); k/v rows are indexed b // rep by the caller
    """One-pass backward: kv block j vs the VMEM-resident q/do row. dq
    accumulates into the full-[S, D] VMEM-resident output slab (index map
    depends only on the bh grid axis; the sequential grid makes the
    accumulation race-free)."""
    j = pl.program_id(1)
    k = k_ref[0]
    v = v_ref[0]
    d = k.shape[-1]

    @pl.when(j == 0)
    def _():
        dq_ref[:] = jnp.zeros_like(dq_ref)

    def body(i, carry):
        dk_acc, dv_acc = carry
        rows = (0, pl.ds(i * bq, bq), slice(None))
        qi_ = q_ref[rows]
        doi = do_ref[rows]
        lse = lse_ref[0, 0, pl.ds(i * bq, bq)][:, None]       # [bq, 1]
        delta = delta_ref[0, 0, pl.ds(i * bq, bq)][:, None]
        s = jnp.dot(qi_, k.T, preferred_element_type=jnp.float32) * sc
        if causal or window is not None:
            qi = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + i * bq
            ki = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
            live = qi >= ki if causal else (qi == qi)
            if window is not None:
                live &= qi - ki < window
            s = jnp.where(live, s, NEG_INF)
        p = jnp.exp(s - lse).astype(k.dtype)
        dv_acc += jnp.dot(p.T, doi, preferred_element_type=jnp.float32)
        dp = jnp.dot(doi, v.T, preferred_element_type=jnp.float32)
        ds = (p.astype(jnp.float32) * (dp - delta)).astype(k.dtype)
        dk_acc += jnp.dot(ds.T, qi_,
                          preferred_element_type=jnp.float32) * sc
        dq_ref[rows] += jnp.dot(ds, k,
                                preferred_element_type=jnp.float32) * sc
        return dk_acc, dv_acc

    # causal: kv block j is attended by q blocks [j, nq) (bq == bk); a
    # window additionally caps the sweep at its last live block
    lo = j if causal else 0
    hi = (jnp.minimum(nq, (j * bk + bk - 1 + window - 1) // bq + 1)
          if window is not None else nq)
    dk_acc, dv_acc = jax.lax.fori_loop(
        lo, hi, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)))
    dk_ref[0] = dk_acc
    dv_ref[0] = dv_acc


def _flash_bwd(q, k, v, o, lse, do, *, causal: bool, sc: float,
               window: int | None = None, rep: int = 1):
    bh, s, d = q.shape
    bq = bk = _block(s)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, s)

    rowfull = pl.BlockSpec((1, s, d), lambda b, j: (b, 0, 0),
                           memory_space=pltpu.VMEM)
    kin = pl.BlockSpec((1, bk, d), lambda b, j: (b // rep, j, 0),
                       memory_space=pltpu.VMEM)
    kout = pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0),
                        memory_space=pltpu.VMEM)
    rowstat = pl.BlockSpec((1, 1, s), lambda b, j: (b, 0, 0),
                           memory_space=pltpu.VMEM)
    call = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, sc=sc, bq=bq, bk=bk,
                          nq=s // bq, causal=causal, window=window),
        grid=(bh, s // bk),
        in_specs=[rowfull, kin, kin, rowfull, rowstat, rowstat],
        out_specs=[rowfull, kout, kout],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
                   jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
                   jax.ShapeDtypeStruct((bh, s, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
        name="ds_flash_bwd",
    )
    # opened here, inside the custom_vjp's backward function, so that the
    # scope survives shard_map and remat
    with jax.named_scope("ds.flash_bwd"):
        dq, dk, dv = call(q, k, v, do, lse, delta)
    if rep > 1:
        # per-q-head dk/dv -> per-kv-head (consecutive q heads share kv)
        dk = dk.reshape(bh // rep, rep, s, d).sum(axis=1)
        dv = dv.reshape(bh // rep, rep, s, d).sum(axis=1)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------- public
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, window, rep):
    sc = 1.0 / np.sqrt(q.shape[-1])
    o, _ = _flash_fwd(q, k, v, causal=causal, sc=sc, window=window,
                      rep=rep)
    return o


def _flash_fwd_rule(q, k, v, causal, window, rep):
    sc = 1.0 / np.sqrt(q.shape[-1])
    o, lse = _flash_fwd(q, k, v, causal=causal, sc=sc, window=window,
                        rep=rep)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, window, rep, res, do):
    q, k, v, o, lse = res
    sc = 1.0 / np.sqrt(q.shape[-1])
    return _flash_bwd(q, k, v, o, lse, do, causal=causal, sc=sc,
                      window=window, rep=rep)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None, **_kw):
    """Drop-in attn_fn: q [B, S, Hq, D], k/v [B, S, Hkv, D], matches
    ops.layers.dot_product_attention numerics. GQA is native: the
    kernels index the shared kv head per q-head group, so repeated k/v
    are never materialized (and remat residuals store unrepeated k/v —
    rep x smaller than the repeat-then-attend form). ``window``
    restricts each query to its last `window` positions (Mistral sliding
    window; kernel skips blocks fully outside the band).

    Dispatches to the in-repo one-pass kernel (see module docstring); for
    sequences past the VMEM residency cap it falls back to the stock
    two-pass jax.experimental kernel on TPU (full-causal only — a window
    there falls back to the exact masked form).
    """
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if window is not None and not causal:
        raise ValueError("window requires causal=True (Mistral SWA)")
    if hq % hkv != 0:
        raise ValueError(f"q heads {hq} must be a multiple of kv heads "
                         f"{hkv}")
    rep = hq // hkv
    if (s > 128 and s % 128 != 0) or (
            s < 128 and jax.default_backend() == "tpu"):
        # the blocked kernels require 128-aligned sequence lengths: an
        # unaligned tail would be silently dropped by the grid floor
        # division, and sub-128 blocks fail Mosaic's lane-width lowering
        # on real hardware (interpret mode accepts them, so CPU tests
        # still exercise the kernel at tiny shapes) — use the exact
        # (unfused) path instead
        from ..layers import dot_product_attention, window_bias
        from ...utils.logging import warning_once
        warning_once(
            f"flash attention falling back to the exact unfused form "
            f"(O(S^2) memory) at seq {s}: the kernel needs a sequence "
            f"length that is a multiple of 128")
        bias = window_bias(s, window) if window is not None else None
        return dot_product_attention(q, k, v, causal=causal, bias=bias)
    from jax.ad_checkpoint import checkpoint_name
    bhsd = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    if jax.default_backend() == "tpu" and s > _resident_max_seq(d):
        if rep > 1:
            # fallback paths take per-q-head kv (dot_product_attention
            # repeats internally; the stock kernel needs equal heads)
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        if d % 8 != 0 or window is not None:
            # the stock kernel needs 8-aligned head dims and supports no
            # window, and the resident kernel's VMEM budget is sized for
            # s <= _resident_max_seq(d) — use the exact masked form
            from ..layers import dot_product_attention, window_bias
            from ...utils.logging import warning_once
            warning_once(
                f"flash attention falling back to the exact masked form "
                f"(O(S^2) memory) at seq {s}: "
                + ("sliding windows are only fused up to seq "
                   f"{_resident_max_seq(d)} at head_dim {d}"
                   if window is not None
                   else f"head_dim {d} is not 8-aligned"))
            bias = window_bias(s, window) if window is not None else None
            return dot_product_attention(q, k, v, causal=causal,
                                         bias=bias)
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            BlockSizes, flash_attention as tpu_flash)
        blk = _block(s)
        bs_ = BlockSizes(
            block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
            block_q_major_dkv=blk, block_k_major_dkv=blk,
            block_k_dkv=blk, block_q_dkv=blk,
            block_k_major_dq=blk, block_k_dq=blk, block_q_dq=blk)
        o = tpu_flash(bhsd(q), bhsd(k), bhsd(v), causal=causal,
                      sm_scale=1.0 / np.sqrt(d), block_sizes=bs_)
        return checkpoint_name(
            o.transpose(0, 2, 1, 3).astype(q.dtype), "attn_out")
    # GQA-native: k/v stay per-kv-head ([B*Hkv, S, D]); the kernels index
    # kv rows at q_head_idx // rep, so repeated k/v are never
    # materialized — and the custom-VJP residuals (what remat stores per
    # layer) hold the UNREPEATED k/v
    to_bh = lambda x: bhsd(x).reshape(-1, s, d)  # noqa: E731
    o = _flash(to_bh(q), to_bh(k), to_bh(v), causal, window, rep)
    return checkpoint_name(
        o.reshape(b, hq, s, d).transpose(0, 2, 1, 3), "attn_out")


def sharded_flash_attention(mesh, batch_axes, *, tp_axis: str = "tp",
                            window: int | None = None):
    """attn_fn for a multi-device mesh: :func:`flash_attention` per shard
    under a shard_map. GSPMD cannot partition a Mosaic custom call
    ("Mosaic kernels cannot be automatically partitioned" at lowering on
    the chip), and it only lowers when EVERY mesh axis is manual — so
    the map is manual over all axes not already manual in an enclosing
    region (the compiled pipeline's ``pp`` map). Batch is split over
    ``batch_axes`` and heads over ``tp_axis`` where they divide; any
    other axis sees replicated inputs (attention is independent per
    (batch, head), so the per-shard result is exact).

    The returned callable carries ``applies_window = True``: it applies
    the model's sliding window itself, unlike the sequence-parallel
    wrappers."""
    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import active_mesh
    from ...utils.jax_compat import shard_map

    def attn(q, k, v, *, causal: bool = True, **_kw):
        use, free = active_mesh(mesh)
        b_ax = tuple(a for a in batch_axes
                     if a in free and use.shape[a] > 1)
        if q.shape[0] % math.prod(use.shape[a] for a in b_ax):
            b_ax = ()       # uneven batch: replicate, still exact
        tp = use.shape.get(tp_axis, 1)
        h_ax = (tp_axis if tp_axis in free and tp > 1
                and q.shape[2] % tp == 0 and k.shape[2] % tp == 0
                else None)
        spec = P(b_ax or None, None, h_ax, None)
        return shard_map(
            functools.partial(flash_attention, causal=causal,
                              window=window),
            mesh=use, axis_names=set(free), in_specs=(spec, spec, spec),
            out_specs=spec, check_vma=False)(q, k, v)

    attn.applies_window = True
    return attn

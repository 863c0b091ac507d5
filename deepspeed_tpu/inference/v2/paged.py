"""Paged KV attention + paged model forward (reference:
inference/v2/kernels/ragged_ops/ — blocked_flash is a paged FlashAttention
over the block table; linear_blocked_kv_rotary writes rotary-embedded k/v
into KV blocks; logits_gather picks each sequence's last-token logits).

TPU translation: each layer gathers its sequence's pages (read-only),
patches the chunk's fresh k/v into the gathered view for attention, and
emits the small chunk as a scan output; ONE bulk scatter after the layer
scan writes every layer's k/v into the pools, and the vocab projection
runs only on each sequence's last valid token (logits_gather, fused).
The pool slabs deliberately never ride the scan as ys — that would copy
the whole pool through HBM every step. On TPU with aligned shapes the
decode path can dispatch to the production paged-attention Pallas kernel;
the jnp gather path below is the portable reference and handles prefill
chunks (q_len > 1) everywhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PyTree = dict


def gather_pages(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """[num_blocks, bs, H, D] pool -> contiguous [B, smax, H, D] pages
    (clamps OOB table slots)."""
    b, max_blocks = block_tables.shape
    bs, h, d = pool.shape[1:]
    safe = jnp.minimum(block_tables, pool.shape[0] - 1)
    return pool[safe].reshape(b, max_blocks * bs, h, d)


def gather_scales(spool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """[num_blocks, bs, Hs] scale pool -> contiguous [B, smax, Hs]
    per-token scale view through the same block table the payload pool
    gathers through (ISSUE 12: the scale pool rides the block table)."""
    b, max_blocks = block_tables.shape
    bs, hs = spool.shape[1:]
    safe = jnp.minimum(block_tables, spool.shape[0] - 1)
    return spool[safe].reshape(b, max_blocks * bs, hs)


def place_in_pages(pages: jax.Array, kv: jax.Array, pos0: jax.Array,
                  true_len: jax.Array) -> jax.Array:
    """Overwrite the gathered page view with this chunk's fresh k/v at
    absolute positions [pos0, pos0+S) (invalid slots dropped). Keeps the
    pool slabs out of the layer scan: attention sees up-to-date pages
    while the bulk pool scatter happens once, after all layers."""
    b, s = kv.shape[:2]
    smax = pages.shape[1]
    positions = pos0[:, None] + jnp.arange(s)[None, :]
    valid = jnp.arange(s)[None, :] < true_len[:, None]
    positions = jnp.where(valid, positions, smax)  # OOB -> dropped
    return pages.at[jnp.arange(b)[:, None], positions].set(
        kv.astype(pages.dtype), mode="drop")


def paged_attention_kernel(q, k_new, v_new, k_pool, v_pool, block_tables,
                           pos0, true_len, *, window: int | None = None,
                           alibi_slopes=None, sanitize_pools: bool = True,
                           k_scale=None, v_scale=None):
    """Blocked-flash Pallas kernel (reference:
    inference/v2/kernels/ragged_ops/blocked_flash): attention reads KV
    pages straight from the pool through scalar-prefetched block tables —
    no gathered [B, smax, H, D] materialization — and folds this chunk's
    fresh k/v in at the end (their pool slots are written after the layer
    scan, so pages and fresh tokens never overlap).

    Grid is (batch, page-slot); blocks carry ALL heads (full-head block
    dims equal the array dims, keeping every BlockSpec TPU-legal) and a
    static Python loop handles the per-head matmuls — GQA indexes the
    shared kv head directly. Forward-only (inference).

    q/k_new/v_new: [B, S_new, H(q/kv), D]; pools [nb, bs, Hkv, D];
    block_tables [B, max_blocks] (entries clamped here); pos0/true_len
    [B]. Returns [B, S_new, Hq, D].

    **Quantized pools (ISSUE 12):** with ``k_scale``/``v_scale``
    ([nb, bs, Hs] f32, ``Hs`` = Hkv per-head or 1 per-token scales)
    the pools hold int8/fp8 codes and each K/V tile is dequantized
    IN-REGISTER inside :func:`fold`'s accumulation — one
    ``codes.astype(f32) * scale`` per tile, fused with the existing
    position-mask selects, so quantized blocks stream from HBM at 1
    byte/element with no materialized fp16 copy anywhere. Scale tiles
    ride the same scalar-prefetched block table (and the same dead-slot
    DMA-eliding index map) as their payload. The fresh-chunk fold is
    unquantized — this chunk's k/v arrive exact; quantization happens
    once, at the pool write after the layer scan.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, hq, d = q.shape
    hkv = k_new.shape[2]
    rep = hq // hkv
    nb, bs = k_pool.shape[0], k_pool.shape[1]
    max_blocks = block_tables.shape[1]
    quant = k_scale is not None
    hs = k_scale.shape[2] if quant else 0     # scale heads (Hkv or 1)
    counts = (-(-jnp.asarray(pos0, jnp.int32) // bs)).astype(jnp.int32)
    tables = jnp.minimum(block_tables, nb - 1).astype(jnp.int32)
    sc = 1.0 / np.sqrt(d)
    # per-head ALiBi slopes become compile-time constants of the static
    # head loop (Bloom; reference blocked_flash takes an alibi operand)
    slopes = (np.asarray(alibi_slopes, np.float32)
              if alibi_slopes is not None else None)

    def kernel(counts_ref, tables_ref, pos0_ref, tlen_ref, q_ref, kn_ref,
               vn_ref, kp_ref, vp_ref, *rest):
        if quant:
            ks_ref, vs_ref, o_ref, m_s, l_s = rest
        else:
            (o_ref, m_s, l_s), ks_ref, vs_ref = rest, None, None
        bi = pl.program_id(0)
        t = pl.program_id(1)
        count = counts_ref[bi]
        p0 = pos0_ref[bi]
        tl = tlen_ref[bi]

        @pl.when(t == 0)
        def _():
            o_ref[:] = jnp.zeros_like(o_ref)
            m_s[:] = jnp.full_like(m_s, -1e30)
            l_s[:] = jnp.zeros_like(l_s)

        def fold(k_ref_, v_ref_, base, limit, ks_=None, vs_=None):
            """Accumulate one kv block whose rows sit at absolute
            positions base+[0, blk); positions >= limit are dead.

            The position mask is head-independent and computed ONCE;
            the running-softmax bookkeeping (max/exp/corr/l) operates
            on the head-stacked [hq*sq, blk] score matrix in one pass —
            only the two MXU contractions stay per-head (their operands
            genuinely differ per head). This cut the per-grid-step VPU
            op count ~6x vs a fully per-head loop (r4 decode-tick
            profiling)."""
            shape2 = (sq, k_ref_.shape[1])
            qpos = p0 + jax.lax.broadcasted_iota(jnp.int32, shape2, 0)
            kpos = base + jax.lax.broadcasted_iota(jnp.int32, shape2, 1)
            live = (kpos <= qpos) & (kpos < limit) \
                & (jax.lax.broadcasted_iota(jnp.int32, shape2, 0) < tl)
            if window is not None:
                live &= qpos - kpos < window
            rel = ((kpos - qpos).astype(jnp.float32)
                   if slopes is not None else None)

            # quantized pools (ISSUE 12): dequantize the K/V tile
            # in-register — one f32 convert + scale multiply per kv
            # head, fused into the same VPU pass as the masks below.
            # `g % hs` folds the per-token granularity (Hs == 1) onto
            # its single scale column at trace time.
            def kload(g):
                tile = k_ref_[0, :, g, :]
                if ks_ is None:
                    return tile
                return tile.astype(jnp.float32) * ks_[0, :, g % hs][:, None]

            def vload(g):
                tile = v_ref_[0, :, g, :]
                if vs_ is None:
                    return tile
                return tile.astype(jnp.float32) * vs_[0, :, g % hs][:, None]
            # rows dead for EVERY q position hold pool garbage; zero
            # them on the v side too — p==0 alone doesn't protect the
            # contraction (0 * NaN = NaN). Computed directly in [blk, 1]
            # orientation (closed form of any(live, axis=0)): Mosaic
            # cannot reshape an i1 vector to add a minor dim. Engines
            # whose pools are zero-initialized pass sanitize_pools=False
            # — garbage is unreachable there and the per-block selects
            # cost real VPU time in the decode hot loop (measured ~1.8x
            # on the 256-ctx tick).
            if sanitize_pools:
                blk = k_ref_.shape[1]
                kcol = base + jax.lax.broadcasted_iota(
                    jnp.int32, (blk, 1), 0)
                any_live = (kcol < limit) & (kcol - p0 < tl)
                if window is not None:
                    any_live &= kcol - p0 + window > 0
                vclean = [jnp.where(any_live, vload(g), 0)
                          for g in range(hq // rep)]     # per kv head
            else:
                vclean = [vload(g) for g in range(hq // rep)]
                # zero-init pools: the cheap additive mask suffices
                # (computed once, head-independent)
                neg = jnp.where(live, 0.0, -1e30)
            kclean = [kload(g) for g in range(hq // rep)]   # per kv head
            parts = []
            for h in range(hq):
                qv = q_ref[0, :, h, :]                      # [sq, d]
                kblk = kclean[h // rep]                     # [blk, d]
                s = jnp.dot(qv, kblk.T,
                            preferred_element_type=jnp.float32) * sc
                if slopes is not None:
                    s = s + float(slopes[h]) * rel
                # sanitize mode: where() (not an additive -1e30) so
                # NaN/Inf in dead KV-pool slots cannot poison the row
                # softmax
                parts.append(jnp.where(live, s, -1e30)
                             if sanitize_pools else s + neg)
            S = jnp.concatenate(parts, axis=0)           # [hq*sq, blk]
            m_prev = m_s[:, :1]
            l_prev = l_s[:, :1]
            m_new = jnp.maximum(
                m_prev, jnp.max(S, axis=-1, keepdims=True))
            p = jnp.exp(S - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_s[:, :1] = l_prev * corr + jnp.sum(
                p, axis=-1, keepdims=True)
            m_s[:, :1] = m_new
            for h in range(hq):
                vblk = vclean[h // rep]
                rows = slice(h * sq, (h + 1) * sq)
                o_ref[0, :, h, :] = (
                    o_ref[0, :, h, :] * corr[rows]
                    + jnp.dot(p[rows].astype(vblk.dtype), vblk,
                              preferred_element_type=jnp.float32))

        page_live = t < count
        if window is not None:
            # pages entirely older than the window contribute nothing —
            # skip their compute (their DMA is also elided: the index
            # map clamps dead slots onto a live page)
            page_live &= (t + 1) * bs > p0 - window

        @pl.when(page_live)
        def _():
            fold(kp_ref, vp_ref, t * bs, p0, ks_ref, vs_ref)

        @pl.when(t == jnp.maximum(count - 1, 0))
        def _():
            fold(kn_ref, vn_ref, p0, p0 + tl)
            for h in range(hq):
                l = jnp.maximum(l_s[pl.ds(h * sq, sq), :1], 1e-30)
                o_ref[0, :, h, :] = o_ref[0, :, h, :] / l

    grid = (b, max_blocks)
    qspec = pl.BlockSpec((1, sq, hq, d),
                         lambda b, t, c, tb, p, tl: (b, 0, 0, 0))
    nspec = pl.BlockSpec((1, sq, hkv, d),
                         lambda b, t, c, tb, p, tl: (b, 0, 0, 0))

    def page_idx(b, t, c, tb, p, tl):
        # clamp dead grid slots (t >= count, or pages older than the
        # window) onto a live page: consecutive identical block indices
        # let Pallas elide the DMA, so short sequences don't pay
        # full-table page traffic every tick
        hi = jnp.maximum(c[b] - 1, 0)
        lo = (jnp.maximum((p[b] - window) // bs, 0)
              if window is not None else 0)
        return (tb[b, jnp.clip(t, lo, hi)], 0, 0, 0)

    pspec = pl.BlockSpec((1, bs, hkv, d), page_idx)
    in_specs = [qspec, nspec, nspec, pspec, pspec]
    operands = [q, k_new, v_new, k_pool, v_pool]
    if quant:
        # scale tiles ride the same clamped block-table index map as
        # their payload pages (dead slots share the DMA elision)
        def scale_idx(b, t, c, tb, p, tl):
            return page_idx(b, t, c, tb, p, tl)[:3]

        sspec = pl.BlockSpec((1, bs, hs), scale_idx)
        in_specs += [sspec, sspec]
        operands += [k_scale, v_scale]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=in_specs,
            out_specs=qspec,
            scratch_shapes=[pltpu.VMEM((hq * sq, 128), jnp.float32),
                            pltpu.VMEM((hq * sq, 128), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, sq, hq, d), jnp.float32),
        # all-heads blocks: at 32 heads x 128 a 256-token prefill chunk
        # needs ~35MB of scoped VMEM (q 2M + f32 out 4M double-buffered,
        # 8M of m/l scratch, the [hq*sq, blk] score slab) against
        # Mosaic's 16MB default — same ceiling as ops/pallas/flash_attention
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=jax.default_backend() != "tpu",
        name="ds_paged_attention",
    )(counts, tables, jnp.asarray(pos0, jnp.int32),
      jnp.asarray(true_len, jnp.int32), *operands)
    return out.astype(q.dtype)


def paged_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    pos0: jax.Array,
                    window: int | None = None,
                    alibi_slopes: jax.Array | None = None):
    """q: [B, S_new, H, D]; k/v: gathered pages [B, smax, H_kv, D]
    (already containing this chunk's fresh k/v); pos0 [B] tokens cached
    before this chunk. Causal over absolute positions; ``window``
    restricts lookback (Mistral SWA); ``alibi_slopes`` [H] adds Bloom's
    per-head linear position bias. (reference: blocked_flash)"""
    b, sq, hq, d = q.shape
    smax = k.shape[1]
    hkv = k.shape[2]
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / np.sqrt(d)
    qpos = pos0[:, None] + jnp.arange(sq)[None, :]            # [B, S]
    kpos = jnp.arange(smax)[None, :]
    mask = kpos[:, None, :] <= qpos[:, :, None]               # [B, S, smax]
    if window is not None:
        mask &= kpos[:, None, :] > qpos[:, :, None] - window
    if alibi_slopes is not None:
        rel = (kpos[:, None, :] - qpos[:, :, None]).astype(jnp.float32)
        logits = logits + (alibi_slopes[None, :, None, None]
                           * rel[:, None])
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def paged_forward(model, params: PyTree, pools: PyTree, tokens: jax.Array,
                  pos0: jax.Array, block_tables: jax.Array,
                  true_len: jax.Array, use_kernel: bool = True,
                  all_logits: bool = False):
    """Full model pass over a (padded) chunk of new tokens with paged KV.

    tokens [B, S]; pos0 [B]; block_tables [B, max_blocks]; true_len [B]
    actual new-token counts (padding beyond is masked). Returns
    (last-valid-token logits [B, V], new_pools) — the vocab projection
    runs only on each sequence's last pending token (the reference's
    logits_gather kernel, fused into the step so continuous-batching
    decode is one dispatch).

    ``all_logits=True`` projects EVERY chunk position instead
    (returns [B, S, V]) — the speculative verify step needs the
    next-token distribution after each draft slot, not just the last
    one. Attention math is unchanged; rows at slots >= ``true_len``
    carry garbage logits the caller must mask (the accept/reject logic
    only ever reads slots < true_len).

    **Quantized KV pools (ISSUE 12):** when ``pools`` carries scale
    slabs (``"ks"``/``"vs"``, [L, nb, bs, Hs] f32 — present iff the
    engine's ``kv_cache`` block is enabled), the payload pools hold
    int8/fp8 codes. Reads dequantize in the consumer (in-register
    inside the Pallas kernel's fold; a fused multiply on the gathered
    view in the jnp reference path) and the bulk scatter below
    quantizes each fresh (token, head) vector ONCE — write-once
    per-vector scales, so a block's stored bytes are a deterministic
    function of the tokens written through it (the prefix cache shares
    quantized blocks bit-stably) and no read-modify-requantize ever
    touches earlier tokens. The scale slabs live INSIDE the pools
    PyTree, so every fused loop's ``lax.while_loop`` carry threads
    them exactly as it threads the payload pools — all serving modes
    (per-tick, chained, ring, speculative) run quantized unchanged.
    A token's own chunk attends to its exact (unquantized) k/v — the
    patched view / fresh-chunk fold; later chunks read the quantized
    pool. The quantization noise model is in docs/serving.md.
    """
    b, s = tokens.shape
    positions = pos0[:, None] + jnp.arange(s)[None, :]
    x = model.embed(params, tokens, positions=positions)
    quant = "ks" in pools
    if quant:
        from ...ops.pallas.quantization import kv_quantize
        kv_dtype = ("int8" if pools["k"].dtype == jnp.int8 else "fp8")

    # The pool slabs enter the scan only as read-only xs (per-layer
    # slices): each layer gathers its pages, patches this chunk's fresh
    # k/v into the gathered view for attention, and emits the small
    # [B, S, H, D] chunk as a scan output; one bulk scatter after the
    # scan writes all layers. Routing the slabs through the ys stream
    # would copy the whole pool through HBM every step.
    alibi = getattr(model, "_alibi_slopes", None)

    def body(x, xs):
        if quant:
            p, k_pool, v_pool, k_scale, v_scale = xs
        else:
            (p, k_pool, v_pool), k_scale, v_scale = xs, None, None
        p = model._maybe_dequant(p, x.dtype)
        h = model._norm(x, p["ln1_scale"], p.get("ln1_bias"))
        q, k, v = model._qkv(p, h, positions)
        bs_ = k_pool.shape[1]
        if use_kernel and q.shape[-1] % 8 == 0 and bs_ % 8 == 0:
            # blocked-flash kernel: reads pages via the block table, no
            # gathered [B, smax, H, D] materialization; ALiBi rides as
            # static per-head slopes; quantized pools dequantize
            # in-register inside the fold (scales ride the same table)
            a = paged_attention_kernel(
                q, k, v, k_pool, v_pool, block_tables, pos0, true_len,
                window=model.config.sliding_window, alibi_slopes=alibi,
                # the engine's pools are zero-initialized (engine_v2
                # __init__), so dead-slot garbage is unreachable and the
                # sanitize selects would tax the decode hot loop
                sanitize_pools=False,
                k_scale=k_scale, v_scale=v_scale)
        else:
            k_pages = gather_pages(k_pool, block_tables)
            v_pages = gather_pages(v_pool, block_tables)
            if quant:
                # jnp reference path: dequantize the gathered view (XLA
                # fuses the multiply into the attention consumer); the
                # fresh chunk is patched in exact afterwards, matching
                # the kernel's unquantized fresh-fold
                ks = gather_scales(k_scale, block_tables)
                vs = gather_scales(v_scale, block_tables)
                k_pages = (k_pages.astype(jnp.float32)
                           * ks[..., :, None]).astype(k.dtype)
                v_pages = (v_pages.astype(jnp.float32)
                           * vs[..., :, None]).astype(v.dtype)
            k_pages = place_in_pages(k_pages, k, pos0, true_len)
            v_pages = place_in_pages(v_pages, v, pos0, true_len)
            a = paged_attention(q, k_pages, v_pages, pos0,
                                window=model.config.sliding_window,
                                alibi_slopes=alibi)
        if model.config.parallel_residual:
            m, _ = model._mlp(p, model._parallel_mlp_input(p, x, h))
            return x + model._attn_out(p, a) + m, (k, v)
        x = x + model._attn_out(p, a)
        x, _ = model._mlp_residual(p, x)
        return x, (k, v)

    xs = (params["layers"], pools["k"], pools["v"])
    if quant:
        xs = xs + (pools["ks"], pools["vs"])
    x, (new_k, new_v) = jax.lax.scan(body, x, xs)

    # bulk scatter: all layers' chunk k/v into the pools in one update
    nb, bs = pools["k"].shape[1], pools["k"].shape[2]
    blk = jnp.take_along_axis(block_tables, positions // bs, axis=1)
    off = positions % bs
    valid = jnp.arange(s)[None, :] < true_len[:, None]
    blk = jnp.where(valid, blk, nb)                     # OOB -> dropped
    if quant:
        # quantize-on-write: each fresh (token, head) vector gets its
        # own symmetric scale, scattered into the scale pool in the
        # SAME graph (per-vector write-once — see the docstring)
        hs = pools["ks"].shape[-1]
        qk, sk = kv_quantize(new_k, kv_dtype, hs)      # [L,B,S,H(s)]
        qv, sv = kv_quantize(new_v, kv_dtype, hs)
        from ...ops.pallas.quantization import KV_QMAX, saturation_probe
        # numsan probe on the k codes (k and v share scale granularity;
        # one fused reduction keeps the armed-probe cost at one pass)
        saturation_probe("kv_write", qk, qmax=KV_QMAX[kv_dtype])
        new_pools = {
            "k": pools["k"].at[:, blk, off].set(qk, mode="drop"),
            "v": pools["v"].at[:, blk, off].set(qv, mode="drop"),
            "ks": pools["ks"].at[:, blk, off].set(sk, mode="drop"),
            "vs": pools["vs"].at[:, blk, off].set(sv, mode="drop"),
        }
    else:
        new_pools = {
            "k": pools["k"].at[:, blk, off].set(
                new_k.astype(pools["k"].dtype), mode="drop"),
            "v": pools["v"].at[:, blk, off].set(
                new_v.astype(pools["v"].dtype), mode="drop"),
        }
    if all_logits:
        # speculative verify: every slot's next-token distribution
        return model.unembed(params, x), new_pools
    # logits_gather: project only each row's last valid position
    idx = jnp.clip(true_len - 1, 0, s - 1)
    x_last = x[jnp.arange(b), idx]                      # [B, D]
    logits = model.unembed(params, x_last[:, None, :])[:, 0]
    return logits, new_pools


def fused_decode_loop(model, params: PyTree, pools: PyTree,
                      tokens: jax.Array, pos: jax.Array,
                      block_tables: jax.Array, active: jax.Array,
                      remaining: jax.Array, row_keys: jax.Array, *,
                      num_steps: int, eos_id: int | None,
                      temperature: float, top_k: int, top_p: float,
                      use_kernel: bool = True):
    """Up to ``num_steps`` decode ticks in ONE compiled program: forward
    -> in-graph sampling -> feed the sampled token back as the next
    step's input, with KV writes, EOS/budget termination masks and the
    output ring buffer all on device (the kernel-resident analogue of
    the reference FastGen's ragged decode loop — no host in the loop).

    Per-sequence state rides the ``lax.while_loop`` carry:

    - ``tokens`` [B] int32 — each row's last sampled token, committed to
      the history but NOT yet in the KV cache (iteration j writes it at
      position ``pos`` and samples its successor).
    - ``pos`` [B] int32 — tokens already cached (= the write position).
    - ``active`` [B] bool — rows that still decode. A row goes inactive
      in-graph when it samples ``eos_id`` or exhausts ``remaining``;
      inactive rows stop writing KV (true_len 0) and stop emitting, so
      sequences finish mid-loop without a host check.
    - ``remaining`` [B] int32 — how many more tokens the row may emit.
    - ``row_keys`` [B, 2] — per-row PRNG keys; each step folds in the
      sampled token's absolute position (ops/sampling.position_keys),
      so stochastic decode is invariant to how steps group into
      dispatches.

    ``block_tables`` must already cover every position the loop can
    write (``pos + num_steps``) — the host preallocates blocks
    (``DSStateManager.reserve``) so the table is static across the
    fused dispatch while the per-token block/offset arithmetic happens
    in-graph. The loop exits early once every row is inactive.

    ``pools`` may carry quantized payload + scale slabs (ISSUE 12;
    see :func:`paged_forward`) — the whole dict rides the carry, so
    the scale pools thread through every chained dispatch exactly as
    the payload pools do. This holds for all the fused loops below
    (serve ring, spec, spec-serve) for the same structural reason.

    Host-free contract (enforced, not just documented): a dispatch of
    this loop performs NO host<->device transfer — operands arrive as
    committed device arrays, the carry never leaves the device, and
    the ring buffer is drained by one explicit pull. The engine's
    sentinel mode (``RaggedInferenceEngineConfig.sentinels``) runs
    every dispatch under ``jax.transfer_guard("disallow")`` plus a
    recompile watch, so a future edit that sneaks a host value into
    the loop (or drifts a shape) fails loudly instead of silently
    serializing decode. See docs/static-analysis.md.

    Returns ``(out_tokens [B, num_steps] (-1 beyond each row's emits),
    steps_run [], tokens, pos, active, remaining, pools)`` — the carry
    comes back so the host (or a chained dispatch) can continue without
    reading anything but the ring buffer.
    """
    from ...ops import sampling

    b = tokens.shape[0]
    out0 = jnp.full((b, num_steps), -1, jnp.int32)
    eos = -1 if eos_id is None else int(eos_id)

    def cond(st):
        step, _, _, active = st[0], st[1], st[2], st[3]
        return (step < num_steps) & jnp.any(active)

    def body(st):
        step, tokens, pos, active, remaining, pools, out = st
        tl = active.astype(jnp.int32)   # inactive rows write nothing
        logits, pools = paged_forward(
            model, params, pools, tokens[:, None], pos, block_tables,
            tl, use_kernel=use_kernel)
        # the sampled token's absolute index is pos + 1 (its input sits
        # at pos); keying on it makes sampling dispatch-schedule-free
        keys = sampling.position_keys(row_keys, pos + 1)
        nxt = sampling.sample_tokens_batched(
            logits, keys, temperature=temperature, top_k=top_k,
            top_p=top_p)
        out = out.at[:, step].set(jnp.where(active, nxt, -1))
        pos = pos + tl
        remaining = remaining - tl
        alive = active & (remaining > 0) & (nxt != eos)
        tokens = jnp.where(active, nxt, tokens)
        return step + 1, tokens, pos, alive, remaining, pools, out

    step, tokens, pos, active, remaining, pools, out = jax.lax.while_loop(
        cond, body, (jnp.asarray(0, jnp.int32), tokens, pos, active,
                     remaining, pools, out0))
    return out, step, tokens, pos, active, remaining, pools


def draft_prompt_lookup(hist: jax.Array, *, min_ngram: int,
                        draft_len: int):
    """Prompt-lookup (self-speculative n-gram) drafter, fully on device.

    ``hist`` [B, H] int32 is each row's recent committed token history
    — RIGHT-aligned (newest token, the pending decode input, at column
    H-1) with ``-1`` filling unused columns on the left. The drafter
    takes the trailing ``min_ngram`` tokens, finds the MOST RECENT
    earlier occurrence of that n-gram in the window, and proposes the
    up-to-``draft_len`` tokens that followed it (PLD / "assisted
    decoding without a draft model"; the history is seeded host-side
    from the sequence's full token record — prefix-cache-shared prompt
    blocks included — and maintained in-graph by the spec loops).

    Returns ``(draft [B, draft_len] int32, eff [B] int32)`` — ``eff``
    is how many proposed tokens are real; 0 when no n-gram fires (the
    depth-0 fallback: the verify step then degenerates to plain
    single-token decode). Real tokens are >= 0, so the ``-1`` fill can
    never match a genuine n-gram.
    """
    b, h = hist.shape
    n, el = int(min_ngram), int(draft_len)
    s = h - n                               # candidate window starts
    tail = hist[:, h - n:]                                   # [B, n]
    widx = jnp.arange(s)[:, None] + jnp.arange(n)[None, :]   # [S, n]
    win = hist[:, widx]                                      # [B, S, n]
    match = jnp.all(win == tail[:, None, :], axis=-1) \
        & jnp.all(win >= 0, axis=-1)                         # [B, S]
    # latest match wins (recency bias, the standard PLD heuristic) —
    # but a match so close to the window edge that fewer than
    # ``draft_len`` tokens follow it is outranked by the latest match
    # with a FULL continuation (a period-1 repetition would otherwise
    # always pick the adjacent match and draft a single token). Start
    # s == h-n (the tail itself) is excluded by construction.
    starts = jnp.arange(s)[None, :]
    best_full = jnp.max(
        jnp.where(match & (starts <= s - 1 - (el - 1)), starts, -1),
        axis=-1)
    best_any = jnp.max(jnp.where(match, starts, -1), axis=-1)
    best = jnp.where(best_full >= 0, best_full, best_any)    # [B]
    hit = (best >= 0) & jnp.all(tail >= 0, axis=-1)
    cont = jnp.maximum(best, 0) + n          # first continuation column
    avail = jnp.minimum(el, h - cont)        # tokens following the match
    didx = jnp.clip(cont[:, None] + jnp.arange(el)[None, :], 0, h - 1)
    draft = jnp.take_along_axis(hist, didx, axis=1)          # [B, el]
    eff = jnp.where(hit, avail, 0).astype(jnp.int32)
    return draft.astype(jnp.int32), eff


def append_history(hist: jax.Array, emitted: jax.Array,
                   m: jax.Array) -> jax.Array:
    """Shift each row of the right-aligned history window left by
    ``m[b]`` and append the first ``m[b]`` columns of ``emitted``
    [B, E] at the right edge — a gather over the concatenation, so the
    traced per-row advance needs no scatter. Rows with ``m == 0`` come
    back unchanged."""
    b, h = hist.shape
    comb = jnp.concatenate([hist, emitted.astype(hist.dtype)], axis=1)
    gidx = jnp.arange(h)[None, :] + m[:, None]               # [B, H]
    return jnp.take_along_axis(comb, gidx, axis=1)


def _spec_tick(model, params, pools, tokens, pos, tables, active,
               remaining, row_keys, *, draft_len, min_ngram, eos,
               temperature, top_k, top_p, use_kernel, hist):
    """One speculative verify tick shared by the spec decode/serve
    loops: draft -> one [B, 1+draft_len] forward -> position-keyed
    sample at every slot -> leading exact-match accept -> commit
    1..1+draft_len tokens per row.

    The sampled targets are the SAME tokens a plain per-position decode
    would produce (greedy: argmax; stochastic: the position-keyed
    categorical draw), so acceptance only decides how many land per
    forward — the emitted chain is bit-identical to spec-off in both
    regimes, and invariant to how ticks group into dispatches.

    Returns ``(target [B, 1+L], m [B] emitted counts, tokens', pos',
    alive, remaining', hist', stats [3] = (proposed, accepted,
    hit_slots), pools')``. KV for draft slots is written through the
    block table like any prefill chunk; slots past the accepted run
    hold stale values that the next tick's fresh chunk overwrites
    before any query can attend to them (queries never look past their
    own position), and the block budget already covers them because
    drafts are clamped to ``remaining - 1``.
    """
    from ...ops import sampling

    el = int(draft_len)
    slots = jnp.arange(1 + el)
    draft, eff = draft_prompt_lookup(hist, min_ngram=min_ngram,
                                     draft_len=el)
    # drafting past the budget is pure waste (acceptance commits at
    # most `remaining` tokens) AND would write KV beyond the reserved
    # block horizon — clamp to remaining-1
    eff = jnp.minimum(eff, jnp.maximum(remaining - 1, 0))
    eff = jnp.where(active, eff, 0)
    inputs = jnp.concatenate([tokens[:, None], draft], axis=1)
    tl = jnp.where(active, 1 + eff, 0)
    logits, pools = paged_forward(model, params, pools, inputs, pos,
                                  tables, tl, use_kernel=use_kernel,
                                  all_logits=True)     # [B, 1+L, V]
    # slot j samples the token at absolute index pos+1+j — the same
    # key the non-spec loop folds for that position, so accept/reject
    # is schedule-invariant and greedy verify is exact-match
    positions = pos[:, None] + 1 + slots[None, :]
    keys = jax.vmap(sampling.position_keys)(row_keys, positions)
    target = sampling.sample_token_grid(
        logits, keys, temperature=temperature, top_k=top_k, top_p=top_p)
    ok = (draft == target[:, :el]) & (slots[None, :el] < eff[:, None])
    acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
    m = jnp.minimum(acc + 1, remaining)      # accepted run + correction
    # EOS truncation: emit up to and including the first eos
    is_eos = (target == eos) & (slots[None, :] < m[:, None])
    any_eos = jnp.any(is_eos, axis=1)
    first_eos = jnp.argmax(is_eos, axis=1)
    m = jnp.where(any_eos, first_eos + 1, m)
    m = jnp.where(active, m, 0)
    last = jnp.take_along_axis(target, jnp.maximum(m - 1, 0)[:, None],
                               axis=1)[:, 0]
    tokens = jnp.where(active, last, tokens)
    pos = pos + m
    remaining = remaining - m
    alive = active & (remaining > 0) & ~any_eos
    hist = append_history(hist, target, m)
    # drafts actually committed: the leading `acc` matches, except that
    # an EOS-truncated emission may end ON an accepted draft (the
    # drafted eos matched) — then every committed token was a draft and
    # `m - 1` would undercount by one
    used = jnp.minimum(acc, m)
    stats = jnp.stack([jnp.sum(eff), jnp.sum(used),
                       jnp.sum((eff > 0).astype(jnp.int32)),
                       jnp.sum(active.astype(jnp.int32))])
    return target, m, tokens, pos, alive, remaining, hist, stats, pools


def fused_spec_decode_loop(model, params: PyTree, pools: PyTree,
                           tokens: jax.Array, pos: jax.Array,
                           block_tables: jax.Array, active: jax.Array,
                           remaining: jax.Array, row_keys: jax.Array,
                           hist: jax.Array, *, num_steps: int,
                           draft_len: int, min_ngram: int,
                           eos_id: int | None, temperature: float,
                           top_k: int, top_p: float,
                           use_kernel: bool = True):
    """:func:`fused_decode_loop` with speculative decoding (ISSUE 9):
    each tick drafts up to ``draft_len`` tokens by prompt lookup over
    the row's device-side history window, verifies them in ONE forward
    over ``[B, 1 + draft_len]`` positions, and commits
    ``1..1+draft_len`` tokens — so a K-step dispatch can emit up to
    ``K * (1 + draft_len)`` tokens per row while paying K forwards.

    Extra carry vs the plain loop: ``hist`` [B, H] (right-aligned
    recent-token window, maintained in-graph; see
    :func:`draft_prompt_lookup`) and the per-row output write pointer
    — rows advance VARIABLE amounts per tick, so the output buffer
    ``out`` [B, num_steps * (1 + draft_len)] is scattered through
    per-row pointers instead of a shared step column.

    Returns ``(out, out_ptr [B], steps_run, tokens, pos, active,
    remaining, hist, spec_stats [4] = (proposed, accepted, hit_slots,
    live_slots), pools)``. Greedy output is bit-identical to the non-spec loop
    (targets ARE the argmax chain; drafts only batch them), stochastic
    output is bit-identical for the same base keys (position-keyed
    draws)."""
    b = tokens.shape[0]
    el = int(draft_len)
    width = num_steps * (1 + el)
    out0 = jnp.full((b, width), -1, jnp.int32)
    eos = -1 if eos_id is None else int(eos_id)
    slots = jnp.arange(1 + el)

    def cond(st):
        step, active = st[0], st[3]
        return (step < num_steps) & jnp.any(active)

    def body(st):
        (step, tokens, pos, active, remaining, hist, out, out_ptr,
         stats, pools) = st
        (target, m, tokens, pos, alive, remaining, hist, tick_stats,
         pools) = _spec_tick(
            model, params, pools, tokens, pos, block_tables, active,
            remaining, row_keys, draft_len=el, min_ngram=min_ngram,
            eos=eos, temperature=temperature, top_k=top_k, top_p=top_p,
            use_kernel=use_kernel, hist=hist)
        cols = jnp.where(slots[None, :] < m[:, None],
                         out_ptr[:, None] + slots[None, :], width)
        out = out.at[jnp.arange(b)[:, None], cols].set(
            target, mode="drop")
        return (step + 1, tokens, pos, alive, remaining, hist, out,
                out_ptr + m, stats + tick_stats, pools)

    (step, tokens, pos, active, remaining, hist, out, out_ptr, stats,
     pools) = jax.lax.while_loop(
        cond, body,
        (jnp.asarray(0, jnp.int32), tokens, pos, active, remaining,
         hist, out0, jnp.zeros((b,), jnp.int32),
         jnp.zeros((4,), jnp.int32), pools))
    return (out, out_ptr, step, tokens, pos, active, remaining, hist,
            stats, pools)


def fused_spec_serve_loop(model, params: PyTree, pools: PyTree,
                          tokens: jax.Array, pos: jax.Array,
                          block_tables: jax.Array, active: jax.Array,
                          remaining: jax.Array, row_keys: jax.Array,
                          hist: jax.Array, epoch: jax.Array,
                          stage_tokens: jax.Array, stage_pos: jax.Array,
                          stage_rem: jax.Array, stage_keys: jax.Array,
                          stage_tables: jax.Array,
                          stage_hist: jax.Array, stage_valid: jax.Array,
                          ring: jax.Array, ring_epochs: jax.Array,
                          ring_ptr: jax.Array, spec_stats: jax.Array, *,
                          num_steps: int, draft_len: int, min_ngram: int,
                          eos_id: int | None, temperature: float,
                          top_k: int, top_p: float,
                          use_kernel: bool = True):
    """:func:`fused_serve_loop` (ring mode, in-graph admission) with
    speculative decoding. Differences from the non-spec ring loop:

    - ``ring_ptr`` is PER-ROW [B] — rows commit 1..1+draft_len tokens
      per tick, so each row owns its own ring watermark; the host
      drains ``ring[b, :ring_ptr[b]]`` once per chain.
    - ``hist`` [B, H] rides the carry and is REPLACED by
      ``stage_hist`` on an in-graph slot swap (the staged request's
      own token history, built host-side at staging).
    - ``spec_stats`` [4] (proposed, accepted, hit_slots, live_slots)
      accumulates
      across the whole chain and is read once at the drain.

    Returns ``(ring, ring_epochs, ring_ptr [B], steps_run, tokens,
    pos, active, remaining, row_keys, block_tables, hist, epoch,
    stage_valid, spec_stats, pools)``."""
    b = tokens.shape[0]
    el = int(draft_len)
    eos = -1 if eos_id is None else int(eos_id)
    slots = jnp.arange(1 + el)
    cap = ring.shape[1]

    def cond(st):
        step, active = st[0], st[3]
        return (step < num_steps) & jnp.any(active)

    def body(st):
        (step, tokens, pos, active, remaining, row_keys, tables, hist,
         epoch, s_valid, ring, ring_ep, ring_ptr, stats, pools) = st
        (target, m, tokens, pos, alive, remaining, hist, tick_stats,
         pools) = _spec_tick(
            model, params, pools, tokens, pos, tables, active,
            remaining, row_keys, draft_len=el, min_ngram=min_ngram,
            eos=eos, temperature=temperature, top_k=top_k, top_p=top_p,
            use_kernel=use_kernel, hist=hist)
        cols = jnp.where(slots[None, :] < m[:, None],
                         ring_ptr[:, None] + slots[None, :], cap)
        rows = jnp.arange(b)[:, None]
        ring = ring.at[rows, cols].set(target, mode="drop")
        ring_ep = ring_ep.at[rows, cols].set(
            jnp.broadcast_to(epoch[:, None], (b, 1 + el)), mode="drop")
        ring_ptr = ring_ptr + m
        # in-graph admission: a row whose occupant just terminated and
        # that carries a staged request swaps it in for the NEXT tick
        swap = active & ~alive & s_valid
        tokens = jnp.where(swap, stage_tokens, tokens)
        pos = jnp.where(swap, stage_pos, pos)
        remaining = jnp.where(swap, stage_rem, remaining)
        row_keys = jnp.where(swap[:, None], stage_keys, row_keys)
        tables = jnp.where(swap[:, None], stage_tables, tables)
        hist = jnp.where(swap[:, None], stage_hist, hist)
        epoch = epoch + swap.astype(jnp.int32)
        alive = alive | swap
        s_valid = s_valid & ~swap
        return (step + 1, tokens, pos, alive, remaining, row_keys,
                tables, hist, epoch, s_valid, ring, ring_ep, ring_ptr,
                stats + tick_stats, pools)

    (step, tokens, pos, active, remaining, row_keys, tables, hist,
     epoch, stage_valid, ring, ring_epochs, ring_ptr, spec_stats,
     pools) = jax.lax.while_loop(
        cond, body,
        (jnp.asarray(0, jnp.int32), tokens, pos, active, remaining,
         row_keys, block_tables, hist, epoch, stage_valid, ring,
         ring_epochs, ring_ptr, spec_stats, pools))
    return (ring, ring_epochs, ring_ptr, step, tokens, pos, active,
            remaining, row_keys, tables, hist, epoch, stage_valid,
            spec_stats, pools)


def fused_serve_loop(model, params: PyTree, pools: PyTree,
                     tokens: jax.Array, pos: jax.Array,
                     block_tables: jax.Array, active: jax.Array,
                     remaining: jax.Array, row_keys: jax.Array,
                     epoch: jax.Array, stage_tokens: jax.Array,
                     stage_pos: jax.Array, stage_rem: jax.Array,
                     stage_keys: jax.Array, stage_tables: jax.Array,
                     stage_valid: jax.Array, ring: jax.Array,
                     ring_epochs: jax.Array, ring_ptr: jax.Array, *,
                     num_steps: int, eos_id: int | None,
                     temperature: float, top_k: int, top_p: float,
                     use_kernel: bool = True):
    """:func:`fused_decode_loop` extended for device-resident multi-tick
    serving (ISSUE 6): in-graph admission of PRE-STAGED requests and a
    device-side output ring the host drains once per dispatch CHAIN,
    not once per dispatch.

    Two additions ride the ``lax.while_loop`` carry:

    - **staged-slot swap** (in-graph admission): each row may carry ONE
      pre-staged request — a prompt the host already prefilled and
      reserved blocks for (``stage_tokens``/``stage_pos``/``stage_rem``
      its pending input, position and budget; ``stage_keys`` its
      sampling key row; ``stage_tables`` its block-table row;
      ``stage_valid`` whether a stage is attached). The instant a row's
      current occupant terminates (EOS or budget), the staged request
      is swapped in by an activity-mask swap — token/position/budget/
      key/table row all replaced in-graph — so a finished slot refills
      INSIDE the compiled loop instead of forcing a host-side operand
      rebuild. ``epoch`` [B] counts swaps per row, letting the host
      attribute ring tokens to the right occupant after the fact.
      ``block_tables`` and ``row_keys`` join the carry to make the swap
      possible (they are loop-invariant in :func:`fused_decode_loop`).

    - **output ring**: sampled tokens land in ``ring`` [B, cap] at
      column ``ring_ptr + step`` with the emitting occupant's epoch in
      ``ring_epochs``; the updated ring and pointer come back as device
      arrays, so a chain of dispatches accumulates into one buffer and
      the host performs ONE device->host read per chain. ``cap`` must
      cover the whole chain (``chain_len * num_steps <= cap`` —
      enforced by the host driver).

    Returns ``(ring, ring_epochs, ring_ptr', tokens, pos, active,
    remaining, row_keys, block_tables, epoch, stage_valid, pools)`` —
    everything a chained dispatch needs arrives as committed device
    arrays; the stage operands are loop-invariant within a chain and
    are re-passed by the host.
    """
    from ...ops import sampling

    eos = -1 if eos_id is None else int(eos_id)

    def cond(st):
        step, active = st[0], st[3]
        return (step < num_steps) & jnp.any(active)

    def body(st):
        (step, tokens, pos, active, remaining, row_keys, tables, epoch,
         s_valid, ring, ring_ep, ring_ptr, pools) = st
        tl = active.astype(jnp.int32)   # inactive rows write nothing
        logits, pools = paged_forward(
            model, params, pools, tokens[:, None], pos, tables,
            tl, use_kernel=use_kernel)
        keys = sampling.position_keys(row_keys, pos + 1)
        nxt = sampling.sample_tokens_batched(
            logits, keys, temperature=temperature, top_k=top_k,
            top_p=top_p)
        col = ring_ptr + step
        ring = ring.at[:, col].set(jnp.where(active, nxt, -1))
        ring_ep = ring_ep.at[:, col].set(jnp.where(active, epoch, -1))
        pos = pos + tl
        remaining = remaining - tl
        alive = active & (remaining > 0) & (nxt != eos)
        tokens = jnp.where(active, nxt, tokens)
        # in-graph admission: a row whose occupant just terminated and
        # that carries a staged request swaps it in for the NEXT step
        swap = active & ~alive & s_valid
        tokens = jnp.where(swap, stage_tokens, tokens)
        pos = jnp.where(swap, stage_pos, pos)
        remaining = jnp.where(swap, stage_rem, remaining)
        row_keys = jnp.where(swap[:, None], stage_keys, row_keys)
        tables = jnp.where(swap[:, None], stage_tables, tables)
        epoch = epoch + swap.astype(jnp.int32)
        alive = alive | swap
        s_valid = s_valid & ~swap
        return (step + 1, tokens, pos, alive, remaining, row_keys,
                tables, epoch, s_valid, ring, ring_ep, ring_ptr, pools)

    (step, tokens, pos, active, remaining, row_keys, tables, epoch,
     stage_valid, ring, ring_epochs, ring_ptr, pools) = jax.lax.while_loop(
        cond, body, (jnp.asarray(0, jnp.int32), tokens, pos, active,
                     remaining, row_keys, block_tables, epoch,
                     stage_valid, ring, ring_epochs, ring_ptr, pools))
    return (ring, ring_epochs, ring_ptr + step, tokens, pos, active,
            remaining, row_keys, tables, epoch, stage_valid, pools)

"""Block-wise quantization kernels (reference: csrc/quantization/*.cu).

Symmetric int8 block quantization with per-block scales — the primitive
behind ZeRO++'s quantized weight all-gather (qwZ) and quantized gradient
reduce-scatter (qgZ) (reference: partition_parameters.py:761 CUDAQuantizer,
runtime/comm/coalesced_collectives.py:31 all_to_all_quant_reduce). On TPU
the quantize/dequantize pair brackets a collective to halve/quarter the
bytes on the wire; XLA fuses the jnp fallback, the Pallas kernels pin the
single-HBM-pass behavior.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _interpret

QBLOCK = 512  # elements per quantization block (lane-dim groups of 128)


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[:].astype(jnp.float32)           # [rows, QBLOCK]
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[:] = q.astype(jnp.int8)
    s_ref[:] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[:] = q_ref[:].astype(jnp.float32) * s_ref[:]


def _to_blocks(x):
    n = x.size
    pad = (-n) % QBLOCK
    flat = jnp.pad(x.reshape(-1), (0, pad))
    return flat.reshape(-1, QBLOCK), n


def stochastic_round(y, key):
    """Unbiased round-to-integer: ``floor(y + u)``, u ~ U[0, 1).
    E[result] = y, so quantization noise averages out across steps —
    the accuracy knob ZeRO++/EQuARX lean on for the gradient wire
    (nearest rounding biases each block toward its own grid)."""
    u = jax.random.uniform(key, y.shape, jnp.float32)
    return jnp.floor(y + u)


def quantize_int8(x, use_pallas: bool | None = None,
                  rounding: str = "nearest", key=None):
    """-> (q int8 [nblocks, QBLOCK], scales f32 [nblocks, 1], meta).

    ``rounding="stochastic"`` (requires ``key``) uses unbiased
    floor-plus-uniform rounding on the jnp path — the gradient-wire
    mode; the Pallas kernel keeps nearest rounding (weight gathers,
    where the bias is squashed by the optimizer update anyway)."""
    blocks, n = _to_blocks(x)
    rows = blocks.shape[0]
    if rounding == "stochastic":
        if key is None:
            raise ValueError("stochastic rounding needs a PRNG key")
        use_pallas = False
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        blk = min(256, rows)
        spec = pl.BlockSpec((blk, QBLOCK), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
        sspec = pl.BlockSpec((blk, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
        q, s = pl.pallas_call(
            _quant_kernel,
            grid=(-(-rows // blk),),
            in_specs=[spec],
            out_specs=[spec, sspec],
            out_shape=[jax.ShapeDtypeStruct(blocks.shape, jnp.int8),
                       jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
            interpret=_interpret(),
        )(blocks)
    else:
        x32 = blocks.astype(jnp.float32)
        amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
        s = jnp.maximum(amax / 127.0, 1e-12)
        y = x32 / s
        rounded = (stochastic_round(y, key) if rounding == "stochastic"
                   else jnp.round(y))
        q = jnp.clip(rounded, -127, 127).astype(jnp.int8)
    return q, s, (x.shape, x.dtype, n)


def dequantize_int8(q, s, meta, use_pallas: bool | None = None):
    shape, dtype, n = meta
    rows = q.shape[0]
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        blk = min(256, rows)
        spec = pl.BlockSpec((blk, QBLOCK), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
        sspec = pl.BlockSpec((blk, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
        x = pl.pallas_call(
            _dequant_kernel,
            grid=(-(-rows // blk),),
            in_specs=[spec, sspec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
            interpret=_interpret(),
        )(q, s)
    else:
        x = q.astype(jnp.float32) * s
    return x.reshape(-1)[:n].reshape(shape).astype(dtype)


# ------------------------------------------------------------------
# KV-pool quantization (ISSUE 12): symmetric per-vector quant/dequant
# for the paged KV cache. Unlike the wire quantizers above (flat
# QBLOCK groups bracketing a collective), the KV pool is quantized
# WRITE-ONCE per token vector — each written (position, kv-head)
# vector of head_dim elements gets its own scale (granularity "head"),
# or one scale spans the whole token across heads (granularity
# "token"). Per-vector scales are what make incremental pool writes
# sound: a block fills one token at a time across many dispatches, and
# a shared per-block scale would need a read-modify-requantize of
# every earlier token whenever a later one raised the block absmax —
# destroying the write-once determinism the prefix cache shares blocks
# under. Quantization blocks therefore never straddle tokens (the PR 8
# boundary-straddle lesson applied to pools), and a cached block's
# bytes are a pure function of the tokens written through it.
#
# Dequantization is plain jnp (``codes.astype(f32) * scale``) so XLA
# fuses it into the consumer; the paged-decode attention kernel
# (inference/v2/paged.paged_attention_kernel) performs the same
# multiply in-register on its pool tiles — quantized blocks are read
# straight from HBM with no materialized fp16 copy.

KV_STORE_DTYPES = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}
# symmetric range the per-vector absmax maps onto: int8 uses the
# ZeRO++ [-127, 127] grid; fp8-e4m3 saturates at the format max (448)
KV_QMAX = {"int8": 127.0, "fp8": 448.0}


def kv_quantize(x, kv_dtype: str, scale_heads: int):
    """Quantize fresh KV vectors for the paged pool.

    ``x`` is ``[..., H, D]`` (any leading batch/layer/seq dims);
    returns ``(codes [..., H, D] in the storage dtype, scales f32
    [..., scale_heads])`` where ``scale_heads`` is ``H`` (granularity
    "head": absmax per (token, kv-head) vector) or ``1`` (granularity
    "token": one absmax across all heads of the token). The scale
    layout matches the engine's scale pools, so the caller scatters
    codes and scales through the same block table."""
    store = KV_STORE_DTYPES[kv_dtype]
    qmax = KV_QMAX[kv_dtype]
    h = x.shape[-2]
    xf = x.astype(jnp.float32)
    if scale_heads == 1:
        amax = jnp.max(jnp.abs(xf), axis=(-2, -1), keepdims=True)[..., 0]
    else:
        assert scale_heads == h, (scale_heads, h)
        amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax / qmax, 1e-12)              # [..., Hs]
    y = xf / scale[..., :, None]
    if kv_dtype == "int8":
        codes = jnp.clip(jnp.round(y), -127, 127).astype(store)
    else:
        # e4m3 has no inf: clip before the cast so overflow saturates
        # instead of producing NaN payload bytes
        codes = jnp.clip(y, -qmax, qmax).astype(store)
    return codes, scale


def kv_dequantize(codes, scales, dtype=jnp.float32):
    """Inverse of :func:`kv_quantize`: ``codes [..., H, D]`` times the
    broadcast per-vector ``scales [..., Hs]`` (``Hs`` is H or 1). Plain
    jnp so XLA fuses the multiply into the first consumer."""
    return (codes.astype(jnp.float32)
            * scales[..., :, None]).astype(dtype)


def kv_bytes_per_token(num_kv_heads: int, head_dim: int, kv_dtype: str,
                       scale_heads: int = 0) -> float:
    """Storage bytes ONE token's k+v vectors cost PER LAYER in a given
    format — the format-comparison counterpart of
    ``ragged.kv_block_bytes`` (the engine sizes pools through that;
    the exported ``ds_kv_bytes_per_token`` gauge is all-layers, from
    the live arrays). Tests cross-check the two layouts against each
    other through this. "fp16"/"bf16"/"fp32" are the unquantized
    baselines (no scales); int8/fp8 add one f32 scale per
    ``scale_heads`` (0 = per-head granularity default)."""
    elems = num_kv_heads * head_dim
    if kv_dtype in ("fp32", "float32"):
        return 2.0 * elems * 4
    if kv_dtype in ("fp16", "float16", "bf16", "bfloat16"):
        return 2.0 * elems * 2
    if kv_dtype in KV_STORE_DTYPES:
        hs = scale_heads or num_kv_heads
        return 2.0 * (elems * 1 + hs * 4)
    raise ValueError(f"unknown kv dtype {kv_dtype!r}")


def quantize_fp8(x):
    """fp8-e4m3 block quantization: native float8 codes + f32 scales.
    Same contract as quantize_int8 — a thin meta adapter over
    ops/fp_quant.fp_quantize (single source of truth for the fp
    formats; reference analogue: csrc/fp_quantizer/fp_quantize.cu)."""
    from ..fp_quant import fp_quantize
    q, s = fp_quantize(x, q_bits=8, mantissa_bits=3, group_size=QBLOCK)
    return q, s, (x.shape, x.dtype, x.size)


def dequantize_fp8(q, s, meta):
    from ..fp_quant import fp_dequantize
    shape, dtype, n = meta
    return fp_dequantize(q, s, q_bits=8, mantissa_bits=3, shape=shape,
                         dtype=dtype)


def saturation_probe(site: str, codes, qmax: float = 127.0) -> None:
    """numsan quantize-site probe (ISSUE 18): when a
    :class:`..analysis.numsan.NumericsSanitizer` with saturation
    probing is active AT TRACE TIME, fold one tiny fused reduction —
    the fraction of codes sitting on the clip boundary — into the
    caller's graph and ship it off-device through
    ``jax.debug.callback`` (the moe/dispatch router-telemetry pattern)
    into ``NumericsSanitizer.report_saturation`` →
    ``ds_numsan_saturation_ratio{site}``. Arming is read through a
    ``sys.modules`` lookup, so a sanitizer-off process imports nothing
    and the traced graph is byte-identical; findings (fraction above
    the configured ceiling) are deferred to the next host
    :meth:`drain` — a callback thread cannot usefully raise."""
    import sys
    mod = sys.modules.get("deepspeed_tpu.analysis.numsan")
    san = mod.get_numsan() if mod is not None else None
    if san is None or not getattr(san, "saturation_probe", False):
        return
    frac = jnp.mean((jnp.abs(codes.astype(jnp.float32))
                     >= float(qmax)).astype(jnp.float32))

    def _emit(f, _site=site):
        m = sys.modules.get("deepspeed_tpu.analysis.numsan")
        s = m.get_numsan() if m is not None else None
        if s is not None:
            s.report_saturation(_site, float(f))

    jax.debug.callback(_emit, frac)


def wire_bytes_per_element(wire_dtype: str, block: int = QBLOCK) -> float:
    """Effective wire bytes per payload element, per-block fp32 scales
    included — the single number the autotuning cost model and the
    telemetry wire accounting share. fp32 wire = 4 exactly (no scales);
    int8/fp8 = 1 + 4/block."""
    if wire_dtype in ("fp32", "f32", "none"):
        return 4.0
    if wire_dtype in ("bf16", "f16"):
        return 2.0 + 4.0 / block
    if wire_dtype in ("int8", "s8", "fp8", "f8"):
        return 1.0 + 4.0 / block
    raise ValueError(f"unknown wire dtype {wire_dtype!r}")


def _wire_quantizer(wire_dtype: str, rounding: str = "nearest",
                    key=None):
    if wire_dtype == "fp8":
        # fp8 codes round via the native dtype cast; stochastic mode is
        # int8-only (documented in docs/zeropp.md accuracy knobs)
        return quantize_fp8, dequantize_fp8
    return (lambda x: quantize_int8(x, rounding=rounding, key=key),
            lambda q, s, m: dequantize_int8(q, s, m, use_pallas=False))


def quantized_all_gather(x, axes, dim: int = 0, wire_dtype: str = "int8"):
    """ZeRO++ qwZ: quantize the local shard, all-gather int8/fp8 codes +
    scales along mesh ``axes``, dequantize, and reassemble on ``dim``.
    Must run inside shard_map (reference: partition_parameters.py:761
    CUDAQuantizer bracketing the param all-gather). The quantize side
    uses the Pallas kernel on TPU (single HBM pass before the
    collective); the dequantize side is plain jnp so XLA fuses it into
    the gathered tensor's first consumer."""
    from jax import lax

    quant, dequant = _wire_quantizer(wire_dtype)
    q, s, meta = quant(x)
    saturation_probe("qwz_wire", q,
                     qmax=448.0 if wire_dtype == "fp8" else 127.0)
    qg = lax.all_gather(q, axes, axis=0, tiled=False)
    sg = lax.all_gather(s, axes, axis=0, tiled=False)
    if wire_dtype == "fp8":
        pieces = jax.vmap(lambda qq, ss: dequant(qq, ss, meta))(qg, sg)
    else:
        shape, dtype, n = meta
        deq = qg.astype(jnp.float32) * sg       # [world, nblocks, QBLOCK]
        world = deq.shape[0]
        pieces = deq.reshape(world, -1)[:, :n].reshape(
            (world,) + shape).astype(dtype)
    world = pieces.shape[0]
    out = jnp.moveaxis(pieces, 0, dim)          # [..., world, shard, ...]
    shape = list(x.shape)
    shape[dim] = world * x.shape[dim]
    return out.reshape(shape)

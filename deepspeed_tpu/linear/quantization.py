"""Quantized frozen parameters (reference: deepspeed/linear/quantization.py
QuantizedParameter + csrc/fp_quantizer — FP6/FP8/FP12/INT8 weight storage
with on-the-fly dequantization).

A ``QuantizedParameter`` is a pytree-registered container of codes +
per-block scales. It lives inside a parameter tree like a regular leaf
pair and dequantizes inside jit right before the matmul — XLA fuses the
dequant into the GEMM prologue, which is the TPU counterpart of the
reference's fused dequant kernels (fp_quantize.cu selective dequant).

Two storage families (``QuantizationConfig.q_format``):

- ``"int"`` — symmetric int block quant at 4/6/8 bits (int8 codes).
- ``"fp"``  — float formats via ops/fp_quant.py: native jnp.float8
  (e4m3/e5m2) at 8 bits, bit-packed fp6/fp12 otherwise — the reference's
  FP6-LLM storage (csrc/fp_quantizer/fp_quantize.cu).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .config import QuantizationConfig


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedParameter:
    """Block-quantized tensor (reference: quantization.py:27)."""

    codes: jax.Array          # int8 [nblocks, group] | float8 | packed u8
    scales: jax.Array         # f32  [nblocks, 1]
    shape: tuple = ()         # original shape (static)
    dtype: Any = jnp.float32  # original dtype (static)
    q_bits: int = 8           # static
    q_format: str = "int"     # "int" | "fp" (static)
    mantissa_bits: int = 3    # static; fp formats only

    def tree_flatten(self):
        return (self.codes, self.scales), (self.shape, self.dtype,
                                           self.q_bits, self.q_format,
                                           self.mantissa_bits)

    @classmethod
    def tree_unflatten(cls, aux, children):
        codes, scales = children
        return cls(codes, scales, *aux)

    def dequantized(self) -> jax.Array:
        """reference: QuantizedParameter.dequantized()"""
        import math
        if self.q_format == "fp":
            from ..ops.fp_quant import fp_dequantize
            return fp_dequantize(
                self.codes, self.scales, q_bits=self.q_bits,
                mantissa_bits=self.mantissa_bits, shape=self.shape,
                dtype=self.dtype)
        x = self.codes.astype(jnp.float32) * self.scales
        n = math.prod(self.shape) if self.shape else 1
        return x.reshape(-1)[:n].reshape(self.shape).astype(self.dtype)

    @property
    def ndim(self):
        return len(self.shape)


def quantize_param(x: jax.Array,
                   cfg: QuantizationConfig | None = None
                   ) -> QuantizedParameter:
    """Block quantization per cfg: int 4/6/8, or float 6/8/12
    (q_format="fp")."""
    cfg = cfg or QuantizationConfig()
    if cfg.q_format == "fp":
        from ..ops.fp_quant import fp_quantize
        codes, scales = fp_quantize(
            x, q_bits=cfg.q_bits, mantissa_bits=cfg.mantissa_bits,
            group_size=cfg.group_size)
        return QuantizedParameter(codes, scales, tuple(x.shape), x.dtype,
                                  cfg.q_bits, "fp", cfg.mantissa_bits)
    if cfg.q_bits not in (4, 6, 8):
        raise ValueError(f"q_bits must be 4, 6 or 8, got {cfg.q_bits}")
    qmax = 2 ** (cfg.q_bits - 1) - 1
    g = cfg.group_size
    n = x.size
    flat = jnp.pad(x.reshape(-1).astype(jnp.float32), (0, (-n) % g))
    blocks = flat.reshape(-1, g)
    amax = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True)
    scales = jnp.maximum(amax / qmax, 1e-12)
    codes = jnp.clip(jnp.round(blocks / scales), -qmax, qmax).astype(jnp.int8)
    return QuantizedParameter(codes, scales, tuple(x.shape), x.dtype,
                              cfg.q_bits)


def is_quantized(leaf: Any) -> bool:
    return isinstance(leaf, QuantizedParameter)


def dequantize_tree(tree: Any) -> Any:
    """Replace every QuantizedParameter leaf with its dequantized array."""
    return jax.tree.map(
        lambda x: x.dequantized() if is_quantized(x) else x,
        tree, is_leaf=is_quantized)


# ---------------------------------------------------------------------
# Serving-side whole-tree weight-only int8 (reference: ZeRO-Inference
# weight quantization + inference/v2 cutlass mixed_gemm — fp16
# activations x int8 weights). Storage uses the same `name_q`/`name_s`
# convention as moe/sharded_moe.quantize_experts, and DecoderLM
# dequantizes per LAYER inside the scan body, so at no point does more
# than one layer's bf16 weights exist in HBM — XLA fuses the
# convert+scale into the consuming GEMM's operand read.

def _q_leaf(w, scale_dtype):
    s = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2,
                keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-12)
    q = jnp.round(w.astype(jnp.float32) / s).astype(jnp.int8)
    return q, s.astype(scale_dtype)


def quantizable_leaf(shape, ndim: int, path: tuple,
                     min_size: int = 1 << 16) -> bool:
    """THE eligibility predicate for weight-only int8 leaves (shared by
    quantize_dense_params and device-side weight generators):
    layer-stacked matrices (ndim>=3 — per-layer [L, d]
    norm/bias VECTORS must never be scaled over the layer axis) or
    top-level 2-D matrices (lm_head), matrix-like trailing dims, and
    big enough to be worth scales."""
    import math
    return ((ndim >= 3 or (ndim == 2 and "layers" not in path))
            and min(shape[-2], shape[-1]) >= 8
            and math.prod(shape) >= min_size)


def quantize_dense_params(params: Any, min_size: int = 1 << 16,
                          scale_dtype=jnp.bfloat16,
                          donate: bool = False) -> Any:
    """Weight-only int8 over a DecoderLM param tree: every eligible
    float leaf becomes `name_q` (int8) + `name_s` (per-output-channel
    scale over the contraction dim, axis -2). Eligible = layer-stacked
    matrices (ndim>=3 — per-layer [L, d] norm/bias VECTORS are never
    scaled over the layer axis) and top-level 2-D matrices (lm_head);
    the embedding table is skipped (its gather is not a GEMM).
    Quantization runs leaf-at-a-time, so host checkpoints move to HBM
    as int8 without the float tree ever existing on device.
    ``donate=True`` additionally frees each input leaf's device buffer
    as it converts (use ONLY for trees the caller owns — donated
    arrays are deleted for every other holder)."""
    q_jit = jax.jit(_q_leaf, static_argnums=(1,),
                    donate_argnums=(0,) if donate else ())

    def walk(tree, path=()):
        out = {}
        for k, v in tree.items():
            if isinstance(v, dict):
                out[k] = (v if k == "embed"
                          else walk(v, path + (k,)))
            elif (hasattr(v, "ndim") and v.ndim >= 2
                    and jnp.issubdtype(v.dtype, jnp.floating)
                    and quantizable_leaf(v.shape, v.ndim, path,
                                         min_size)):
                q, s = q_jit(v, scale_dtype)
                out[k + "_q"], out[k + "_s"] = q, s
            else:
                out[k] = v
        return out

    return walk(params)


def dequantize_dense(tree: dict, dtype) -> dict:
    """Shallow inline dequant of one quantize_dense_params level (the
    per-layer dict inside the scan body, or the top level for the
    head); nested dicts pass through untouched (the MoE experts dict
    dequantizes at its own use site, moe/sharded_moe.py)."""
    if not any(k.endswith("_q") for k in tree):
        return tree
    out = {k: v for k, v in tree.items()
           if not (k.endswith("_q") or k.endswith("_s"))}
    for k in tree:
        if k.endswith("_q"):
            out[k[:-2]] = (tree[k].astype(dtype)
                           * tree[k[:-2] + "_s"].astype(dtype))
    return out

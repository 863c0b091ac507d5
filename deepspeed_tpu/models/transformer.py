"""Decoder-only transformer core, TPU-first.

One parameterized implementation serves GPT-2 (learned positions, LayerNorm,
GELU, biases) and Llama (RoPE, RMSNorm, SwiGLU, GQA, no biases) — the
architecture switches live in ``ModelConfig``. Design choices that matter
on TPU:

- **Stacked layer parameters** ``[L, ...]`` + ``lax.scan`` over layers: one
  compiled block regardless of depth, and ZeRO-3-style parameter sharding
  becomes "all-gather one layer slice per scan step" which XLA pipelines
  against compute — the static-schedule translation of the reference's
  trace-based prefetch coordinator
  (``runtime/zero/partitioned_param_coordinator.py:276``).
- **Pluggable attention** (``attn_fn``): the Ulysses/ring sequence-parallel
  wrappers (deepspeed_tpu/sequence/) and the Pallas flash kernel drop in
  without touching the model, mirroring how ``DistributedAttention`` wraps
  any local attention (``deepspeed/sequence/layer.py:271``).
- **Exposed embed/block/unembed** pieces so the pipeline engine
  (runtime/pipe/) can place stage boundaries without re-deriving the model.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import layers as L
from ..ops.pallas._common import KEPT_RESIDUAL
from ..parallel.mesh import constrain_free
from .base import Model, ModelConfig, Rules

PyTree = Any
AttnFn = Callable[..., jax.Array]


def _dense_init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


class DecoderLM:
    """Functional decoder-only LM over a parameter pytree."""

    def __init__(self, config: ModelConfig):
        self.config = config
        if config.position_embedding == "rope":
            # partial rotary (rotary_pct < 1): rope covers only the first
            # rot_dim channels of each head (GPT-NeoX/Phi-2 style)
            self._rot_dim = max(2, int(config.head_dim
                                       * config.rotary_pct) // 2 * 2)
            self._rope = L.rotary_embedding(
                config.max_seq_len, self._rot_dim, config.rope_theta)
            self._rotary = L.rotary_tables(*self._rope, config.head_dim)
        else:
            self._rot_dim = 0
            self._rope = self._rotary = None
        self._alibi_slopes = (L.alibi_slopes(config.num_heads)
                              if config.position_embedding == "alibi"
                              else None)
        if self._alibi_slopes is not None and config.attn_impl == "flash":
            raise ValueError(
                "attn_impl='flash' does not support ALiBi yet — the "
                "kernel has no per-head additive-bias path; use the "
                "default attention (O(S^2) bias) or rope/learned "
                "positions with flash")

    # ---------------- init ----------------
    def init(self, rng: jax.Array) -> PyTree:
        c = self.config
        dt = c.param_dtype
        d, f, v = c.hidden_size, c.intermediate_size, c.vocab_size
        nh, nkv, hd = c.num_heads, c.num_kv_heads, c.head_dim
        keys = jax.random.split(rng, 8)
        std = 0.02
        resid_std = std / (2 * c.num_layers) ** 0.5

        def layer_stack(key, shape, scale):
            return _dense_init(key, (c.num_layers, *shape), scale, dt)

        lk = jax.random.split(keys[0], 12)
        layers = {
            "ln1_scale": jnp.ones((c.num_layers, d), dt),
            "wq": layer_stack(lk[0], (d, nh * hd), std),
            "wk": layer_stack(lk[1], (d, nkv * hd), std),
            "wv": layer_stack(lk[2], (d, nkv * hd), std),
            "wo": layer_stack(lk[3], (nh * hd, d), resid_std),
            "w_up": layer_stack(lk[4], (d, f), std),
            "w_down": layer_stack(lk[5], (f, d), resid_std),
        }
        has_ln2 = not c.parallel_residual or c.parallel_dual_norm
        if has_ln2:  # single-norm parallel blocks share ln1
            layers["ln2_scale"] = jnp.ones((c.num_layers, d), dt)
        if c.activation == "swiglu":
            layers["w_gate"] = layer_stack(lk[6], (d, f), std)
        if c.norm_type == "layernorm":
            layers["ln1_bias"] = jnp.zeros((c.num_layers, d), dt)
            if has_ln2:
                layers["ln2_bias"] = jnp.zeros((c.num_layers, d), dt)
        if c.use_bias or c.attn_qkv_bias:
            layers.update({
                "wq_b": jnp.zeros((c.num_layers, nh * hd), dt),
                "wk_b": jnp.zeros((c.num_layers, nkv * hd), dt),
                "wv_b": jnp.zeros((c.num_layers, nkv * hd), dt),
            })
        if c.use_bias:
            layers["wo_b"] = jnp.zeros((c.num_layers, d), dt)
        if c.effective_mlp_bias:
            layers.update({
                "w_up_b": jnp.zeros((c.num_layers, f), dt),
                "w_down_b": jnp.zeros((c.num_layers, d), dt),
            })
            if c.activation == "swiglu":
                layers["w_gate_b"] = jnp.zeros((c.num_layers, f), dt)
        params: dict[str, Any] = {
            "embed": {"tokens": _dense_init(keys[1], (v, d), std, dt)},
            "layers": layers,
            "final_norm": {"scale": jnp.ones((d,), dt)},
        }
        if c.position_embedding == "learned":
            params["embed"]["positions"] = _dense_init(
                keys[2], (c.max_seq_len, d), std, dt)
        if c.embed_layernorm:   # Bloom: LayerNorm after word embeddings
            params["embed"]["ln_scale"] = jnp.ones((d,), dt)
            params["embed"]["ln_bias"] = jnp.zeros((d,), dt)
        if c.norm_type == "layernorm":
            params["final_norm"]["bias"] = jnp.zeros((d,), dt)
        if not c.tie_embeddings:
            params["lm_head"] = _dense_init(keys[3], (d, v), std, dt)
            if c.lm_head_bias:  # Phi / GPT-J biased vocab projection
                params["lm_head_b"] = jnp.zeros((v,), dt)
        return params

    # ---------------- pieces (reused by pipeline/inference) --------------
    def _maybe_dequant(self, p: PyTree, dtype) -> PyTree:
        """Inline per-layer dequant of weight-only int8 serving trees
        (linear/quantization.py quantize_dense_params): inside the layer
        scan, at most ONE layer's bf16 weights ever exist and XLA fuses
        the convert+scale into the consuming GEMM (reference:
        ZeRO-Inference weight quantization / cutlass mixed_gemm)."""
        from ..linear.quantization import dequantize_dense
        return dequantize_dense(p, dtype)

    def _norm(self, x, scale, bias=None):
        if self.config.norm_type == "rmsnorm":
            return L.rms_norm(x, scale, self.config.norm_eps)
        return L.layer_norm(x, scale, bias, self.config.norm_eps)

    def embed(self, params: PyTree, tokens: jax.Array,
              positions: jax.Array | None = None) -> jax.Array:
        c = self.config
        if tokens.shape[-1] > c.max_seq_len:
            raise ValueError(
                f"sequence length {tokens.shape[-1]} exceeds max_seq_len "
                f"{c.max_seq_len}")
        x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
        if c.position_embedding == "learned":
            if positions is None:
                positions = jnp.arange(tokens.shape[-1])[None, :]
            x = x + jnp.take(params["embed"]["positions"], positions, axis=0)
        if c.embed_layernorm:
            x = L.layer_norm(x, params["embed"]["ln_scale"],
                             params["embed"]["ln_bias"], c.norm_eps)
        return x

    def _hands_rotary(self, attn_fn, positions=None) -> dict:
        """``{"rotary": tables}`` where ``attn_fn`` rotates q and k itself
        (``ops.layers.hands_rotary``: the flash kernels' wrappers at a
        lane-aligned head, no ``positions``), else ``{}``: what a block
        hands ``attn_fn`` beside the q and k that ``_qkv(rotate=not ...)``
        then leaves unrotated."""
        return ({"rotary": self._rotary}
                if L.hands_rotary(attn_fn, self._rotary, positions) else {})

    def _qkv(self, p: PyTree, h: jax.Array,
             positions: jax.Array | None = None, *, rotate: bool = True):
        """Shared q/k/v projection (+bias, head reshape, rope unless the
        attention rotates: ``_hands_rotary``)."""
        c = self.config
        b, s, _ = h.shape
        nh, nkv, hd = c.num_heads, c.num_kv_heads, c.head_dim
        q = h @ p["wq"]
        k = h @ p["wk"]
        v = h @ p["wv"]
        if c.use_bias or c.attn_qkv_bias:
            q, k, v = q + p["wq_b"], k + p["wk_b"], v + p["wv_b"]
        q = q.reshape(b, s, nh, hd)
        k = k.reshape(b, s, nkv, hd)
        v = v.reshape(b, s, nkv, hd)
        if rotate and self._rope is not None:
            cos, sin = self._rope
            if self._rot_dim < hd:   # partial rotary: rotate a prefix
                q = jnp.concatenate(
                    [L.apply_rotary(q[..., :self._rot_dim], cos, sin,
                                    positions), q[..., self._rot_dim:]],
                    axis=-1)
                k = jnp.concatenate(
                    [L.apply_rotary(k[..., :self._rot_dim], cos, sin,
                                    positions), k[..., self._rot_dim:]],
                    axis=-1)
            else:
                q = L.apply_rotary(q, cos, sin, positions)
                k = L.apply_rotary(k, cos, sin, positions)
        from jax.ad_checkpoint import checkpoint_name
        return (checkpoint_name(q, "qkv"), checkpoint_name(k, "qkv"),
                checkpoint_name(v, "qkv"))

    def _attn_out(self, p: PyTree, a: jax.Array) -> jax.Array:
        b, s = a.shape[:2]
        out = a.reshape(b, s, -1) @ p["wo"]
        if self.config.use_bias:
            out = out + p["wo_b"]
        return out

    def _mlp_residual(self, p: PyTree, x: jax.Array):
        h = self._norm(x, p["ln2_scale"], p.get("ln2_bias"))
        m, aux = self._mlp(p, h)
        return x + m, aux

    def _parallel_mlp_input(self, p: PyTree, x: jax.Array, h: jax.Array):
        """MLP input for parallel-residual blocks — THE single place for
        the dual-norm switch (GPT-NeoX norms the raw residual with ln2;
        Falcon/GPT-J share ln1's output). apply/flash/decode/paged all
        route through here so the paths can't drift (a past bug: decode
        and v2 serving fed ln1's output to a dual-norm MLP)."""
        if self.config.parallel_dual_norm:
            return self._norm(x, p["ln2_scale"], p.get("ln2_bias"))
        return h

    def block(self, layer_params: PyTree, x: jax.Array, *,
              attn_fn: AttnFn | None = None,
              positions: jax.Array | None = None) -> jax.Array:
        """One transformer block. layer_params carries per-layer slices
        (no leading L dim)."""
        c = self.config
        p = self._maybe_dequant(layer_params, x.dtype)
        if (attn_fn is not None and c.sliding_window is not None
                and not getattr(attn_fn, "applies_window", False)):
            from ..utils.logging import warning_once
            warning_once(
                "sliding_window is set but a custom attn_fn (e.g. the "
                "sequence-parallel wrapper) is in use; the window mask is "
                "NOT applied by the wrapper — attention is full-causal")
        if attn_fn is not None and c.position_embedding == "alibi":
            from ..utils.logging import warning_once
            warning_once(
                "position_embedding='alibi' but a custom attn_fn (e.g. the "
                "sequence-parallel wrapper) is in use; the ALiBi bias is "
                "NOT applied by the wrapper — the model runs with no "
                "positional encoding")
        if attn_fn is None:
            if c.position_embedding == "alibi":
                # ALiBi rides the exact path as a per-head additive bias
                # (Bloom; reference bloom containers add it in-kernel)
                import functools
                attn_fn = functools.partial(
                    L.dot_product_attention,
                    bias=L.alibi_bias(self._alibi_slopes, x.shape[1]))
            elif c.attn_impl == "flash":
                import functools

                from ..ops.pallas.flash_attention import flash_attention
                attn_fn = (functools.partial(flash_attention,
                                             window=c.sliding_window)
                           if c.sliding_window is not None
                           else flash_attention)
            elif c.sliding_window is not None:
                import functools
                attn_fn = functools.partial(
                    L.dot_product_attention,
                    bias=self._window_bias(x.shape[1]))
            else:
                attn_fn = L.dot_product_attention

        if c.remat and c.remat_policy == "segments":
            return self._block_segmented(p, x, attn_fn, positions)

        # device scopes (telemetry/scopes.py DEVICE_SCOPES): HLO metadata a
        # device trace is read by, no run-time cost
        with jax.named_scope("ds.attn"):
            h = self._norm(x, p["ln1_scale"], p.get("ln1_bias"))
            rotary = self._hands_rotary(attn_fn, positions)
            q, k, v = self._qkv(p, h, positions, rotate=not rotary)
            attn_out = self._attn_out(
                p, attn_fn(q, k, v, causal=True, **rotary))
        if c.parallel_residual:
            with jax.named_scope("ds.mlp"):
                m, aux = self._mlp(p, self._parallel_mlp_input(p, x, h))
            return x + attn_out + m, aux
        x = x + attn_out
        with jax.named_scope("ds.mlp"):
            return self._mlp_residual(p, x)

    def _block_segmented(self, p, x, attn_fn, positions):
        """Segment remat: attention sits OUTSIDE any jax.checkpoint, so
        all five of its custom-VJP residuals (q, k, v, o, lse) are stored
        and the backward never re-runs the forward flash kernel, nor the
        projections that make q, k, v. (A whole-layer checkpoint stores
        `o` and `lse` alone, which the kernel's forward rule declares
        kept, `_remat_policy`: it skips the kernel's rerun too, and makes
        q, k, v again.) The projections around it are rematted in two
        segments:

        - seg_qkv (norm + qkv projection): saves nothing internally; its
          outputs q/k/v are boundary values (= the flash residuals).
        - seg_out (output proj + MLP): saves the mid-residual and the
          pre-activation ffn tensors, so backward recomputes only norms
          and the activation function — no matmul re-runs.

        Net per-layer saves at [B=24, S=1024, D=768]: ~378MB vs ~302MB
        for "save_attn_ffn", in exchange for skipping the qkv, attn-proj
        and up-matmul recomputes (~3.5ms/layer on v5e with the flash
        rerun, which no policy pays any more).
        """
        c = self.config
        from jax.ad_checkpoint import checkpoint_name

        rotary = self._hands_rotary(attn_fn, positions)

        def seg_qkv(p, x):
            h = self._norm(x, p["ln1_scale"], p.get("ln1_bias"))
            q, k, v = self._qkv(p, h, positions, rotate=not rotary)
            return q, k, v, (h if c.parallel_residual else None)

        with jax.named_scope("ds.attn"):
            q, k, v, h = jax.checkpoint(seg_qkv, prevent_cse=False)(p, x)
            a = attn_fn(q, k, v, causal=True, **rotary)

        def seg_out(p, x, a, h):
            with jax.named_scope("ds.attn"):
                attn_out = self._attn_out(p, a)
            if c.parallel_residual:
                with jax.named_scope("ds.mlp"):
                    m, aux = self._mlp(
                        p, self._parallel_mlp_input(p, x, h))
                return x + attn_out + m, aux
            x2 = checkpoint_name(x + attn_out, "resid_mid")
            with jax.named_scope("ds.mlp"):
                return self._mlp_residual(p, x2)

        pol = jax.checkpoint_policies.save_only_these_names(
            "resid_mid", "ffn_pre")
        return jax.checkpoint(seg_out, prevent_cse=False, policy=pol)(
            p, x, a, h)

    def _window_bias(self, seq_len: int) -> jax.Array:
        return L.window_bias(seq_len, self.config.sliding_window)

    def _mlp(self, p: PyTree, h: jax.Array):
        """Dense FFN. Returns (out, aux_loss) — MoE subclasses override
        (aux carries the router load-balancing loss)."""
        from jax.ad_checkpoint import checkpoint_name
        c = self.config
        mlp_bias = c.effective_mlp_bias
        if c.activation == "swiglu":
            gate = checkpoint_name(h @ p["w_gate"], "ffn_pre")
            up = checkpoint_name(h @ p["w_up"], "ffn_pre")
            if mlp_bias:
                gate = gate + p["w_gate_b"]
                up = up + p["w_up_b"]
            m = L.silu(gate) * up
        else:
            up = checkpoint_name(h @ p["w_up"], "ffn_pre")
            if mlp_bias:
                up = up + p["w_up_b"]
            m = jax.nn.relu(up) if c.activation == "relu" else L.gelu(up)
        m = checkpoint_name(m, "ffn")
        m = m @ p["w_down"]
        if mlp_bias:
            m = m + p["w_down_b"]
        return m, jnp.zeros((), jnp.float32)

    # ---------------- KV-cache decode (inference engine) -----------------
    def init_cache(self, batch_size: int, max_len: int,
                   dtype=None) -> PyTree:
        """Static-shape KV cache (reference: inference_context.h KV buffer
        allocation). [L, B, S_max, H_kv, D] per k/v."""
        c = self.config
        dt = dtype or c.param_dtype
        shape = (c.num_layers, batch_size, max_len, c.num_kv_heads,
                 c.head_dim)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
                "index": jnp.zeros((), jnp.int32)}

    def block_decode(self, layer_params: PyTree, x: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array,
                     index: jax.Array):
        """One block over new tokens with cache read/write. x: [B, S_new,
        D]; caches [B, S_max, H_kv, D]. Returns (x, new_k, new_v)."""
        p = self._maybe_dequant(layer_params, x.dtype)
        b, s, _ = x.shape
        positions = (index + jnp.arange(s))[None, :].repeat(b, axis=0)

        h = self._norm(x, p["ln1_scale"], p.get("ln1_bias"))
        q, k, v = self._qkv(p, h, positions)
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), index, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), index, axis=1)
        a = L.cached_attention(q, k_cache, v_cache, index,
                               window=self.config.sliding_window,
                               alibi_slopes=self._alibi_slopes)
        if self.config.parallel_residual:
            m, _ = self._mlp(p, self._parallel_mlp_input(p, x, h))
            return x + self._attn_out(p, a) + m, k_cache, v_cache
        x = x + self._attn_out(p, a)
        x, _ = self._mlp_residual(p, x)
        return x, k_cache, v_cache

    def decode(self, params: PyTree, tokens: jax.Array, cache: PyTree):
        """Prefill or incremental decode: run `tokens` (appended at
        cache["index"]) through all layers, updating the cache. Returns
        (logits [B, S_new, V], new_cache)."""
        index = cache["index"]
        b, s = tokens.shape
        positions = (index + jnp.arange(s))[None, :].repeat(b, axis=0)
        x = self.embed(params, tokens, positions=positions)

        def body(x, xs):
            layer_params, k_l, v_l = xs
            x, new_k, new_v = self.block_decode(layer_params, x, k_l, v_l,
                                                index)
            return x, (new_k, new_v)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        logits = self.unembed(params, x)
        return logits, {"k": new_k, "v": new_v, "index": index + s}

    def unembed(self, params: PyTree, x: jax.Array) -> jax.Array:
        x = self._norm(x, params["final_norm"]["scale"],
                       params["final_norm"].get("bias"))
        return self._project_vocab(params, x)

    # ---------------- apply / loss ----------------
    def apply(self, params: PyTree, tokens: jax.Array, *,
              attn_fn: AttnFn | None = None,
              positions: jax.Array | None = None,
              return_aux: bool = False, act_sharding=None):
        x, aux = self._final_hidden(params, tokens, attn_fn=attn_fn,
                                    positions=positions,
                                    act_sharding=act_sharding)
        with jax.named_scope("ds.loss_head"):
            logits = self._project_vocab(params, x)
        return (logits, aux) if return_aux else logits

    def loss(self, params: PyTree, batch: Any, *,
             attn_fn: AttnFn | None = None,
             act_sharding=None) -> jax.Array:
        tokens, targets = _unpack_batch(batch)
        if self.config.loss_chunk > 0:
            return self._chunked_loss(params, tokens, targets,
                                      attn_fn=attn_fn,
                                      act_sharding=act_sharding)
        logits, aux = self.apply(params, tokens, attn_fn=attn_fn,
                                 return_aux=True,
                                 act_sharding=act_sharding)
        with jax.named_scope("ds.loss_head"):
            ce = L.cross_entropy_loss(logits, targets)
        return ce + self.aux_loss_coef() * aux

    def _chunked_loss(self, params: PyTree, tokens, targets, *,
                      attn_fn=None, act_sharding=None) -> jax.Array:
        """Fused chunked cross-entropy: the [B, S, V] logits tensor is
        never materialized. The unembed matmul, the f32 softmax and, under
        differentiation, the gradient's two matmuls run per sequence chunk
        in one scan, so peak HBM holds one [B, loss_chunk, V] slab and
        nothing is recomputed (see _chunked_cross_entropy). The
        HBM-traffic role of the reference's fused logits kernels
        (csrc/transformer/inference logits_gather + fused softmax)."""
        x, aux = self._final_hidden(params, tokens, attn_fn=attn_fn,
                                    act_sharding=act_sharding)
        with jax.named_scope("ds.loss_head"):
            ce = self._chunked_ce(params, x, targets)
        return ce + self.aux_loss_coef() * aux

    def _chunked_ce(self, params: PyTree, x, targets, weights=None):
        """Mean cross-entropy of final-normed hidden states ``x``, one
        ``loss_chunk`` slab of logits at a time
        (``_chunked_cross_entropy``). With float32 ``weights`` a row
        ``[B, S]``: ``(sum(weights * nll) / count, the rows' nll)``
        (``_chunked_weighted_cross_entropy``)."""
        c = self.config
        # the casts stay outside the custom_vjp, so JAX transposes them
        # (and the tied embedding's ``.T``) onto the parameters' dtypes
        W = (params["embed"]["tokens"].T if c.tie_embeddings
             else params["lm_head"]).astype(x.dtype)
        bias = params.get("lm_head_b")
        if bias is not None:
            bias = bias.astype(jnp.float32)
        s = x.shape[1]
        chunk = min(c.loss_chunk, s)
        if s % chunk != 0:
            raise ValueError(
                f"loss_chunk {c.loss_chunk} (effective {chunk}) must "
                f"divide sequence length {s}")
        if weights is not None:
            return _chunked_weighted_cross_entropy(x, W, bias, targets,
                                                   weights, chunk)
        return _chunked_cross_entropy(x, W, bias, targets, chunk)

    def _final_hidden(self, params: PyTree, tokens, *, attn_fn=None,
                      positions=None, act_sharding=None):
        """Final-normed hidden states [B, S, D] + router aux loss.

        ``act_sharding`` (a NamedSharding for [B, S, D]; the engine
        passes ``[B(batch axes), S(sp), D]`` on every mesh of more than
        one device) pins the layer-scan carry to the ZeRO plan's layout,
        in the forward and, through the constraint's transpose, in the
        backward: activations stay on the chip that owns their sequences
        and each layer's weights are gathered to them. Without it GSPMD
        lays out ``[B, S, D]`` and ``[B, S, F]`` between two shard_map
        boundaries as it likes. With fsdp-sharded stacked weights the
        TPU compile then moved the activations to the weights: on
        ``fsdp=4`` at Mistral-7B widths it ran the MLP's backward
        tensor-parallel over ``fsdp``, five 224 MiB all-to-alls a layer
        (PERF.md, PR 28); on the ring configuration it flipped layouts
        between scan iterations ('Involuntary full rematerialization' of
        the embed gradient scatter-add, VERDICT r4 #2). The constraint
        holds wherever this is traced (``parallel.mesh.constrain_free``:
        axes manual in an enclosing region are dropped, an uneven batch
        stays unconstrained)."""
        pin = (functools.partial(constrain_free, sharding=act_sharding)
               if act_sharding is not None else lambda x: x)
        with jax.named_scope("ds.embed"):
            x = self.embed(params, tokens, positions)
        x = pin(x)

        with jax.named_scope("ds.layers"):
            x, aux = self._layer_stack(params["layers"], x, pin,
                                       attn_fn=attn_fn, positions=positions,
                                       act_sharding=act_sharding)
        with jax.named_scope("ds.loss_head"):
            x = self._norm(x, params["final_norm"]["scale"],
                           params["final_norm"].get("bias"))
        return x, aux

    def _layer_stack(self, layers: PyTree, x, pin, *, attn_fn, positions,
                     act_sharding=None):
        """The layers between embedding and final norm: here ONE kind of
        layer, stacked ``[L, ...]`` under one scan. A family whose stack
        holds several kinds overrides this (models/kimi_linear.py; its
        mixer's kernels run per shard of ``act_sharding``, which this one
        needs only as ``pin``).
        Returns (x, summed router aux loss)."""
        c = self.config

        def body(carry, layer_params):
            x, aux = carry
            x, layer_aux = self.block(layer_params, x, attn_fn=attn_fn,
                                      positions=positions)
            return (pin(x), aux + layer_aux), None

        if c.remat and c.remat_policy != "segments":
            # "segments" applies selective checkpoints INSIDE block()
            # (attention outside remat); wrapping the whole body here
            # would rerun the projections it exists to keep
            body = jax.checkpoint(body, prevent_cse=False,
                                  policy=_remat_policy(c.remat_policy))
        (x, aux), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), layers)
        return x, aux

    def _project_vocab(self, params: PyTree, x: jax.Array) -> jax.Array:
        """Vocab projection of already-final-normed hidden states."""
        if self.config.tie_embeddings:
            return x @ params["embed"]["tokens"].T
        if "lm_head_q" in params:   # weight-only int8 serving
            W = (params["lm_head_q"].astype(x.dtype)
                 * params["lm_head_s"].astype(x.dtype))
        else:
            W = params["lm_head"]
        out = x @ W
        if "lm_head_b" in params:   # Phi / GPT-J biased head
            out = out + params["lm_head_b"]
        return out

    def aux_loss_coef(self) -> float:
        return getattr(self.config, "router_aux_loss_coef", 0.0)

    # ---------------- sharding ----------------
    def partition_rules(self) -> Rules:
        """Megatron-style TP rules; the engine overlays fsdp sharding
        (reference TP analogue: module_inject/auto_tp.py row/col split)."""
        return [
            (r"embed/tokens", P("tp", None)),
            (r"embed/positions", P()),
            (r"layers/(wq|wk|wv|w_up|w_gate)$", P(None, None, "tp")),
            (r"layers/(wq_b|wk_b|wv_b|w_up_b|w_gate_b)$", P(None, "tp")),
            (r"layers/(wo|w_down)$", P(None, "tp", None)),
            (r"layers/(wo_b|w_down_b)$", P()),
            (r"layers/ln\d_(scale|bias)", P()),
            (r"final_norm", P()),
            (r"lm_head$", P(None, "tp")),
            (r"lm_head_b$", P("tp")),
        ]


def _remat_policy(name: str):
    """Map a config policy name to a jax.checkpoint policy. Every policy
    keeps what a kernel's forward rule declares (`KEPT_RESIDUAL`: the
    flash kernel's output and row log-sum-exp, O(S) bytes whose rerun is
    the O(S^2) kernel), so ``nothing_saveable`` keeps that and nothing
    else: the backward reruns a layer's norms, projections and MLP, not
    its attention kernel. Besides the stock jax.checkpoint_policies
    names, ``save_attn_ffn`` saves the O(S)-sized per-layer tensors named
    "qkv"/"attn_out"/"ffn" (both the reference attention and the flash
    wrapper name their outputs) — backward then recomputes only norms
    and, with the reference attention, the O(S^2) scores; usually the
    best single-chip throughput point."""
    names = jax.checkpoint_policies.save_only_these_names
    if name == "nothing_saveable":
        return names(KEPT_RESIDUAL)
    if name == "save_attn_ffn":
        return names("qkv", "attn_out", "ffn", KEPT_RESIDUAL)
    return jax.checkpoint_policies.save_from_both_policies(
        getattr(jax.checkpoint_policies, name), names(KEPT_RESIDUAL))


def _unpack_batch(batch):
    if isinstance(batch, dict):
        return batch["tokens"], batch["targets"]
    tokens, targets = batch
    return tokens, targets


# ---------------- chunked cross-entropy ----------------
def _by_chunk(x, targets, chunk):
    """[B, S, D] and [B, S] as scan inputs [S/chunk, B, chunk, ...]."""
    b, s, d = x.shape
    n = s // chunk
    return (x.reshape(b, n, chunk, d).swapaxes(0, 1),
            targets.reshape(b, n, chunk).swapaxes(0, 1))


def _valid_count(targets):
    return jnp.maximum(jnp.sum(targets != -100), 1).astype(jnp.float32)


def _chunk_logits(x_c, t_c, W, bias, rows: bool = False):
    """One slab: the f32 logits [B, chunk, V] of a chunk, their
    logsumexp, the mask and clamped targets, and the chunk's summed NLL
    (same masking contract as ops.layers.cross_entropy_loss); with
    ``rows`` the NLL a row [B, chunk] instead, 0 where masked."""
    logits = (x_c @ W).astype(jnp.float32)
    if bias is not None:
        logits = logits + bias
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    valid = t_c != -100
    safe = jnp.where(valid, t_c, 0)
    tl = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, lse - tl, 0.0)
    return logits, lse, valid, safe, nll if rows else jnp.sum(nll)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _chunked_cross_entropy(x, W, bias, targets, chunk):
    """Mean cross-entropy of hidden states ``x`` [B, S, D] under the head
    ``W`` [D, V] (``x``'s dtype) and f32 ``bias`` [V] or None, one
    [B, chunk, V] slab of f32 logits at a time.

    This body is the primal (eval_batch, anything that asks no gradient):
    a loss-only scan, one vocabulary matmul a chunk. Under differentiation
    the forward rule below computes the gradient in the same scan, while
    the slab is live, so the backward pass holds no vocabulary matmul and
    no logits are recomputed: three vocabulary matmuls a chunk, where
    autodiff of this scan under jax.checkpoint ran four."""
    def body(nll, xs):
        return nll + _chunk_logits(*xs, W, bias)[-1], None

    nll, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                          _by_chunk(x, targets, chunk))
    return nll / _valid_count(targets)


def _chunked_cross_entropy_fwd(x, W, bias, targets, chunk):
    """Loss and, per chunk, dlogits = (softmax - onehot) * valid in
    ``x``'s dtype (as autodiff casts it), dX_c = dlogits @ W^T and
    dW += x_c^T @ dlogits. dW (and db) accumulate in f32 over the chunks.
    The 1/count of the mean and the incoming cotangent (the engine's loss
    scale) are applied together in the backward rule, in f32 before the
    one rounding to the operands' dtype: dlogits stay in [-1, 1], so fp16
    keeps the protection loss scaling gives it."""
    def body(carry, xs):
        nll, dW, db = carry
        x_c, t_c = xs
        logits, lse, valid, safe, nll_c = _chunk_logits(x_c, t_c, W, bias)
        onehot = safe[..., None] == jnp.arange(logits.shape[-1])
        dl = jnp.where(valid[..., None],
                       jnp.exp(logits - lse[..., None]) - onehot, 0.0)
        if bias is not None:
            db = db + dl.sum((0, 1))
        dl = dl.astype(x_c.dtype)
        dx_c = jnp.einsum("bcv,dv->bcd", dl, W)
        dW = dW + jnp.einsum("bcd,bcv->dv", x_c, dl,
                             preferred_element_type=jnp.float32)
        return (nll + nll_c, dW, db), dx_c

    init = (jnp.zeros((), jnp.float32), jnp.zeros(W.shape, jnp.float32),
            None if bias is None else jnp.zeros(bias.shape, jnp.float32))
    (nll, dW, db), dx = jax.lax.scan(body, init,
                                     _by_chunk(x, targets, chunk))
    count = _valid_count(targets)
    return nll / count, (dx.swapaxes(0, 1).reshape(x.shape), dW, db, count)


def _chunked_cross_entropy_bwd(chunk, res, g):
    dx, dW, db, count = res
    k = g / count
    return ((dx * k).astype(dx.dtype), (dW * k).astype(dx.dtype),
            None if db is None else db * k, None)


_chunked_cross_entropy.defvjp(_chunked_cross_entropy_fwd,
                              _chunked_cross_entropy_bwd)


# ---------------- the same, each row with a weight of its own ----------------
def _rows_by_chunk(rows, chunk):
    """[B, S] (weights in, NLL rows out) as the scan's [S/chunk, B, chunk],
    and back."""
    b, s = rows.shape
    return rows.reshape(b, s // chunk, chunk).swapaxes(0, 1)


def _rows_from_chunks(rows):
    n, b, chunk = rows.shape
    return rows.swapaxes(0, 1).reshape(b, n * chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunked_weighted_cross_entropy(x, W, bias, targets, weights, chunk):
    """``(sum(weights * nll) / count, nll)``: the cross-entropy of
    ``_chunked_cross_entropy`` with a float32 weight a row ``[B, S]`` that
    is itself differentiated (a looped stack's exit distribution over its
    passes' rows, stacked along ``B``: models/ouro.py), and beside it the
    rows' own NLL ``[B, S]`` float32, for statistics: NO gradient flows
    through the second result (the caller stops it).

    One call over every pass's rows keeps ONE float32 ``dW`` from forward
    to backward where a call a pass would keep one each. Under
    differentiation the forward rule makes ``dx`` and ``dW`` in the same
    scan with the weights on the slab's ``dlogits`` (in f32, before the
    one rounding), and keeps the rows' NLL for the weights' cotangent,
    ``nll / count``."""
    def body(_, xs):
        x_c, t_c = xs
        return None, _chunk_logits(x_c, t_c, W, bias, rows=True)[-1]

    _, nll = jax.lax.scan(body, None, _by_chunk(x, targets, chunk))
    nll = _rows_from_chunks(nll)
    return jnp.sum(weights * nll) / _valid_count(targets), nll


def _chunked_weighted_cross_entropy_fwd(x, W, bias, targets, weights, chunk):
    def body(carry, xs):
        dW, db = carry
        x_c, t_c, w_c = xs
        logits, lse, valid, safe, nll_c = _chunk_logits(x_c, t_c, W, bias,
                                                        rows=True)
        onehot = safe[..., None] == jnp.arange(logits.shape[-1])
        dl = jnp.where(
            valid[..., None],
            (jnp.exp(logits - lse[..., None]) - onehot) * w_c[..., None],
            0.0)
        if bias is not None:
            db = db + dl.sum((0, 1))
        dl = dl.astype(x_c.dtype)
        dx_c = jnp.einsum("bcv,dv->bcd", dl, W)
        dW = dW + jnp.einsum("bcd,bcv->dv", x_c, dl,
                             preferred_element_type=jnp.float32)
        return (dW, db), (dx_c, nll_c)

    init = (jnp.zeros(W.shape, jnp.float32),
            None if bias is None else jnp.zeros(bias.shape, jnp.float32))
    (dW, db), (dx, nll) = jax.lax.scan(
        body, init, (*_by_chunk(x, targets, chunk),
                     _rows_by_chunk(weights, chunk)))
    nll = _rows_from_chunks(nll)
    count = _valid_count(targets)
    return ((jnp.sum(weights * nll) / count, nll),
            (dx.swapaxes(0, 1).reshape(x.shape), dW, db, count, nll))


def _chunked_weighted_cross_entropy_bwd(chunk, res, g):
    dx, dW, db, count, nll = res
    k = g[0] / count        # g[1], the rows' own cotangent, is not taken
    return ((dx * k).astype(dx.dtype), (dW * k).astype(dx.dtype),
            None if db is None else db * k, None, nll * k)


_chunked_weighted_cross_entropy.defvjp(_chunked_weighted_cross_entropy_fwd,
                                       _chunked_weighted_cross_entropy_bwd)

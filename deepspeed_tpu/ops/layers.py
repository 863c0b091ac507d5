"""Core neural-net ops, jnp reference implementations.

These are the XLA-fused equivalents of the reference's fused CUDA kernels
(``csrc/transformer/*_kernels.cu``: gelu/layernorm/softmax/transform). On
TPU, XLA fuses these elementwise/norm ops into surrounding matmuls; Pallas
variants (deepspeed_tpu/ops/pallas/) replace the ones XLA can't fuse well
(flash attention, quantized collectives, fused optimizers); ``short_conv``
(the recurrent mixers' convolution, SiLU and l2 norm in one pass) is the
thin caller of such a pair, as ``ops/ssd.py`` is of its own, and so are
``gated_short_conv`` and ``gated_norm``.

Everything here is shape-static and jit-safe.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def layer_norm(x, scale, bias, eps: float = 1e-5):
    """LayerNorm in fp32 accumulations regardless of input dtype
    (reference kernel: csrc/transformer/normalize_kernels.cu)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (out * scale + bias).astype(dtype)


def rms_norm(x, scale, eps: float = 1e-6):
    """RMSNorm (reference kernel: csrc/transformer/inference rms_norm.cu)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(dtype)


def gelu(x):
    """tanh-approximated GELU, matching the reference's gelu kernel
    (csrc/transformer/gelu_kernels.cu uses the tanh approximation)."""
    return jax.nn.gelu(x, approximate=True)


def silu(x):
    return jax.nn.silu(x)


def short_conv(x, w, bias=None, *, norm_width: int | None = None,
               norm_scale: float = 1.0):
    """The short convolution of KDA and Mamba-2 with what follows it
    elementwise, one pass over a projection's output: x [B, S, C], taps
    w [n, C], bias [C] or None;

        u_t = sum_i w[i] x_{t-(n-1)+i} (+ bias), zeros before the start
        y = silu(u), and where ``norm_width`` is given
        y = y * rsqrt(sum(y^2) + 1e-6) * norm_scale

    over each run of ``norm_width`` channels (a head's l2 norm). Float32
    inside, rounded once to ``x``'s dtype. A Pallas kernel pair under one
    ``custom_vjp`` (``ops/pallas/short_conv.py``; scope ``ds.conv``);
    ``tests/helpers/short_conv_reference.py`` keeps the ``jax.numpy``
    form the mixers ran until PR 43."""
    from .pallas.short_conv import short_conv as kernels
    return kernels(x, w, bias, norm_width=norm_width, norm_scale=norm_scale)


def sharded_short_conv(act_sharding):
    """``short_conv`` for a multi-device mesh: per shard of the batch
    under a shard_map, because GSPMD cannot partition the kernels' Mosaic
    calls (``parallel.mesh.per_batch_shard``, which see: channels are
    independent and ``act_sharding`` never splits the sequence, so the
    per-shard result is exact; the taps' and the bias's gradients are
    summed over the shards)."""
    from ..parallel.mesh import per_batch_shard
    per_shard = per_batch_shard(short_conv, act_sharding,
                                (True, False, False))

    def conv(x, w, bias=None, **kw):
        if bias is None:
            bias = jnp.zeros(x.shape[2:], w.dtype)
        return per_shard(x, w, bias, **kw)

    return conv


def gated_short_conv(bcx, w):
    """LFM2's gated short convolution, one pass over the input projection's
    output: bcx [B, S, 3 C] = ``[B | Cg | X]`` (three equal column runs),
    taps w [n, C];

        u = B * X;  c_t = sum_i w[i] u_{t-(n-1)+i}, zeros before the start
        y = Cg * c

    with no bias and no activation. Float32 inside, rounded once to
    ``bcx``'s dtype; returns [B, S, C]. A Pallas kernel pair under one
    ``custom_vjp`` (``ops/pallas/short_conv.py``; scope ``ds.gconv_mix``)
    that reads the three runs where they lie and writes the cotangent of
    ``bcx`` as one array; ``tests/helpers/gated_conv_reference.py`` keeps
    the ``jax.numpy`` form."""
    from .pallas.short_conv import gated_short_conv as kernels
    return kernels(bcx, w)


def sharded_gated_short_conv(act_sharding):
    """``gated_short_conv`` for a multi-device mesh: per shard of the batch
    under a shard_map, as ``sharded_short_conv`` and for its reasons (the
    taps' gradient is summed over the shards)."""
    from ..parallel.mesh import per_batch_shard
    return per_batch_shard(gated_short_conv, act_sharding, (True, False))


def gated_norm(o, gate, w, bias=None, *, act: str, eps: float = 1e-6,
               round_norm: bool = False):
    """The gated per-head RMSNorm between a delta-rule scan and the output
    projection, one pass: o [B, S, H, d] or, as ``ops.kda.chunk_kda(
    by_head=True)`` leaves it, the heads' stack [G, B, H / G, S, d]; the
    gate's pre-activation [B, S, H d]; the norm's weight w [d]; bias
    [H d] or None;

        n = o * rsqrt(mean_d(o^2) + eps) * w         a head of d channels
        y = n * act(gate (+ bias))                   act: sigmoid | silu

    The callers say their family's published arithmetic: ``act``, a
    ``bias`` or none, and ``round_norm`` (``n`` passes through ``o``'s
    dtype before the product: Kimi's norm returns its input's dtype;
    Qwen3-Next's stays float32). Float32 inside, rounded once to the
    gate's dtype; returns [B, S, H d]. A Pallas kernel pair under one
    ``custom_vjp`` (``ops/pallas/gated_norm.py``; scope ``ds.mix_post``,
    opened by the op in both directions) whose residuals are its inputs;
    ``tests/helpers/gated_norm_reference.py`` keeps the two ``jax.numpy``
    forms the mixers ran until PR 55."""
    from .pallas.gated_norm import gated_norm as kernels
    return kernels(o, gate, w, bias, act=act, eps=eps, round_norm=round_norm)


def sharded_gated_norm(act_sharding):
    """``gated_norm`` for a multi-device mesh: per shard of the batch under
    a shard_map, as ``sharded_short_conv`` and for its reasons (heads and
    rows are independent; the weight's and the bias's gradients are summed
    over the shards). ``o`` leads with the batch there: [B, S, H, d]."""
    from ..parallel.mesh import per_batch_shard

    def gate_first(gate, o, *rest, **kw):   # the result is laid out as
        return gated_norm(o, gate, *rest, **kw)     # the first argument

    def norm(o, gate, w, bias=None, **kw):
        rest = (w,) if bias is None else (w, bias)
        return per_batch_shard(gate_first, act_sharding,
                               (True, True) + (False,) * len(rest))(
            gate, o, *rest, **kw)

    return norm


def yarn_inv_freq(head_dim: int, theta: float, *, factor: float,
                  original_max_position_embeddings: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0, **_kw):
    """YaRN's frequencies (Peng et al. 2023, "NTK-by-parts"), and the
    ramp's two ends: pair ``i`` of ``head_dim // 2`` turns
    ``original_max_position_embeddings / (2 pi theta^(2i/head_dim))``
    times over the original context. ``c(n)`` is the (real) pair that
    turns ``n`` times; pairs up to ``low = floor(c(beta_fast))`` keep
    their frequency, pairs from ``high = ceil(c(beta_slow))`` on have it
    divided by ``factor``, and between them it is blended linearly.
    Returns (inv_freq [head_dim // 2] float64, low, high)."""
    base = theta ** (-np.arange(0, head_dim, 2) / head_dim)

    def pair_turning(n):
        return (head_dim * math.log(
            original_max_position_embeddings / (n * 2 * math.pi))
            / (2 * math.log(theta)))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return base * (1 - ramp) + base / factor * ramp, low, high


def rotary_embedding(seq_len: int, head_dim: int, theta: float = 10000.0,
                     dtype=jnp.float32, *, scaling: dict | None = None):
    """Precompute RoPE cos/sin tables [seq, rotated // 2]. ``scaling``: one
    section of a published ``rope_parameters`` (``rope_type`` ``default``
    or ``yarn``; its ``rope_theta`` wins over ``theta``). Its
    ``partial_rotary_factor`` (1 where it gives none) says what share of
    the head is ROTATED: the table is built for ``head_dim x factor``
    channels, YaRN's correction range is reckoned on that width, and
    :func:`apply_rotary` rotates the leading channels of a head that is
    wider than the table. A YaRN table is multiplied by
    ``attention_factor`` (``0.1 ln(factor) + 1`` where the section gives
    none), so q k^T carries its square. Another ``rope_type`` (linear,
    dynamic NTK, llama3, longrope) is not built."""
    scaling = dict(scaling or {})
    theta = float(scaling.get("rope_theta", theta))
    kind = scaling.get("rope_type", "default")
    part = scaling.pop("partial_rotary_factor", 1)
    if part != 1:
        head_dim = int(head_dim * part)
        if head_dim <= 0 or head_dim % 2:
            raise ValueError(
                f"partial_rotary_factor {part} leaves {head_dim} rotated "
                f"channels: pairs need an even number")
    scale = 1.0
    if kind == "yarn":
        inv_freq, _, _ = yarn_inv_freq(head_dim, theta, **scaling)
        scale = scaling.get("attention_factor")
        if scale is None:
            scale = 0.1 * math.log(scaling["factor"]) + 1.0
    elif kind == "default":
        inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    else:
        raise NotImplementedError(
            f"rope_type {kind!r}: only 'default' and 'yarn' tables are "
            f"built")
    t = np.arange(seq_len)
    freqs = np.outer(t, inv_freq)
    return (jnp.asarray(np.cos(freqs) * scale, dtype),
            jnp.asarray(np.sin(freqs) * scale, dtype))


def apply_rotary(x, cos, sin, positions=None):
    """Apply rotary embedding. x: [B, S, H, D]; cos/sin: [S_max, R//2] or
    already-sliced [S, R//2]; positions: optional [B, S] int32 for
    decode-time offsets (reference kernel: apply_rotary_pos_emb.cu).
    Where the table is narrower than the head (R < D: a partial rotation,
    :func:`rotary_embedding`) the leading R channels are rotated, pairs
    (i, i + R / 2), and the other D - R pass through."""
    if positions is not None:
        cos = cos[positions]  # [B, S, R//2]
        sin = sin[positions]
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    else:
        s = x.shape[1]
        cos = cos[None, :s, None, :]
        sin = sin[None, :s, None, :]
    rot = 2 * cos.shape[-1]
    if rot > x.shape[-1]:
        raise ValueError(f"a table of {rot} rotated channels for a head of "
                         f"{x.shape[-1]}")
    xr = x if rot == x.shape[-1] else x[..., :rot]
    x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)
    parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if rot < x.shape[-1]:
        parts.append(x[..., rot:].astype(jnp.float32))
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)


def pairs_to_halves(x):
    """The last axis' interleaved pairs ``(x_0, x_1), (x_2, x_3), ...`` laid
    out as halves ``[x_0, x_2, ... | x_1, x_3, ...]``: what
    :func:`apply_rotary`'s pairs ``(i, i + R / 2)`` then rotate is the
    checkpoint's pair ``(x_2i, x_2i+1)`` at ``theta_i`` (a published
    ``rope_interleave``: DeepSeek-V3's rotated channels). Applied to q and
    k alike it leaves their product alone, so nothing lays them back."""
    *lead, d = x.shape
    return jnp.swapaxes(x.reshape(*lead, d // 2, 2), -1, -2).reshape(x.shape)


class RotaryTables(NamedTuple):
    """A model's rotary tables: ``cos``, ``sin`` [S_max, R // 2] as
    :func:`rotary_embedding` builds them, and ``wide`` (``[cos | cos |
    1...]``, ``[-sin | sin | 0...]``, float32 [S_max, D]: a whole head's
    row, what ``ops/pallas/rope.py`` multiplies by) where the head is a
    whole number of 128-lane tiles, else None."""
    cos: jax.Array
    sin: jax.Array
    wide: tuple | None

    @property
    def rotated(self) -> int:
        return 2 * self.cos.shape[-1]


def rotary_tables(cos, sin, head_dim: int) -> RotaryTables:
    """``cos``, ``sin`` with the wide tables of a head of ``head_dim``
    channels, built once where the model builds its tables."""
    rot = 2 * cos.shape[-1]
    if head_dim % 128 or rot > head_dim or cos.dtype != jnp.float32:
        return RotaryTables(cos, sin, None)
    rest = (cos.shape[0], head_dim - rot)
    return RotaryTables(cos, sin, (
        jnp.concatenate([cos, cos, jnp.ones(rest, cos.dtype)], axis=-1),
        jnp.concatenate([-sin, sin, jnp.zeros(rest, sin.dtype)], axis=-1)))


def latent_rotary_tables(cos, sin, pairs: bool = False) -> RotaryTables:
    """``cos``, ``sin`` [S_max, rope // 2] of latent attention's rotated
    channels with the tables ``ops/pallas/rope.py`` ``latent_to_heads``
    multiplies by as ``wide``: a 128-lane tile's row, two heads' 64
    channels side by side, each ``[cos | cos]``, ``[-sin | sin]`` for
    halves or, ``pairs``, ``[c0, c0, c1, c1, ..]``, ``[-s0, s0, -s1, s1,
    ..]`` for a checkpoint's interleaved pairs where they lie. None where
    ``rope`` is not 64 (every published latent attention's) or the tables
    are not float32."""
    rope = 2 * cos.shape[-1]
    if rope != 64 or cos.dtype != jnp.float32:
        return RotaryTables(cos, sin, None)
    if pairs:
        cos_w = jnp.repeat(cos, 2, axis=-1)
        sin_w = jnp.stack([-sin, sin], axis=-1).reshape(cos_w.shape)
    else:
        cos_w = jnp.concatenate([cos, cos], axis=-1)
        sin_w = jnp.concatenate([-sin, sin], axis=-1)
    return RotaryTables(cos, sin, (jnp.tile(cos_w, (1, 2)),
                                   jnp.tile(sin_w, (1, 2))))


def rotate(q, k, tables: RotaryTables, positions=None):
    """q and k through :func:`apply_rotary` under scope ``ds.rope``: the
    XLA form of the rotation (gauge ``ds_rope_calls{form="xla"}``)."""
    from .pallas.rope import count_rotation
    # (cos, sin) alone where the rows' positions are their indices: what
    # the benchmark's controls plant in ``apply_rotary``'s place takes those
    how = (tables.cos, tables.sin) + (
        () if positions is None else (positions,))
    with jax.named_scope("ds.rope"):
        for x in (q, k):
            count_rotation("xla", x.shape[-1], tables.rotated)
        return apply_rotary(q, *how), apply_rotary(k, *how)


def hands_rotary(attn, tables: RotaryTables | None, positions=None) -> bool:
    """Whether ``attn`` is handed unrotated q and k with ``rotary=tables``:
    it says that it rotates (``applies_rotary``, as
    ``ops/pallas/flash_attention.py`` ``flash_attention`` and its
    per-shard wrapper do: they rotate as they lay q and k out for their
    kernels), the head is lane-aligned (``tables.wide``) and the rows'
    positions are their indices (training; decode gathers its own)."""
    while isinstance(attn, functools.partial):
        attn = attn.func
    return (tables is not None and tables.wide is not None
            and positions is None
            and getattr(attn, "applies_rotary", False))


def rotary_attention(attn, q, k, v, tables: RotaryTables, *,
                     positions=None, **kw):
    """``attn`` over the rotated q and k: the tables go to an attention
    that rotates (:func:`hands_rotary`), every other one gets what
    :func:`rotate` returns. One arithmetic either way (float32 products of
    the input and the float32 tables, one rounding to the input's dtype),
    two ways to move the bytes, chosen by what the shapes say."""
    if hands_rotary(attn, tables, positions):
        return attn(q, k, v, rotary=tables, **kw)
    q, k = rotate(q, k, tables, positions)
    return attn(q, k, v, **kw)


# the rotation ``ops/pallas/rope.py`` ``latent_to_heads`` computes: it
# stands in for these two only while the module holds them, so whoever plants
# another rotation in their place (a test, the benchmark's controls) gets
# the XLA form, which calls what was planted
_ROTATION_OF_THE_KERNELS = (apply_rotary, pairs_to_halves)


def hands_latent(attn, q, kv, k_pe, tables: RotaryTables | None,
                 positions=None) -> bool:
    """Whether ``attn`` is handed latent attention's projections as they
    lie (:func:`latent_attention`'s arguments): it says that it takes them
    (``attn.latent``, as ``ops/pallas/flash_attention.py``
    ``flash_attention`` and its per-shard wrapper do), ``nope`` and the
    value are whole 128-lane tiles, ``rope`` is 64 (two heads' fill one)
    with its wide tables built (or nothing is rotated), the heads are even,
    the rows are whole blocks of the flash kernels and their positions are
    their indices."""
    rope = k_pe.shape[-1]
    nope, dv = q.shape[-1] - rope, kv.shape[-1] - (q.shape[-1] - rope)
    return (getattr(attn, "latent", None) is not None and positions is None
            and nope > 0 and nope % 128 == 0 and dv > 0 and dv % 128 == 0
            and rope == 64 and q.shape[2] % 2 == 0 and q.shape[1] % 128 == 0
            and (tables is None or tables.wide is not None)
            and (apply_rotary, pairs_to_halves) == _ROTATION_OF_THE_KERNELS)


def latent_attention(attn, q, kv, k_pe, tables: RotaryTables | None = None,
                     *, pairs: bool = False, positions=None, **kw):
    """``attn`` over latent attention's expanded heads: q [B, S, H,
    nope + rope], kv [B, S, H, nope + dv] (a head is ``[k_nope | v]``) and
    the ONE key k_pe [B, S, rope] all heads share (or as one head,
    [B, S, 1, rope]); ``tables`` over the ``rope`` channels
    (:func:`latent_rotary_tables`; None: nothing is rotated), ``pairs``:
    they come as a checkpoint's interleaved pairs.
    An attention that takes the three as they lie (:func:`hands_latent`)
    rotates and builds its operands itself, one pass; every other one gets

        q_h = [q_nope_h | rot(q_pe_h)],  k_h = [k_nope_h | rot(k_pe)],  v_h

    built here, XLA's form: ``rot`` is :func:`apply_rotary` behind
    :func:`pairs_to_halves` under scope ``ds.rope``. One arithmetic either
    way; the kernels leave interleaved pairs where they lie, q and k
    permuted alike, which their product does not see."""
    from .pallas.rope import count_rotation
    b, s, heads, w = q.shape
    rope = k_pe.shape[-1]
    nope = w - rope
    if hands_latent(attn, q, kv, k_pe, tables, positions):
        return attn.latent(q, kv, k_pe.reshape(b, s, rope), rotary=tables,
                           pairs=pairs, **kw)
    count_rotation("xla", w, 0 if tables is None else rope, 2)  # q and k
    one_head = lambda x: x if x.ndim == 4 else x[:, :, None, :]  # noqa: E731
    if tables is not None:
        how = (tables.cos, tables.sin) + (
            () if positions is None else (positions,))

        def rotated(x):
            return apply_rotary(pairs_to_halves(x) if pairs else x, *how)

        with jax.named_scope("ds.rope"):
            k_pe = rotated(one_head(k_pe))
            q = jnp.concatenate([q[..., :nope], rotated(q[..., nope:])],
                                axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(one_head(k_pe), (b, s, heads, rope))], axis=-1)
    return attn(q, k, kv[..., nope:], **kw)


def window_bias(seq_len: int, window: int):
    """Additive mask for sliding-window attention (Mistral SWA): query i
    sees keys in (i - window, i]. Single source for the model path and
    the flash-kernel fallback."""
    qi = jnp.arange(seq_len)[:, None]
    ki = jnp.arange(seq_len)[None, :]
    return jnp.where(qi - ki < window, 0.0, -1e30)[None, None]


def alibi_slopes(num_heads: int):
    """ALiBi per-head slopes (reference: Bloom containers /
    deepspeed/module_inject — the original train-short-test-long
    geometric schedule). Power-of-two head counts get 2^(-8i/n); others
    interleave the doubled-count schedule like the paper's released
    code."""
    import math

    def pow2(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * start ** i for i in range(n)]

    if math.log2(num_heads).is_integer():
        s = pow2(num_heads)
    else:
        closest = 2 ** int(math.floor(math.log2(num_heads)))
        s = pow2(closest) + pow2(2 * closest)[0::2][: num_heads - closest]
    return jnp.asarray(s, jnp.float32)


def alibi_bias(slopes, seq_len: int):
    """[H, S, S] additive attention bias: slope_h * (k - q) (zero on the
    diagonal, increasingly negative into the past; future positions are
    handled by the causal mask)."""
    pos = jnp.arange(seq_len)
    rel = pos[None, :] - pos[:, None]            # k - q
    return slopes[:, None, None] * rel[None].astype(jnp.float32)


def dot_product_attention(q, k, v, *, causal: bool = True, bias=None,
                          segment_ids=None, softmax_scale: float | None = None):
    """Reference attention: q,k,v [B, S, H, D] (k/v may have fewer heads —
    GQA: H_q % H_kv == 0). Computes in fp32, returns q.dtype.

    This is the jnp fallback; the Pallas flash kernel
    (ops/pallas/flash_attention.py) is numerically interchangeable.
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if softmax_scale is None:
        softmax_scale = 1.0 / np.sqrt(d)
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * softmax_scale
    if bias is not None:
        logits = logits + bias
    mask = None
    if causal:
        qi = jnp.arange(sq)[:, None] + (skv - sq)
        ki = jnp.arange(skv)[None, :]
        mask = qi >= ki
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        seg_mask = seg_mask[:, None, :, :]
        mask = seg_mask if mask is None else (mask[None, None] & seg_mask)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None]
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    # named so selective remat policies can save the O(S)-sized attention
    # output while recomputing the O(S^2) scores in backward
    # (models/transformer.py "save_attn_ffn")
    from jax.ad_checkpoint import checkpoint_name
    out = checkpoint_name(out.astype(q.dtype), "attn_out")
    return out


def cached_attention(q, k_cache, v_cache, index, *,
                     window: int | None = None, alibi_slopes=None):
    """Decode-time attention against a static KV cache (reference:
    csrc/transformer/inference softmax + attention over the
    inference_context.h KV buffers).

    q: [B, S_new, H, D] (the tokens being decoded); k/v_cache:
    [B, S_max, H_kv, D] with positions [0, index + S_new) valid (the new
    tokens' k/v already written at [index, index + S_new)). `index` is a
    traced scalar — the mask keeps shapes static for XLA. ``window``
    restricts each query to its last `window` positions (Mistral SWA).
    """
    b, sq, hq, d = q.shape
    _, smax, hkv, _ = k_cache.shape
    if hq != hkv:
        rep = hq // hkv
        k_cache = jnp.repeat(k_cache, rep, axis=2)
        v_cache = jnp.repeat(v_cache, rep, axis=2)
    scale = 1.0 / np.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache,
                        preferred_element_type=jnp.float32) * scale
    qpos = index + jnp.arange(sq)[:, None]        # absolute q positions
    kpos = jnp.arange(smax)[None, :]
    mask = kpos <= qpos                           # causal over the cache
    if window is not None:
        mask &= kpos > qpos - window
    if alibi_slopes is not None:
        rel = (kpos - qpos).astype(jnp.float32)   # [sq, smax]
        logits = logits + alibi_slopes[None, :, None, None] * rel[None, None]
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v_cache.dtype), v_cache)
    return out.astype(q.dtype)


def cross_entropy_loss(logits, targets, *, ignore_index: int = -100,
                       z_loss: float = 0.0):
    """Mean token cross-entropy in fp32 with optional z-loss.

    logits: [..., V]; targets: [...] int32. Tokens equal to `ignore_index`
    are masked out of the mean.
    """
    logits = logits.astype(jnp.float32)
    valid = targets != ignore_index
    safe_targets = jnp.where(valid, targets, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(
        logits, safe_targets[..., None], axis=-1)[..., 0]
    nll = lse - true_logit
    if z_loss > 0.0:
        nll = nll + z_loss * jnp.square(lse)
    nll = jnp.where(valid, nll, 0.0)
    count = jnp.maximum(jnp.sum(valid), 1)
    return jnp.sum(nll) / count

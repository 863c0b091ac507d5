"""The rotation of q and k and their relayout for the flash kernels as ONE
pass: a Pallas kernel pair under one ``jax.custom_vjp``;
``flash_attention.py`` ``flash_attention(rotary=...)`` is the caller.

``ops/layers.py`` ``apply_rotary`` splits the head in two, multiplies in
float32, concatenates on the minor dimension and casts, and the flash
wrapper then transposes [B, S, H, D] to [B x H, S, D]: a split, a
concatenation and a transpose of the same bytes, which XLA cuts into four
fusions round a float32 copy in HBM (28.8 ms of the Laguna cell's 263.4 ms
step for 5.2 here: ``PERF.md`` section 6, PR 62). Here a head's partner channel comes by a lane roll and the
transpose is the output's index map, so each byte is read once and
written once::

    y = x * cos_w + pair(x) * sin_w                 float32, rounded once
    pair(x)[i] = x[i + R/2]  (i < R/2),   x[i - R/2]  (R/2 <= i < R)
    cos_w = [cos | cos | 1...],  sin_w = [-sin | sin | 0...]      [S, D]

which is ``apply_rotary``'s ``x1 cos - x2 sin | x2 cos + x1 sin`` product
for product (``a + b * (-s)`` is ``a - b * s`` in every bit). Where the
rotated width ``R`` is narrower than the head the channels past it are
copied by a select, never multiplied.

- **Operands** are read where they lie: the projection's output
  [B, S, H x D] (a head is a lane-aligned column run), the float32 tables
  [S, D] that ``ops/layers.py`` ``rotary_tables`` builds once a model, and
  the kernels' [B x H, S, D].
- **Grid** (batch, row tiles, head groups), the head groups innermost: a
  row tile's block of the tables is fetched once for all its heads. A grid
  step takes ``_ROWS`` rows of as many heads as fill ``_WIDTH`` lanes and
  walks them by chunks of ``_CHUNK`` rows in registers, the tables' chunk
  loaded once for the step's heads.
- **Backward** (``ds_rope_bwd``): the same pass the other way, ``dq`` and
  the group-summed ``dk`` [B x H, S, D] in, the projection's cotangent
  [B, S, H x D] out, the rotation inverted (``sin_w`` subtracted). No
  residual but the tables.

No more VMEM than any XLA op gets. Each kernel is traced once a shape
(``_common._bind``). On the chip ``D`` must be a multiple of 128 lanes
(the caller's rule: ``rotary_tables`` builds no wide tables otherwise);
interpret mode takes any width.

**Latent attention** (``latent_to_heads``, PR 65; the kernels
``ds_latent_fwd`` / ``ds_latent_bwd``, the same scope): the three operands
of the flash kernels from the three projections of ``models/stack.py``
``LatentAttention``, where XLA sliced, rotated, concatenated, broadcast and
transposed in a dozen fusions (173.7 ms of the Kanana cell's 1918 ms step
for 41.6 here: ``PERF.md`` section 6, PR 65). A query head is ``[nope | 64
rotated]`` (128 + 64 in every published config: it starts on a lane tile
every second head), a head of the key-value expansion ``[k_nope | v]`` (two
lane-aligned runs), and the ONE key ``k_pe`` [B, S, 64] is shared by all
heads::

    q_h = [q_nope_h | rot(q_pe_h)]      k_h = [k_nope_h | rot(k_pe)]
    v_h = v_h                           each laid out [B x H, S, .]
    rot(x) = x * cos_w + x[lane ^ m] * sin_w       float32, rounded once

``m`` is the partner's lane mask: 32 for halves (``apply_rotary``'s pairs
``(i, i + 32)``), 1 for a checkpoint's interleaved pairs, which are rotated
WHERE THEY LIE (``cos_w = [c0, c0, c1, c1, ..]``, ``sin_w = [-s0, s0, -s1,
s1, ..]``): channel ``2 i`` is ``pairs_to_halves``' channel ``i`` and
``2 i + 1`` its ``32 + i``, bit for bit, and q and k are permuted alike,
which their product does not see. Two heads' rotated channels fill one
128-lane tile, rotated at once; no tables: the same pass with the products
left out. The grid is the pair's (heads innermost: the tables' and
``k_pe``'s blocks are fetched once a row tile); ``rot(k_pe)`` is made once
a chunk of rows and stored behind every head of the step. Backward:
``dq``, ``dk``, ``dv`` [B x H, S, .] in, the three projections' cotangents
out, the rotation inverted, ``dk_pe`` summed over the heads in a float32
scratch that the row tile's last step rotates back and rounds. No residual
but the tables. On a v5e both kernels move their bytes at 80 to 84% of the
chip's 819 GB/s at 32768 rows (``tools/rope_kernel_bench.py latent``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _bind, _interpret, _nbytes, _registry

_ROWS = 512         # rows a grid step, at most
_WIDTH = 1024       # lanes a grid step, at most: the heads a step takes
_CHUNK = 64         # rows a pass in registers, at most
_LANES = 128        # a vector register's minor dimension
# bytes the latent pass's five head blocks may hold in VMEM, both buffers of
# each: 4 heads of 128 + 64 / 128 by 512 rows at bf16 (8.5 MiB); the tables,
# k_pe and the backward's scratch take 1.8 MiB more
_LATENT_BLOCKS = 9 * 2 ** 20
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _geometry(s: int, heads: int, d: int):
    """(rows a grid step, rows a chunk, heads a grid step) from the
    shape."""
    tr = _ROWS
    while tr > 128 and s % tr:
        tr //= 2
    if s % tr:
        tr = s          # interpret mode's short sequences
    rc = _CHUNK if tr % _CHUNK == 0 else tr
    g = max(n for n in range(1, heads + 1)
            if heads % n == 0 and n * d <= max(_WIDTH, d))
    return tr, rc, g


def count_rotation(form: str, head: int, rot: int, n: int = 1):
    """Trace time, host only: gauge ``ds_rope_calls`` counts the rotations
    this process has built in each form, by the head's and the rotated
    width: ``kernel`` (this module's pairs) or ``xla`` (``apply_rotary``).
    Latent attention counts its q and its k at a head of ``nope + rope``,
    ``rotated`` 0 where the family rotates nothing."""
    reg = _registry()
    if reg is not None:
        reg.gauge("ds_rope_calls",
                  "rotations of q or k built so far as a kernel pair "
                  "(form=kernel: ds_rope_fwd / ds_rope_bwd, or latent "
                  "attention's ds_latent_fwd / ds_latent_bwd) or as "
                  "apply_rotary's XLA form (form=xla), by head width and "
                  "rotated width"
                  ).inc(n, form=form, head=str(head), rotated=str(rot))


def _kernel(x_ref, cos_ref, sin_ref, o_ref, *, rc, g, d, rot, to_heads):
    """``g`` heads by one tile of a sequence's rows: [rows, g d] rotated to
    [g, rows, d] (``to_heads``), or back with the rotation inverted."""
    f32 = jnp.float32
    half = rot // 2
    lane = (None if rot == d else
            jax.lax.broadcasted_iota(jnp.int32, (rc, d), 1))

    def chunk(c, _):
        rows = pl.ds(pl.multiple_of(c * rc, rc), rc)
        cos, sin = cos_ref[rows, :], sin_ref[rows, :]
        for i in range(g):
            cols = slice(i * d, (i + 1) * d)
            x = (x_ref[rows, cols] if to_heads else x_ref[i, rows, :]
                 ).astype(f32)
            if lane is None:
                pair = pltpu.roll(x, half, 1)
            else:
                pair = jnp.where(lane < half, pltpu.roll(x, d - half, 1),
                                 pltpu.roll(x, half, 1))
            y = x * cos + pair * sin if to_heads else x * cos - pair * sin
            if lane is not None:
                y = jnp.where(lane < rot, y, x)
            y = y.astype(o_ref.dtype)
            if to_heads:
                o_ref[i, rows, :] = y
            else:
                o_ref[rows, cols] = y
        return 0

    jax.lax.fori_loop(0, x_ref.shape[0 if to_heads else 1] // rc, chunk, 0)


def _call(x, cos, sin, *, heads, rot, to_heads):
    """``to_heads``: x [B, S, H D] to [B H, S, D], rotated; else x
    [B H, S, D] to [B, S, H D], the rotation inverted. cos, sin [>= S, D]
    float32, the wide tables."""
    d = cos.shape[1]
    if to_heads:
        b, s, _ = x.shape
    else:
        s = x.shape[1]
        b = x.shape[0] // heads
    tr, rc, g = _geometry(s, heads, d)
    wide = pl.BlockSpec((None, tr, g * d), lambda b, r, h: (b, r, h))
    stack = pl.BlockSpec((g, tr, d),
                         lambda b, r, h: (b * (heads // g) + h, r, 0))
    table = pl.BlockSpec((tr, d), lambda b, r, h: (r, 0))
    out_shape = jax.ShapeDtypeStruct(
        (b * heads, s, d) if to_heads else (b, s, heads * d), x.dtype)
    name = "ds_rope_fwd" if to_heads else "ds_rope_bwd"
    call = pl.pallas_call(
        functools.partial(_kernel, rc=rc, g=g, d=d, rot=rot,
                          to_heads=to_heads),
        grid=(b, s // tr, heads // g),
        in_specs=[wide if to_heads else stack, table, table],
        out_specs=stack if to_heads else wide,
        out_shape=out_shape,
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=int(3 * x.size), transcendentals=0,
            bytes_accessed=int(_nbytes(x, out_shape)
                               + 2 * b * s * d * cos.dtype.itemsize)),
        interpret=_interpret(),
        name=name,
    )
    # the scope and the kernel's name are all a device trace shows of this
    # call (telemetry/scopes.py); _bind opens ds.rope in the backward too:
    # a custom_vjp's backward function is traced outside the scope its
    # forward was called under
    return _bind(call, "ds.rope", (name, tr, rc, g, heads, rot),
                 x, cos, sin)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rotate(x, cos, sin, heads, rot):
    return _call(x, cos, sin, heads=heads, rot=rot, to_heads=True)


def _rotate_fwd(x, cos, sin, heads, rot):
    return _call(x, cos, sin, heads=heads, rot=rot, to_heads=True), (
        cos, sin)


def _rotate_bwd(heads, rot, tables, dy):
    cos, sin = tables
    dx = _call(dy, cos, sin, heads=heads, rot=rot, to_heads=False)
    return dx, None, None


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def rotate_to_heads(x, wide, rot: int):
    """x [B, S, H, D] rotated by the wide tables ``(cos_w, sin_w)`` [>= S,
    D] float32 over its leading ``rot`` channels, pairs (i, i + rot / 2),
    and laid out [B x H, S, D] for the flash kernels: ``apply_rotary`` and
    the transpose in one pass (the module docstring)."""
    b, s, heads, d = x.shape
    cos, sin = wide
    if (cos.shape != sin.shape or cos.shape[1] != d or cos.shape[0] < s
            or cos.dtype != jnp.float32 or rot % 2 or not 0 < rot <= d):
        raise ValueError(
            f"rotate_to_heads: tables {cos.shape} / {sin.shape} "
            f"{cos.dtype} with {rot} rotated channels for x {x.shape}")
    count_rotation("kernel", d, rot)
    return _rotate(x.reshape(b, s, heads * d), cos, sin, heads, rot)


# ------------------------------------------------ latent attention's operands
_ROPE = _LANES // 2     # the rotated channels of a head: two heads' fill a tile


def _latent_geometry(s: int, heads: int, nope: int, dv: int, itemsize: int):
    """(rows a grid step, rows a chunk, heads a grid step): the pair's rows,
    and as many pairs of heads as keep the five head blocks (q and kv as
    the projections leave them, q, k, v as the flash kernels take them,
    lanes padded to whole tiles, two buffers each) inside
    ``_LATENT_BLOCKS``."""
    tr, rc, _ = _geometry(s, 1, _LANES)
    w = nope + _ROPE
    row = 2 * itemsize * (w + nope + dv + 2 * (w + _ROPE) + dv)
    fits = [n for n in range(2, heads + 1, 2)
            if heads % n == 0 and n * tr * row <= _LATENT_BLOCKS]
    return tr, rc, max(fits, default=2)


def _partner(x, lane, m: int):
    """``x[lane ^ m]`` of a [rows, 128] float32 tile: each rotated channel's
    partner, by two lane rolls and a select."""
    return jnp.where((lane & m) == 0, pltpu.roll(x, _LANES - m, 1),
                     pltpu.roll(x, m, 1))


def _swap(x):
    """A [rows, 128] tile's two halves exchanged. Mosaic rolls 32-bit lanes
    only: two rows of bf16 ride in one, and a lane roll does not see
    rows."""
    if x.dtype.itemsize == 4:
        return pltpu.roll(x, _ROPE, 1)
    return pltpu.bitcast(pltpu.roll(pltpu.bitcast(x, jnp.uint32), _ROPE, 1),
                         x.dtype)


def _latent_kernel(*refs, rc, g, nope, dv, m, to_heads):
    """``g`` heads by one tile of a sequence's rows. ``to_heads``: the
    projections' q [rows, g (nope + 64)], kv [rows, g (nope + dv)] and k_pe
    [rows, 64] to q, k [g, rows, nope + 64] and v [g, rows, dv]; else their
    cotangents back, ``dk_pe`` summed over the row tile's head steps in
    ``acc``. ``m``: the partner's lane mask, None: no rotation (and no
    tables among ``refs``).

    Two heads of q are ``2 n + 1`` lane tiles (``n = nope / 128``)::

        | nope_0 (n tiles) | rope_0 : nope_1 ... (n tiles) ... : rope_1 |

    so every load and store is a whole tile or the 64 lanes at a head's
    tail, head 1's ``nope`` is cut from two neighbours by a select and a
    swap of halves, and ``[rope_0 | rope_1]`` is ONE tile, rotated at
    once."""
    tables = () if m is None else refs[3:5]
    if to_heads:
        q_ref, kv_ref, pe_ref = refs[:3]
        hq_ref, hk_ref, hv_ref = refs[3 + len(tables):]
    else:
        hq_ref, hk_ref, hv_ref = refs[:3]
        q_ref, kv_ref, pe_ref, acc_ref = refs[3 + len(tables):]
    f32 = jnp.float32
    n, wkv = nope // _LANES, nope + dv
    lane = jax.lax.broadcasted_iota(jnp.int32, (rc, _LANES), 1)
    low = lane < _ROPE
    at = lambda k, base=0: slice(  # noqa: E731
        base + k * _LANES, base + (k + 1) * _LANES)
    tail = slice(nope, nope + _ROPE)
    step, last = pl.program_id(2), pl.num_programs(2) - 1

    def turned(x, cos, sin):
        """A tile rotated, or rotated back, in float32."""
        if m is None:
            return x
        y = x.astype(f32)
        pair = _partner(y, lane, m) * sin
        y = y * cos + pair if to_heads else y * cos - pair
        return y.astype(x.dtype)

    def chunk(c, _):
        rows = pl.ds(pl.multiple_of(c * rc, rc), rc)
        cos, sin = (t[rows, :] for t in tables) if tables else (None, None)
        if to_heads:
            # the ONE shared key: rotated once, stored behind every head
            pe = pe_ref[rows, :]
            if m is not None:
                pe = turned(jnp.concatenate([pe, pe], axis=1), cos,
                            sin)[:, :_ROPE]
        else:
            total = jnp.zeros((rc, _LANES), f32)
        for i in range(0, g, 2):
            q0 = i * (nope + _ROPE)
            if to_heads:
                t = [q_ref[rows, at(k, q0)] for k in range(2 * n + 1)]
                y = turned(jnp.where(low, t[n], t[2 * n]), cos, sin)
                for k in range(n):
                    hq_ref[i, rows, at(k)] = t[k]
                    hq_ref[i + 1, rows, at(k)] = _swap(
                        jnp.where(low, t[n + k + 1], t[n + k]))
                hq_ref[i, rows, tail] = y[:, :_ROPE]
                hq_ref[i + 1, rows, tail] = _swap(y)[:, :_ROPE]
            else:
                y = turned(jnp.concatenate(
                    [hq_ref[i, rows, tail], hq_ref[i + 1, rows, tail]],
                    axis=1), cos, sin)
                # head 1's tiles with their halves exchanged, between the
                # two rotated tails
                b = [y] + [_swap(hq_ref[i + 1, rows, at(k)])
                           for k in range(n)] + [y]
                for k in range(n):
                    q_ref[rows, at(k, q0)] = hq_ref[i, rows, at(k)]
                for k in range(n + 1):
                    q_ref[rows, at(n + k, q0)] = jnp.where(
                        low, b[k], b[k + 1])
                total += jnp.concatenate(
                    [hk_ref[i, rows, tail], hk_ref[i + 1, rows, tail]],
                    axis=1).astype(f32)
            for j in (i, i + 1):
                for k in range(n + dv // _LANES):
                    flat = (rows, at(k, j * wkv))
                    ref, where = ((hk_ref, (j, rows, at(k))) if k < n else
                                  (hv_ref, (j, rows, at(k - n))))
                    if to_heads:
                        ref[where] = kv_ref[flat]
                    else:
                        kv_ref[flat] = ref[where]
                if to_heads:
                    hk_ref[j, rows, tail] = pe
        if not to_heads:
            # float32 over the row tile's head steps; the last one folds the
            # tile's two heads, rotates the sum back and rounds it
            total += jnp.where(step == 0, 0.0, acc_ref[rows, :])
            acc_ref[rows, :] = total

            @pl.when(step == last)
            def _():
                pe_ref[rows, :] = turned(total + _swap(total), cos, sin)[
                    :, :_ROPE].astype(pe_ref.dtype)
        return 0

    rows = (q_ref if to_heads else hq_ref).shape[0 if to_heads else 1]
    jax.lax.fori_loop(0, rows // rc, chunk, 0)


def _latent_call(a, b_, c, cos, sin, *, heads, m, to_heads):
    """``to_heads``: (q [B, S, H (nope + 64)], kv [B, S, H (nope + dv)],
    k_pe [B, S, 64]) to (q, k [B H, S, nope + 64], v [B H, S, dv]); else
    the other way, for their cotangents. cos, sin [>= S, 128] float32 where
    ``m`` is a lane mask."""
    if to_heads:
        batch, s, _ = a.shape
        w, wkv = a.shape[-1] // heads, b_.shape[-1] // heads
        dv = wkv - (w - _ROPE)
    else:
        (_, s, w), dv = a.shape, c.shape[-1]
        batch, wkv = a.shape[0] // heads, w - _ROPE + dv
    nope, dt = w - _ROPE, a.dtype
    tr, rc, g = _latent_geometry(s, heads, nope, dv, dt.itemsize)
    wide = lambda n: pl.BlockSpec(  # noqa: E731
        (None, tr, n), lambda b, r, h: (b, r, h))
    stack = lambda n: pl.BlockSpec(  # noqa: E731
        (g, tr, n), lambda b, r, h: (b * (heads // g) + h, r, 0))
    flat = [wide(g * w), wide(g * wkv),
            pl.BlockSpec((None, tr, _ROPE), lambda b, r, h: (b, r, 0))]
    flat_shape = [jax.ShapeDtypeStruct((batch, s, n), dt)
                  for n in (heads * w, heads * wkv, _ROPE)]
    stacks = [stack(w), stack(w), stack(dv)]
    stacks_shape = [jax.ShapeDtypeStruct((batch * heads, s, n), dt)
                    for n in (w, w, dv)]
    tables = [] if m is None else [cos, sin]
    table = pl.BlockSpec((tr, _LANES), lambda b, r, h: (r, 0))
    out_shape = stacks_shape if to_heads else flat_shape
    name = "ds_latent_fwd" if to_heads else "ds_latent_bwd"
    call = pl.pallas_call(
        functools.partial(_latent_kernel, rc=rc, g=g, nope=nope, dv=dv, m=m,
                          to_heads=to_heads),
        grid=(batch, s // tr, heads // g),
        in_specs=(flat if to_heads else stacks) + [table] * len(tables),
        out_specs=stacks if to_heads else flat,
        out_shape=out_shape,
        scratch_shapes=[] if to_heads else [
            pltpu.VMEM((tr, _LANES), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=int(3 * a.size), transcendentals=0,
            bytes_accessed=int(_nbytes(a, b_, c, *out_shape)
                               + len(tables) * batch * s * _LANES * 4)),
        interpret=_interpret(),
        name=name,
    )
    # the rotation's scope, opened in the backward too (``_call``)
    return _bind(call, "ds.rope", (name, tr, rc, g, heads, m),
                 a, b_, c, *tables)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _latent(q, kv, k_pe, cos, sin, heads, m):
    return tuple(_latent_call(q, kv, k_pe, cos, sin, heads=heads, m=m,
                              to_heads=True))


def _latent_fwd(q, kv, k_pe, cos, sin, heads, m):
    return _latent(q, kv, k_pe, cos, sin, heads, m), (cos, sin)


def _latent_bwd(heads, m, tables, cts):
    return (*_latent_call(*cts, *tables, heads=heads, m=m, to_heads=False),
            None, None)


_latent.defvjp(_latent_fwd, _latent_bwd)


def latent_to_heads(q, kv, k_pe, wide=None, *, pairs: bool = False):
    """Latent attention's projections as the flash kernels' operands, one
    pass (the module docstring): q [B, S, H, nope + 64], kv [B, S, H,
    nope + dv] (a head is ``[k_nope | v]``) and the shared k_pe [B, S, 64]
    to q, k [B x H, S, nope + 64] and v [B x H, S, dv]. ``wide``:
    ``ops.layers.latent_rotary_tables``' ``(cos_w, sin_w)`` [>= S, 128]
    float32 for halves or, ``pairs``, for interleaved pairs where they
    lie; None: nothing is rotated. ``nope`` and ``dv`` are whole 128-lane
    tiles, the rotated width is 64 and the heads are even (the caller's
    rule: ``ops.layers.hands_latent``)."""
    b, s, heads, w = q.shape
    nope = w - _ROPE
    if (kv.shape[:3] != q.shape[:3] or k_pe.shape != (b, s, _ROPE)
            or nope <= 0 or nope % _LANES or kv.shape[3] <= nope
            or (kv.shape[3] - nope) % _LANES or heads % 2):
        raise ValueError(
            f"latent_to_heads: q {q.shape}, kv {kv.shape}, k_pe "
            f"{k_pe.shape}")
    cos = sin = m = None
    if wide is not None:
        cos, sin = wide
        m = 1 if pairs else _ROPE // 2
        if (cos.shape != sin.shape or cos.shape[1] != _LANES
                or cos.shape[0] < s or cos.dtype != jnp.float32):
            raise ValueError(
                f"latent_to_heads: tables {cos.shape} / {sin.shape} "
                f"{cos.dtype} for {s} rows")
    count_rotation("kernel", w, 0 if m is None else _ROPE, 2)   # q and k
    return _latent(q.reshape(b, s, heads * w), kv.reshape(b, s, -1), k_pe,
                   cos, sin, heads, m)

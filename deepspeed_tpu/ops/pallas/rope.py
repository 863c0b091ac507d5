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


def count_rotation(form: str, x, rot: int):
    """Trace time, host only: gauge ``ds_rope_calls`` counts the rotations
    this process has built in each form, by the head's and the rotated
    width: ``kernel`` (this pair) or ``xla`` (``apply_rotary``)."""
    reg = _registry()
    if reg is not None:
        reg.gauge("ds_rope_calls",
                  "rotations of q or k built so far as the kernel pair "
                  "ds_rope_fwd / ds_rope_bwd (form=kernel) or as "
                  "apply_rotary's XLA form (form=xla), by head width and "
                  "rotated width"
                  ).inc(form=form, head=str(x.shape[-1]), rotated=str(rot))


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
    count_rotation("kernel", x, rot)
    return _rotate(x.reshape(b, s, heads * d), cos, sin, heads, rot)

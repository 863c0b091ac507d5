"""Manifold-constrained hyper-connections (``ops/mhc.py``, which says the
mathematics and is the caller) as three Pallas kernel pairs, each under one
``jax.custom_vjp`` whose residuals are its inputs: a rematted layer reruns
the forward kernels and nothing is kept.

The streams lie as rows: ``X`` [T, n, C] is read as [T, n C], stream ``i``
the 128-lane-aligned column run ``[i C, (i + 1) C)``; a grid step takes a
tile of rows whole (``n C`` = 14336 channels at the published widths: 28
KiB a row in bf16) and walks it by chunks of rows for everything that is
not a matmul. The n (n + 2) = 24 coefficients of a token ride in ONE
128-lane float32 row (``_LANES``) from kernel to kernel, forward and
backward, with no op of XLA's between: ``phi``, ``b`` and ``alpha`` are
padded to it by the caller below, a coefficient's column is read out of a
chunk by a masked lane sum (``_col``), and the pads are zero columns
throughout.

``ds_mhc_pre_fwd``: one read of a row tile gives ``vec(X) phi`` (n
matmuls [rows, C] x [C, 128] on the MXU, the bf16 operands as they lie),
the mean square, ``raw = alpha (vec(X) phi) rsqrt(ms + eps) + b`` (the
division by the rms after the product, as the paper orders it), ``H_pre =
sigmoid(raw_pre)`` and ``u = sum_i H_pre[i] X[i]``. It writes ``raw``
[T, 128] float32 ``[pre | post | res | 0]`` and ``u`` [T, C]; the forward
rule hands ``X`` itself on as a third result, for the post pass to read.

``ds_mhc_pre_bwd``: makes ``raw`` again; with ``g = draw + (du . X[i])
H_pre (1 - H_pre)`` on the pre columns, ``dz = g alpha``, ``r`` the rsqrt,
``z0 = vec(X) phi`` and ``dX_on`` the cotangent of the ``X`` handed on
(the post pass's ``dX``: read by the row tile ``dX`` is written by, in the
same buffer)::

    dX[i] = dX_on[i] + H_pre[i] du + (dz r) phi_i^T
            - X[i] r^3 (dz . z0) / (n C)      summed in float32, rounded once
    dphi  = vec(X)^T (dz r)        summed in float32 over the row tiles in
                                   ONE output block, as its transpose
                                   [128, n C] (the small operand is the one
                                   transposed)
    db = sum_t g;  dalpha_k = sum_t sum_(column in k) g z0 r
                                   two rows of one [8, 128] block, summed
                                   across the grid like dphi

``ds_mhc_coef_fwd`` (scope ``ds.mhc_coef``): ``raw`` as the pre pass wrote
it -> ``[H_post | H_res | 0]`` as the post pass reads it, ``H_post = 2
sigmoid(raw_post)``, ``H_res`` = ``iters`` Sinkhorn iterations on
``exp(clip(raw_res))``, and the Sinkhorn residual (a [1, 1] block, the
largest of the grid). A tile of 1024 tokens is transposed in VMEM
(``_to_lanes``) so that an ENTRY of ``M`` is one [8, 128] array of tokens,
a whole vector register, and the iteration is elementwise on n n of them
with exact divides. ``ds_mhc_coef_bwd``: runs the forward again keeping
the 2 ``iters`` states of the tile (2.6 MB of VMEM), walks the half steps
back as written (``_half_step_back``) and writes ``draw`` in the pre
pass's layout, zeros in the pre columns and the pads.

``ds_mhc_post_fwd``: ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`` in
one read of ``X`` and ``y``; the coefficients come as one row ``[H_post |
H_res]`` of 128 lanes. ``ds_mhc_post_bwd``: ``dX[j] = sum_i H_res[i, j]
dX'[i]``, ``dy = sum_i H_post[i] dX'[i]``, ``dH_res[i, j] = dX'[i] .
X[j]``, ``dH_post[i] = dX'[i] . y`` in one read of the three; ``dcoef``
goes out as the row the coefficients came in.

Each kernel is traced once a shape (``_common._bind``), under scope
``ds.mhc_pre`` / ``ds.mhc_coef`` / ``ds.mhc_post``, in its backward rule
too. On the chip ``C`` must be a multiple of 128 lanes and ``T`` of 128
tokens; interpret mode (any other backend, the tests) takes any shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _bind, _dot, _interpret, _nbytes, _registry

_LANES = 128
_ROWS = 256         # rows a grid step, at most
_ROWS_PRE_BWD = 128
_COEF_TOKENS = 1024  # tokens a grid step of the coefficient kernels, at most
_CHUNK = 32         # rows a pass outside the matmuls, at most
_VMEM = 100 << 20
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_F32 = jnp.float32


def _fits(n: int):
    if n * (n + 2) > _LANES:
        raise ValueError(
            f"mhc: a token's {n * (n + 2)} coefficients ({n} streams) do "
            f"not fit one row of {_LANES} lanes")


def _geometry(t: int, c: int, rows: int):
    """(rows a grid step, rows a chunk) for ``t`` rows of streams ``c``
    wide."""
    if not _interpret() and (c % _LANES or t % 16):
        raise ValueError(
            f"mhc: on the chip a stream must be a multiple of {_LANES} "
            f"channels and the tokens of 16, not {c} and {t}")
    tr = next((r for r in range(min(rows, t), 0, -1)
               if t % r == 0 and r % 16 == 0), t)
    rc = next((r for r in range(min(_CHUNK, tr), 0, -1)
               if tr % r == 0 and r % 16 == 0), tr)
    return tr, rc


def _params(semantics: str):
    return pltpu.CompilerParams(dimension_semantics=(semantics,),
                                vmem_limit_bytes=_VMEM)


def _lane(rc: int):
    return jax.lax.broadcasted_iota(jnp.int32, (rc, _LANES), 1)


def _col(v, lane, k: int):
    """Column ``k`` of a [rows, 128] value as [rows, 1]."""
    return jnp.sum(jnp.where(lane == k, v, 0.0), axis=-1, keepdims=True)


def _place(cols, lane):
    """[rows, 1] values at the lanes they name, zeros elsewhere."""
    out = jnp.zeros(lane.shape, _F32)
    for k, v in cols.items():
        out = out + jnp.where(lane == k, v, 0.0)
    return out


def _product(x_ref, phi_ref, n: int, c: int):
    """``vec(X) phi`` of a row tile, float32 [rows, 128]."""
    return sum(_dot(x_ref[:, i * c:(i + 1) * c], phi_ref[i * c:(i + 1) * c, :])
               for i in range(n))


# ------------------------------------------------------------ pre, forward
def _pre_fwd_kernel(x_ref, phi_ref, ab_ref, u_ref, raw_ref, *, n, c, rc, eps):
    raw_ref[...] = _product(x_ref, phi_ref, n, c)
    alpha, b = ab_ref[0:1, :], ab_ref[1:2, :]
    lane = _lane(rc)

    def chunk(k, _):
        rows = pl.ds(pl.multiple_of(k * rc, rc), rc)
        xs = [x_ref[rows, i * c:(i + 1) * c].astype(_F32) for i in range(n)]
        ms = sum(jnp.sum(x * x, axis=-1, keepdims=True) for x in xs) / (n * c)
        raw = alpha * (raw_ref[rows, :] * jax.lax.rsqrt(ms + eps)) + b
        raw_ref[rows, :] = raw
        h = jax.nn.sigmoid(raw)
        u_ref[rows, :] = sum(_col(h, lane, i) * xs[i]
                             for i in range(n)).astype(u_ref.dtype)
        return 0

    jax.lax.fori_loop(0, x_ref.shape[0] // rc, chunk, 0)


def _pre_forward(x, phi, ab, *, n, eps):
    """x [T, n C]; phi [n C, 128]; ab [2, 128] float32 (alpha a column,
    then b). -> (raw [T, 128] float32, u [T, C])."""
    t, width = x.shape
    c = width // n
    tr, rc = _geometry(t, c, _ROWS)
    vm = pltpu.VMEM
    out_shape = [jax.ShapeDtypeStruct((t, c), x.dtype),
                 jax.ShapeDtypeStruct((t, _LANES), _F32)]
    call = pl.pallas_call(
        functools.partial(_pre_fwd_kernel, n=n, c=c, rc=rc, eps=eps),
        grid=(t // tr,),
        in_specs=[pl.BlockSpec((tr, width), lambda i: (i, 0), memory_space=vm),
                  pl.BlockSpec((width, _LANES), lambda i: (0, 0),
                               memory_space=vm),
                  pl.BlockSpec((2, _LANES), lambda i: (0, 0),
                               memory_space=vm)],
        out_specs=[pl.BlockSpec((tr, c), lambda i: (i, 0), memory_space=vm),
                   pl.BlockSpec((tr, _LANES), lambda i: (i, 0),
                                memory_space=vm)],
        out_shape=out_shape,
        compiler_params=_params("parallel"),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * t * width * (_LANES + 2)), transcendentals=int(
                t * _LANES),
            bytes_accessed=int(_nbytes(x, phi, ab, *out_shape))),
        interpret=_interpret(),
        name="ds_mhc_pre_fwd",
    )
    u, raw = _bind(call, "ds.mhc_pre", ("mhc_pre_fwd", n, tr, rc, eps),
                   x, phi, ab)
    return raw, u


# ----------------------------------------------------------- pre, backward
def _pre_bwd_kernel(x_ref, phi_ref, ab_ref, du_ref, draw_ref, dxin_ref, dx_ref,
                    sums_ref, dphi_ref, z_s, dz_s, coef_s, t_s, *, n, c, rc,
                    eps):
    tr = x_ref.shape[0]
    alpha, b = ab_ref[0:1, :], ab_ref[1:2, :]
    lane = _lane(rc)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_ref[...] = jnp.zeros(dphi_ref.shape, _F32)
        sums_ref[...] = jnp.zeros(sums_ref.shape, _F32)

    z_s[...] = _product(x_ref, phi_ref, n, c)           # z0

    def coefficients(k, sums):
        rows = pl.ds(pl.multiple_of(k * rc, rc), rc)
        xs = [x_ref[rows, i * c:(i + 1) * c].astype(_F32) for i in range(n)]
        du = du_ref[rows, :].astype(_F32)
        ms = sum(jnp.sum(x * x, axis=-1, keepdims=True) for x in xs) / (n * c)
        r = jax.lax.rsqrt(ms + eps)
        z0 = z_s[rows, :]
        z = z0 * r
        h = jax.nn.sigmoid(alpha * z + b)
        through_u = _place({i: jnp.sum(du * xs[i], axis=-1, keepdims=True)
                            for i in range(n)}, lane)
        g = draw_ref[rows, :] + through_u * h * (1.0 - h)
        dz = g * alpha
        dz_s[rows, :] = (dz * r).astype(dz_s.dtype)
        # H_pre in its own lanes, and at lane n what multiplies X itself
        norm = -(r * r * r) * jnp.sum(dz * z0, axis=-1, keepdims=True) / (
            n * c)
        coef_s[rows, :] = jnp.where(lane < n, h, 0.0) + jnp.where(
            lane == n, norm, 0.0)
        return (sums[0] + jnp.sum(g, axis=0, keepdims=True),
                sums[1] + jnp.sum(g * z, axis=0, keepdims=True))

    zero = jnp.zeros((1, _LANES), _F32)
    by_col = jax.lax.fori_loop(0, tr // rc, coefficients, (zero, zero))
    sums_ref[0:1, :] += by_col[0]                       # db
    sums_ref[1:2, :] += by_col[1]                       # dalpha, by column
    dz = dz_s[...]
    for i in range(n):
        cols = slice(i * c, (i + 1) * c)
        dphi_ref[:, cols] += _dot(dz, x_ref[:, cols], _TN)
        t_s[...] = _dot(dz, phi_ref[cols, :], _NT)

        def stream(k, _):
            rows = pl.ds(pl.multiple_of(k * rc, rc), rc)
            coef = coef_s[rows, :]
            dx = (t_s[rows, :] + dxin_ref[rows, cols].astype(_F32)
                  + _col(coef, lane, i) * du_ref[rows, :].astype(_F32)
                  + _col(coef, lane, n) * x_ref[rows, cols].astype(_F32))
            dx_ref[rows, cols] = dx.astype(dx_ref.dtype)
            return 0

        jax.lax.fori_loop(0, tr // rc, stream, 0)


def _pre_backward(x, phi, ab, du, draw, dx_in, *, n, eps):
    """-> (dx [T, n C] with ``dx_in`` (X's cotangent from its other
    consumer, like x) added before the rounding, in ``dx_in``'s buffer;
    sums [8, 128] float32: row 0 ``sum_t g``, row 1 ``sum_t g z``; dphi^T
    [128, n C] float32)."""
    t, width = x.shape
    c = width // n
    tr, rc = _geometry(t, c, _ROWS_PRE_BWD)
    vm = pltpu.VMEM
    tile = lambda w: pl.BlockSpec(  # noqa: E731
        (tr, w), lambda i: (i, 0), memory_space=vm)
    whole = lambda r, w: pl.BlockSpec(  # noqa: E731
        (r, w), lambda i: (0, 0), memory_space=vm)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype),
                 jax.ShapeDtypeStruct((8, _LANES), _F32),
                 jax.ShapeDtypeStruct((_LANES, width), _F32)]
    call = pl.pallas_call(
        functools.partial(_pre_bwd_kernel, n=n, c=c, rc=rc, eps=eps),
        grid=(t // tr,),
        in_specs=[tile(width), whole(width, _LANES), whole(2, _LANES),
                  tile(c), tile(_LANES), tile(width)],
        out_specs=[tile(width), whole(8, _LANES), whole(_LANES, width)],
        out_shape=out_shape,
        input_output_aliases={5: 0},
        scratch_shapes=[pltpu.VMEM((tr, _LANES), _F32),
                        pltpu.VMEM((tr, _LANES), x.dtype),
                        pltpu.VMEM((tr, _LANES), _F32),
                        pltpu.VMEM((tr, c), _F32)],
        compiler_params=_params("arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * t * width * (3 * _LANES + 7)),
            transcendentals=int(t * _LANES),
            bytes_accessed=int(_nbytes(x, phi, ab, du, draw, dx_in,
                                       *out_shape))),
        interpret=_interpret(),
        name="ds_mhc_pre_bwd",
    )
    return _bind(call, "ds.mhc_pre", ("mhc_pre_bwd", n, tr, rc, eps),
                 x, phi, ab, du, draw, dx_in)


# ----------------------------------------------------------- post, forward
def _post_fwd_kernel(x_ref, y_ref, coef_ref, out_ref, *, n, c, rc):
    lane = _lane(rc)

    def chunk(k, _):
        rows = pl.ds(pl.multiple_of(k * rc, rc), rc)
        coef = coef_ref[rows, :]
        xs = [x_ref[rows, j * c:(j + 1) * c].astype(_F32) for j in range(n)]
        y = y_ref[rows, :].astype(_F32)
        for i in range(n):
            out = _col(coef, lane, i) * y
            for j in range(n):
                out = out + _col(coef, lane, n + i * n + j) * xs[j]
            out_ref[rows, i * c:(i + 1) * c] = out.astype(out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, x_ref.shape[0] // rc, chunk, 0)


def _post_forward(x, y, coef, *, n):
    """x [T, n C]; y [T, C]; coef [T, 128] float32 = [H_post | H_res | 0].
    -> X' [T, n C]."""
    t, width = x.shape
    c = width // n
    tr, rc = _geometry(t, c, _ROWS)
    tile = lambda w: pl.BlockSpec(  # noqa: E731
        (tr, w), lambda i: (i, 0), memory_space=pltpu.VMEM)
    out_shape = jax.ShapeDtypeStruct(x.shape, x.dtype)
    call = pl.pallas_call(
        functools.partial(_post_fwd_kernel, n=n, c=c, rc=rc),
        grid=(t // tr,),
        in_specs=[tile(width), tile(c), tile(_LANES)],
        out_specs=tile(width),
        out_shape=out_shape,
        compiler_params=_params("parallel"),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * t * width * (n + 1)), transcendentals=0,
            bytes_accessed=int(_nbytes(x, y, coef, out_shape))),
        interpret=_interpret(),
        name="ds_mhc_post_fwd",
    )
    return _bind(call, "ds.mhc_post", ("mhc_post_fwd", n, tr, rc),
                 x, y, coef)[0]


# ---------------------------------------------------------- post, backward
def _post_bwd_kernel(x_ref, y_ref, coef_ref, do_ref, dx_ref, dy_ref,
                     dcoef_ref, *, n, c, rc):
    lane = _lane(rc)

    def chunk(k, _):
        rows = pl.ds(pl.multiple_of(k * rc, rc), rc)
        coef = coef_ref[rows, :]
        xs = [x_ref[rows, j * c:(j + 1) * c].astype(_F32) for j in range(n)]
        ds = [do_ref[rows, i * c:(i + 1) * c].astype(_F32) for i in range(n)]
        y = y_ref[rows, :].astype(_F32)
        dot = lambda a, b: jnp.sum(a * b, axis=-1, keepdims=True)  # noqa: E731
        found = {}
        dy = jnp.zeros(y.shape, _F32)
        for i in range(n):
            dy = dy + _col(coef, lane, i) * ds[i]
            found[i] = dot(ds[i], y)
            for j in range(n):
                found[n + i * n + j] = dot(ds[i], xs[j])
        dy_ref[rows, :] = dy.astype(dy_ref.dtype)
        for j in range(n):
            dx = sum(_col(coef, lane, n + i * n + j) * ds[i]
                     for i in range(n))
            dx_ref[rows, j * c:(j + 1) * c] = dx.astype(dx_ref.dtype)
        dcoef_ref[rows, :] = _place(found, lane)
        return 0

    jax.lax.fori_loop(0, x_ref.shape[0] // rc, chunk, 0)


def _post_backward(x, y, coef, do, *, n):
    """-> (dx [T, n C], dy [T, C], dcoef [T, 128] float32)."""
    t, width = x.shape
    c = width // n
    tr, rc = _geometry(t, c, _ROWS)
    tile = lambda w: pl.BlockSpec(  # noqa: E731
        (tr, w), lambda i: (i, 0), memory_space=pltpu.VMEM)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype),
                 jax.ShapeDtypeStruct(y.shape, y.dtype),
                 jax.ShapeDtypeStruct(coef.shape, _F32)]
    call = pl.pallas_call(
        functools.partial(_post_bwd_kernel, n=n, c=c, rc=rc),
        grid=(t // tr,),
        in_specs=[tile(width), tile(c), tile(_LANES), tile(width)],
        out_specs=[tile(width), tile(c), tile(_LANES)],
        out_shape=out_shape,
        compiler_params=_params("parallel"),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * t * width * (n + 1)), transcendentals=0,
            bytes_accessed=int(_nbytes(x, y, coef, do, *out_shape))),
        interpret=_interpret(),
        name="ds_mhc_post_bwd",
    )
    return _bind(call, "ds.mhc_post", ("mhc_post_bwd", n, tr, rc),
                 x, y, coef, do)


# ------------------------------------------------------------ coefficients
def _coef_geometry(t: int, n: int):
    """(tokens a grid step, sublane rows, lanes, a token's n (n + 2)
    coefficients rounded up to whole sublanes): inside the coefficient
    kernels a tile's tokens lie as [rows, lanes] an entry, a vector
    register at 8 x 128."""
    _fits(n)
    kk = -(-n * (n + 2) // 8) * 8
    if t % _LANES:
        if not _interpret():
            raise ValueError(
                f"mhc: on the chip the tokens must be a multiple of "
                f"{_LANES}, not {t}")
        return t, 1, t, kk
    r = next(r for r in (8, 4, 2, 1)
             if r * _LANES <= _COEF_TOKENS and t % (r * _LANES) == 0)
    return r * _LANES, r, _LANES, kk


def _to_lanes(ref, t_s, r: int, kk: int):
    """A tile [r rows, 128] of 128-lane rows as ``t_s`` [r kk, rows]: row
    ``s kk + k`` is column ``k`` of the tile's rows ``[s rows, (s + 1)
    rows)``."""
    rows = ref.shape[0] // r
    for s in range(r):
        t_s[s * kk:(s + 1) * kk, :] = ref[s * rows:(s + 1) * rows, :].T[:kk]


def _from_lanes(t_s, ref, r: int, kk: int):
    """``_to_lanes`` back; the columns from ``kk`` on are zeros."""
    rows = ref.shape[0] // r
    pad = jnp.zeros((_LANES - kk, rows), _F32)
    for s in range(r):
        ref[s * rows:(s + 1) * rows, :] = jnp.concatenate(
            [t_s[s * kk:(s + 1) * kk, :], pad], axis=0).T


def _entry(k: int, r: int, kk: int):
    """Where column ``k`` of every token of the tile lies in a scratch
    ``_to_lanes`` filled: [r, rows], read or written whole."""
    return (pl.ds(k, r, stride=kk) if r > 1 else pl.ds(k, 1)), slice(None)


def _sum(values):
    return functools.reduce(lambda a, b: a + b, values)


def _group(m, a: int, n: int, by_row: bool):
    """Row ``a`` of ``m[i][j]``, or column ``a``."""
    return [m[a][j] for j in range(n)] if by_row else [
        m[i][a] for i in range(n)]


def _half_step(m, n: int, eps: float, by_row: bool):
    """``M / (rowsum(M) + eps)`` or the columns' form; ``m[i][j]`` [r, rows]."""
    d = [_sum(_group(m, a, n, by_row)) + eps for a in range(n)]
    return [[m[i][j] / d[i if by_row else j] for j in range(n)]
            for i in range(n)]


def _half_step_back(g, y, x, n: int, eps: float, by_row: bool):
    """The cotangent of ``x`` where ``y = x / (sum_group(x) + eps)`` and
    ``g`` is ``y``'s: ``(g - sum_group(g y)) / (sum_group(x) + eps)``."""
    d = [_sum(_group(x, a, n, by_row)) + eps for a in range(n)]
    inner = [_sum([p * q for p, q in zip(_group(g, a, n, by_row),
                                         _group(y, a, n, by_row))])
             for a in range(n)]
    return [[(g[i][j] - inner[i if by_row else j]) / d[i if by_row else j]
             for j in range(n)] for i in range(n)]


def _start(t_s, n: int, r: int, kk: int, clamp):
    """exp(clip(raw_res)) of a tile in ``t_s``: ``m[i][j]`` [r, rows]."""
    return [[jnp.exp(jnp.clip(t_s[_entry(2 * n + i * n + j, r, kk)],
                              *clamp))
             for j in range(n)] for i in range(n)]


def _coef_fwd_kernel(raw_ref, coef_ref, res_ref, t_s, *, n, r, kk, eps, clamp,
                     iters):
    _to_lanes(raw_ref, t_s, r, kk)
    entry = functools.partial(_entry, r=r, kk=kk)
    post = [2.0 * jax.nn.sigmoid(t_s[entry(n + i)]) for i in range(n)]
    m = _start(t_s, n, r, kk, clamp)

    def sinkhorn(_, m):
        return _half_step(_half_step(m, n, eps, True), n, eps, False)

    m = jax.lax.fori_loop(0, iters, sinkhorn, m)
    worst = functools.reduce(jnp.maximum, [
        jnp.abs(_sum(_group(m, a, n, by_row)) - 1.0)
        for by_row in (True, False) for a in range(n)])
    worst = jnp.max(jnp.max(worst, axis=0, keepdims=True), axis=1,
                    keepdims=True)

    @pl.when(pl.program_id(0) == 0)
    def _():
        res_ref[...] = jnp.zeros(res_ref.shape, _F32)

    res_ref[...] = jnp.maximum(res_ref[...], worst)
    # the row the post pass reads: [H_post | H_res | 0]
    for i in range(n):
        t_s[entry(i)] = post[i]
        for j in range(n):
            t_s[entry(n + i * n + j)] = m[i][j]
    zero = jnp.zeros(post[0].shape, _F32)
    for k in range(n + n * n, kk):
        t_s[entry(k)] = zero
    _from_lanes(t_s, coef_ref, r, kk)


def _coef_flops(t: int, n: int, iters: int) -> int:
    return int(t * iters * 2 * (2 * n * n + n))


def _coef_forward(raw, *, n, eps, clamp, iters):
    """raw [T, 128] float32 ``[pre | post | res | 0]`` -> (coef [T, 128]
    float32 ``[H_post | H_res | 0]``, the Sinkhorn residual [1, 1])."""
    t = raw.shape[0]
    tt, r, lanes, kk = _coef_geometry(t, n)
    vm = pltpu.VMEM
    tile = pl.BlockSpec((tt, _LANES), lambda i: (i, 0), memory_space=vm)
    out_shape = [jax.ShapeDtypeStruct(raw.shape, _F32),
                 jax.ShapeDtypeStruct((1, 1), _F32)]
    call = pl.pallas_call(
        functools.partial(_coef_fwd_kernel, n=n, r=r, kk=kk, eps=eps,
                          clamp=clamp, iters=iters),
        grid=(t // tt,),
        in_specs=[tile],
        out_specs=[tile, pl.BlockSpec((1, 1), lambda i: (0, 0),
                                      memory_space=vm)],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((r * kk, lanes), _F32)],
        compiler_params=_params("arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=_coef_flops(t, n, iters),
            transcendentals=int(t * n * (n + 1)),
            bytes_accessed=int(_nbytes(raw, *out_shape))),
        interpret=_interpret(),
        name="ds_mhc_coef_fwd",
    )
    return _bind(call, "ds.mhc_coef",
                 ("mhc_coef_fwd", n, tt, eps, clamp, iters), raw)


def _coef_bwd_kernel(raw_ref, dcoef_ref, draw_ref, t_s, d_s, kept_s, *, n, r,
                     kk, eps, clamp, iters):
    _to_lanes(raw_ref, t_s, r, kk)
    _to_lanes(dcoef_ref, d_s, r, kk)
    entry = functools.partial(_entry, r=r, kk=kk)
    nn = n * n
    flat = lambda m: [v for row in m for v in row]  # noqa: E731
    square = lambda vs: [list(vs[i * n:(i + 1) * n])  # noqa: E731
                         for i in range(n)]

    def keep(at, m):
        for e, v in enumerate(flat(m)):
            kept_s[at * nn + e] = v

    def kept(at):
        return square([kept_s[at * nn + e] for e in range(nn)])

    # the forward again: state 2 i is what iteration i starts from, state
    # 2 i + 1 what its rows' division leaves
    def sinkhorn(i, m):
        keep(2 * i, m)
        m = _half_step(m, n, eps, True)
        keep(2 * i + 1, m)
        return _half_step(m, n, eps, False)

    m = jax.lax.fori_loop(0, iters, sinkhorn, _start(t_s, n, r, kk, clamp))

    # and back, step by step as written
    def back(step, carry):
        i = iters - 1 - step
        g, y = carry
        x = kept(2 * i + 1)
        g = _half_step_back(g, y, x, n, eps, False)
        y, x = x, kept(2 * i)
        return _half_step_back(g, y, x, n, eps, True), x

    g = square([d_s[entry(n + e)] for e in range(nn)])
    g, start = jax.lax.fori_loop(0, iters, back, (g, m))
    zero = jnp.zeros(start[0][0].shape, _F32)
    for i in range(n):
        sig = jax.nn.sigmoid(t_s[entry(n + i)])
        dpost = d_s[entry(i)] * (2.0 * sig * (1.0 - sig))
        for j in range(n):
            k = 2 * n + i * n + j
            raw = t_s[entry(k)]
            inside = (raw >= clamp[0]) & (raw <= clamp[1])
            t_s[entry(k)] = jnp.where(inside, g[i][j] * start[i][j], 0.0)
        t_s[entry(n + i)] = dpost
        t_s[entry(i)] = zero
    for k in range(n * (n + 2), kk):
        t_s[entry(k)] = zero
    _from_lanes(t_s, draw_ref, r, kk)


def _coef_backward(raw, dcoef, *, n, eps, clamp, iters):
    """-> draw [T, 128] float32, zeros in the pre columns and the pads: the
    gradient of the ``iters`` unrolled iterations, walked back over the
    states a token tile's rerun keeps in VMEM."""
    t = raw.shape[0]
    tt, r, lanes, kk = _coef_geometry(t, n)
    tile = pl.BlockSpec((tt, _LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    out_shape = jax.ShapeDtypeStruct(raw.shape, _F32)
    call = pl.pallas_call(
        functools.partial(_coef_bwd_kernel, n=n, r=r, kk=kk, eps=eps,
                          clamp=clamp, iters=iters),
        grid=(t // tt,),
        in_specs=[tile, tile],
        out_specs=tile,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((r * kk, lanes), _F32),
                        pltpu.VMEM((r * kk, lanes), _F32),
                        pltpu.VMEM((2 * iters * n * n, r, lanes), _F32)],
        compiler_params=_params("parallel"),
        cost_estimate=pl.CostEstimate(
            flops=4 * _coef_flops(t, n, iters),
            transcendentals=int(t * n * (n + 1)),
            bytes_accessed=int(_nbytes(raw, dcoef, out_shape))),
        interpret=_interpret(),
        name="ds_mhc_coef_bwd",
    )
    return _bind(call, "ds.mhc_coef",
                 ("mhc_coef_bwd", n, tt, eps, clamp, iters), raw, dcoef)[0]


# ------------------------------------------------------------------ public
def _padded(v, width: int = _LANES):
    """The last axis zero-padded to ``width`` lanes, float32."""
    return jnp.pad(v.astype(_F32),
                   [(0, 0)] * (v.ndim - 1) + [(0, width - v.shape[-1])])


def _pre_operands(x, phi, b, alpha):
    from ..mhc import expand_alpha
    t, n, c = x.shape
    _fits(n)
    ab = jnp.stack([_padded(expand_alpha(alpha, n)), _padded(b)])
    return (x.reshape(t, n * c),
            jnp.pad(phi.astype(x.dtype), ((0, 0), (0, _LANES - phi.shape[1]))),
            ab)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def mhc_pre(x, phi, b, alpha, eps):
    """x [T, n, C]; phi [n C, n (n + 2)]; b [n (n + 2)]; alpha [3] ->
    (raw [T, 128] float32: ``ops/mhc.py`` ``pre_reference``'s in the
    leading n (n + 2) columns, zeros behind them; u [T, C] in x's dtype;
    x itself, handed on). Whoever else reads the streams reads the third
    result: ``x`` then has this one consumer, and the cotangent of every
    other use comes to ``ds_mhc_pre_bwd`` to be added inside it."""
    return _mhc_pre_fwd(x, phi, b, alpha, eps)[0]


def _mhc_pre_fwd(x, phi, b, alpha, eps):
    raw, u = _pre_forward(*_pre_operands(x, phi, b, alpha), n=x.shape[1],
                          eps=eps)
    return (raw, u, x), (x, phi, b, alpha)


def _mhc_pre_bwd(eps, inputs, cotangents):
    x, phi, b, alpha = inputs
    draw, du, dx_on = cotangents
    t, n, c = x.shape
    k = phi.shape[1]
    dx_on = dx_on.reshape(t, n * c).astype(x.dtype)
    reg = _registry()
    if reg is not None:         # trace time, host only
        reg.gauge("ds_mhc_handed_on_bytes",
                  "bytes of the streams' cotangent from their other "
                  "consumer that one call of ds_mhc_pre_bwd last traced "
                  "takes in and adds inside the kernel"
                  ).set(_nbytes(dx_on))
    dx, sums, dphi_t = _pre_backward(
        *_pre_operands(x, phi, b, alpha), du.astype(x.dtype),
        draw.astype(_F32), dx_on, n=n, eps=eps)
    by_group = sums[1, :k]
    dalpha = jnp.stack([jnp.sum(by_group[:n]), jnp.sum(by_group[n:2 * n]),
                        jnp.sum(by_group[2 * n:])])
    return (dx.reshape(x.shape), dphi_t[:k].T.astype(phi.dtype),
            sums[0, :k].astype(b.dtype), dalpha.astype(alpha.dtype))


mhc_pre.defvjp(_mhc_pre_fwd, _mhc_pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def coefficients(raw, n, eps, clamp, iters):
    """``raw`` [T, 128] float32 as ``ds_mhc_pre_fwd`` wrote it -> (the row
    ``ds_mhc_post_*`` read, [T, 128] float32 ``[H_post | H_res | 0]``; the
    Sinkhorn residual, a float32 scalar with no gradient): ``ops/mhc.py``
    ``coefficients``, the same arithmetic."""
    return _coefficients_fwd(raw, n, eps, clamp, iters)[0]


def _coefficients_fwd(raw, n, eps, clamp, iters):
    coef, residual = _coef_forward(raw, n=n, eps=eps, clamp=clamp,
                                   iters=iters)
    return (coef, residual.reshape(())), raw


def _coefficients_bwd(n, eps, clamp, iters, raw, cotangents):
    return (_coef_backward(raw, cotangents[0].astype(_F32), n=n, eps=eps,
                           clamp=clamp, iters=iters),)


coefficients.defvjp(_coefficients_fwd, _coefficients_bwd)


def coefficient_row(h_post, h_res):
    """h_post [T, n] and h_res [T, n n] as the row the post pass reads,
    for a caller whose coefficients are not ``coefficients``' own."""
    return _padded(jnp.concatenate([h_post, h_res], axis=-1))


@jax.custom_vjp
def mhc_post(x, y, coef):
    """x [T, n, C]; y [T, C]; coef [T, 128] float32 ``[H_post | H_res |
    0]`` -> X' like x: ``ops/mhc.py`` ``post_reference``."""
    return _mhc_post_fwd(x, y, coef)[0]


def _mhc_post_fwd(x, y, coef):
    t, n, c = x.shape
    out = _post_forward(x.reshape(t, n * c), y, coef, n=n)
    return out.reshape(x.shape), (x, y, coef)


def _mhc_post_bwd(inputs, do):
    x, y, coef = inputs
    t, n, c = x.shape
    dx, dy, dcoef = _post_backward(
        x.reshape(t, n * c), y, coef, do.reshape(t, n * c).astype(x.dtype),
        n=n)
    return dx.reshape(x.shape), dy, dcoef


mhc_post.defvjp(_mhc_post_fwd, _mhc_post_bwd)

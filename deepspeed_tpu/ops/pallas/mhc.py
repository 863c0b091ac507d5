"""The two stream passes of manifold-constrained hyper-connections
(``ops/mhc.py``, which says the mathematics and is the caller) as Pallas
kernel pairs, each under one ``jax.custom_vjp`` whose residuals are its
inputs: a rematted layer reruns the forward kernels and nothing is kept.

The streams lie as rows: ``X`` [T, n, C] is read as [T, n C], stream ``i``
the 128-lane-aligned column run ``[i C, (i + 1) C)``; a grid step takes a
tile of rows whole (``n C`` = 14336 channels at the published widths: 28
KiB a row in bf16) and walks it by chunks of rows for everything that is
not a matmul. The n (n + 2) = 24 coefficients of a token ride in ONE
128-lane float32 row (``_LANES``): ``phi``, ``b`` and ``alpha`` are padded
to it by the caller below, a coefficient's column is read out of a chunk by
a masked lane sum (``_col``), and the pads are zero columns throughout.

``ds_mhc_pre_fwd``: one read of a row tile gives ``vec(X) phi`` (n
matmuls [rows, C] x [C, 128] on the MXU, the bf16 operands as they lie),
the mean square, ``raw = alpha (vec(X) phi) rsqrt(ms + eps) + b`` (the
division by the rms after the product, as the paper orders it), ``H_pre =
sigmoid(raw_pre)`` and ``u = sum_i H_pre[i] X[i]``. It writes ``raw``
[T, 128] float32 and ``u`` [T, C].

``ds_mhc_pre_bwd``: makes ``raw`` again; with ``g = draw + (du . X[i])
H_pre (1 - H_pre)`` on the pre columns, ``dz = g alpha``, ``r`` the rsqrt
and ``z0 = vec(X) phi``::

    dX[i] = H_pre[i] du + (dz r) phi_i^T - X[i] r^3 (dz . z0) / (n C)
    dphi  = vec(X)^T (dz r)        summed in float32 over the row tiles in
                                   ONE output block, as its transpose
                                   [128, n C] (the small operand is the one
                                   transposed)

and hands ``g`` and ``z = z0 r`` back ([T, 128] each): ``db`` and
``dalpha`` are their sums, taken by the caller.

``ds_mhc_post_fwd``: ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`` in
one read of ``X`` and ``y``; the coefficients come as one row ``[H_post |
H_res]`` of 128 lanes. ``ds_mhc_post_bwd``: ``dX[j] = sum_i H_res[i, j]
dX'[i]``, ``dy = sum_i H_post[i] dX'[i]``, ``dH_res[i, j] = dX'[i] .
X[j]``, ``dH_post[i] = dX'[i] . y`` in one read of the three.

Each kernel is traced once a shape (``_common._bind``), under scope
``ds.mhc_pre`` / ``ds.mhc_post``, in its backward rule too. On the chip
``C`` must be a multiple of 128 lanes and ``T`` of 16 rows; interpret mode
(any other backend, the tests) takes any shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _bind, _dot, _interpret, _nbytes

_LANES = 128
_ROWS = 256         # rows a grid step, at most
_ROWS_PRE_BWD = 128
_CHUNK = 32         # rows a pass outside the matmuls, at most
_VMEM = 100 << 20
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_F32 = jnp.float32


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
def _pre_bwd_kernel(x_ref, phi_ref, ab_ref, du_ref, draw_ref, dx_ref, g_ref,
                    z_ref, dphi_ref, dz_s, coef_s, t_s, *, n, c, rc, eps):
    tr = x_ref.shape[0]
    alpha, b = ab_ref[0:1, :], ab_ref[1:2, :]
    lane = _lane(rc)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_ref[...] = jnp.zeros(dphi_ref.shape, _F32)

    z_ref[...] = _product(x_ref, phi_ref, n, c)         # z0, for now

    def coefficients(k, _):
        rows = pl.ds(pl.multiple_of(k * rc, rc), rc)
        xs = [x_ref[rows, i * c:(i + 1) * c].astype(_F32) for i in range(n)]
        du = du_ref[rows, :].astype(_F32)
        ms = sum(jnp.sum(x * x, axis=-1, keepdims=True) for x in xs) / (n * c)
        r = jax.lax.rsqrt(ms + eps)
        z0 = z_ref[rows, :]
        z = z0 * r
        h = jax.nn.sigmoid(alpha * z + b)
        through_u = _place({i: jnp.sum(du * xs[i], axis=-1, keepdims=True)
                            for i in range(n)}, lane)
        g = draw_ref[rows, :] + through_u * h * (1.0 - h)
        dz = g * alpha
        g_ref[rows, :] = g
        z_ref[rows, :] = z
        dz_s[rows, :] = (dz * r).astype(dz_s.dtype)
        # H_pre in its own lanes, and at lane n what multiplies X itself
        norm = -(r * r * r) * jnp.sum(dz * z0, axis=-1, keepdims=True) / (
            n * c)
        coef_s[rows, :] = jnp.where(lane < n, h, 0.0) + jnp.where(
            lane == n, norm, 0.0)
        return 0

    jax.lax.fori_loop(0, tr // rc, coefficients, 0)
    dz = dz_s[...]
    for i in range(n):
        cols = slice(i * c, (i + 1) * c)
        dphi_ref[:, cols] += _dot(dz, x_ref[:, cols], _TN)
        t_s[...] = _dot(dz, phi_ref[cols, :], _NT)

        def stream(k, _):
            rows = pl.ds(pl.multiple_of(k * rc, rc), rc)
            coef = coef_s[rows, :]
            dx = (t_s[rows, :]
                  + _col(coef, lane, i) * du_ref[rows, :].astype(_F32)
                  + _col(coef, lane, n) * x_ref[rows, cols].astype(_F32))
            dx_ref[rows, cols] = dx.astype(dx_ref.dtype)
            return 0

        jax.lax.fori_loop(0, tr // rc, stream, 0)


def _pre_backward(x, phi, ab, du, draw, *, n, eps):
    """-> (dx [T, n C], g and z [T, 128] float32, dphi^T [128, n C]
    float32)."""
    t, width = x.shape
    c = width // n
    tr, rc = _geometry(t, c, _ROWS_PRE_BWD)
    vm = pltpu.VMEM
    tile = lambda w: pl.BlockSpec(  # noqa: E731
        (tr, w), lambda i: (i, 0), memory_space=vm)
    whole = lambda r, w: pl.BlockSpec(  # noqa: E731
        (r, w), lambda i: (0, 0), memory_space=vm)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype),
                 jax.ShapeDtypeStruct((t, _LANES), _F32),
                 jax.ShapeDtypeStruct((t, _LANES), _F32),
                 jax.ShapeDtypeStruct((_LANES, width), _F32)]
    call = pl.pallas_call(
        functools.partial(_pre_bwd_kernel, n=n, c=c, rc=rc, eps=eps),
        grid=(t // tr,),
        in_specs=[tile(width), whole(width, _LANES), whole(2, _LANES),
                  tile(c), tile(_LANES)],
        out_specs=[tile(width), tile(_LANES), tile(_LANES),
                   whole(_LANES, width)],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((tr, _LANES), x.dtype),
                        pltpu.VMEM((tr, _LANES), _F32),
                        pltpu.VMEM((tr, c), _F32)],
        compiler_params=_params("arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * t * width * (3 * _LANES + 6)),
            transcendentals=int(t * _LANES),
            bytes_accessed=int(_nbytes(x, phi, ab, du, draw, *out_shape))),
        interpret=_interpret(),
        name="ds_mhc_pre_bwd",
    )
    return _bind(call, "ds.mhc_pre", ("mhc_pre_bwd", n, tr, rc, eps),
                 x, phi, ab, du, draw)


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


# ------------------------------------------------------------------ public
def _padded(v, width: int = _LANES):
    """The last axis zero-padded to ``width`` lanes, float32."""
    return jnp.pad(v.astype(_F32),
                   [(0, 0)] * (v.ndim - 1) + [(0, width - v.shape[-1])])


def _pre_operands(x, phi, b, alpha):
    from ..mhc import expand_alpha
    t, n, c = x.shape
    ab = jnp.stack([_padded(expand_alpha(alpha, n)), _padded(b)])
    return (x.reshape(t, n * c),
            jnp.pad(phi.astype(x.dtype), ((0, 0), (0, _LANES - phi.shape[1]))),
            ab)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def mhc_pre(x, phi, b, alpha, eps):
    """x [T, n, C]; phi [n C, n (n + 2)]; b [n (n + 2)]; alpha [3] ->
    (raw [T, n (n + 2)] float32, u [T, C] in x's dtype): ``ops/mhc.py``
    ``pre_reference``."""
    return _mhc_pre_fwd(x, phi, b, alpha, eps)[0]


def _mhc_pre_fwd(x, phi, b, alpha, eps):
    raw, u = _pre_forward(*_pre_operands(x, phi, b, alpha), n=x.shape[1],
                          eps=eps)
    return (raw[:, :phi.shape[1]], u), (x, phi, b, alpha)


def _mhc_pre_bwd(eps, inputs, cotangents):
    x, phi, b, alpha = inputs
    draw, du = cotangents
    t, n, c = x.shape
    k = phi.shape[1]
    dx, g, z, dphi_t = _pre_backward(
        *_pre_operands(x, phi, b, alpha), du.astype(x.dtype), _padded(draw),
        n=n, eps=eps)
    g, z = g[:, :k], z[:, :k]
    by_group = jnp.sum(g * z, axis=0)
    dalpha = jnp.stack([jnp.sum(by_group[:n]), jnp.sum(by_group[n:2 * n]),
                        jnp.sum(by_group[2 * n:])])
    return (dx.reshape(x.shape), dphi_t[:k].T.astype(phi.dtype),
            jnp.sum(g, axis=0).astype(b.dtype), dalpha.astype(alpha.dtype))


mhc_pre.defvjp(_mhc_pre_fwd, _mhc_pre_bwd)


def _post_operands(x, y, h_post, h_res):
    t, n, c = x.shape
    return (x.reshape(t, n * c), y,
            _padded(jnp.concatenate([h_post, h_res], axis=-1)))


@jax.custom_vjp
def mhc_post(x, y, h_post, h_res):
    """x [T, n, C]; y [T, C]; h_post [T, n] and h_res [T, n n] float32 ->
    X' like x: ``ops/mhc.py`` ``post_reference``."""
    return _mhc_post_fwd(x, y, h_post, h_res)[0]


def _mhc_post_fwd(x, y, h_post, h_res):
    out = _post_forward(*_post_operands(x, y, h_post, h_res), n=x.shape[1])
    return out.reshape(x.shape), (x, y, h_post, h_res)


def _mhc_post_bwd(inputs, do):
    x, y, h_post, h_res = inputs
    t, n, c = x.shape
    dx, dy, dcoef = _post_backward(
        *_post_operands(x, y, h_post, h_res),
        do.reshape(t, n * c).astype(x.dtype), n=n)
    return (dx.reshape(x.shape), dy, dcoef[:, :n].astype(h_post.dtype),
            dcoef[:, n:n + n * n].astype(h_res.dtype))


mhc_post.defvjp(_mhc_post_fwd, _mhc_post_bwd)

"""The gated delta rule in its chunked form, with either gate: Kimi Delta
Attention (KDA: a decay per key CHANNEL; Kimi-Linear,
``linear_attn_config``) and Gated DeltaNet (a decay per HEAD; Qwen3-Next,
``linear_*`` keys).

Per head, with a float32 state ``S`` [dk, dv], ``S_0 = 0``::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t <= 0`` is the log-decay, ``beta_t`` in (0, 1). KDA's ``g`` is
[B, S, H, dk], a number a key channel; Gated DeltaNet's is [B, S, H], a
number a head: the same recurrence with ``Diag(exp(g_t)) = exp(g_t) I``.
Every function here takes either. With one number a head a chunk's decay
is ONE [C, C] mask ``D_ij = exp(G_i - G_j)`` on the plain score matrices
(``A = (K K^T * D) beta``), its exponent never positive: the preparation's
kernels build that in place of the row blocks below (``ops/pallas/kda.py``
``_HeadChunk``), exact at any decay; the recurrence's kernels are the
same (``shrink`` is the chunk's one number on every channel).
``recurrent_kda`` is the recurrence token by token (tests only).
``chunk_kda`` is what the models run: chunks of ``CHUNK`` tokens, inside a
chunk with incoming state ``S`` and ``G_r = sum_{i<=r} g_i``::

    A_ij = beta_j <k_i * exp(G_i - G_j), k_j>   (j < i, else 0)
    U    = (I + A)^-1 (V - (K * exp(G)) S)
    o_r  = S^T (q_r * exp(G_r)) + sum_{j<=r} beta_j <q_r * exp(G_r - G_j), k_j> u_j
    S'   = Diag(exp(G_C)) S + sum_j (k_j * exp(G_C - G_j)) beta_j u_j^T

Everything that does not need ``S`` (both score matrices, the inverse
``T = (I + A)^-1``, ``T V``, ``T (K * exp(G))`` and the decay products) is
the PREPARATION: a Pallas kernel pair under one ``custom_vjp``
(``ops/pallas/kda.py`` ``kda_prepare``) builds it chunk by chunk in VMEM
from q, k, v, g and beta, which it reads once in the model's layout, and
writes six operands (the inverse's float32 products two heads to a
product 128 lanes wide, PR 44); its backward rebuilds a chunk's forward
from the same five inputs, its only residuals. What needs ``S`` is serial
in the chunks and runs in a second kernel pair under its own ``custom_vjp``
(``kda_recurrence``): the forward carries ``S`` in VMEM across the chunks
and writes ``o`` alone; the backward rebuilds the states by segments of
``SEG`` chunks from float32 segment checkpoints and carries ``dS`` in
VMEM. No score matrix, inverse, state history or per-chunk residual
reaches HBM; the six operands cross it once each way. (Until PR 32 the
recurrence was a ``lax.scan`` under autodiff, until PR 35 the preparation
``jax.numpy`` under autodiff: ``tests/helpers/kda_reference.py`` keeps
that form as the kernels' reference.)

A layer of the Kimi cell (``train-kda-s16k-1chip``: four KDA layers of 32
heads in four head groups) runs the preparation's forward twice (the
forward, and the head group's own checkpoint in the backward) and its
backward once; ``ds_kda_fwd`` once, its checkpoint form once and
``ds_kda_bwd`` once (``PERF.md`` section 5 has their times). The layer's
own remat does not run the scan again: ``chunk_kda`` declares its ``o``
kept (``_kept``), 2 B S H dv bytes a layer (134 MB) alive across the
backward, and the rerun lacked nothing else (until PR 51 it ran both
forward kernels a third time). A layer of the Qwen3-Next cell
(``train-gdn-s16k-1chip``: three Gated DeltaNet layers of 32 value heads in
ONE group) keeps nothing and reruns the scan: the compiled step holds the
preparation's forward twice a layer all the same (with no loop round it
XLA merges the layer's rerun of it with the group's) and ``ds_kda_fwd``
twice and its checkpoint form once. The operand shapes are the same in
both cells (dk = dv = 128, 16384 tokens); the second reads its gate as
rows, a number a token, as both read beta.

A gate a CHANNEL cannot be a mask: its decay rides inside the products.
``exp(G_i - G_j) <= 1``, but ``exp(G_i) * exp(-G_j)`` overflows float32
where a channel decays fast (a log-decay of -1.6 a token is -100 over a
chunk). So the score matrices are built by row blocks of ``SUB`` rows,
each factored about the block's own first row, with the exponent of a
block's own columns held to ``CLAMP`` and an exact diagonal, and the
inverse of the unit lower triangle is exact block elimination in two
finite Neumann products: ``ops/pallas/kda.py`` says how, and why 8 rows
(a training run on the chip went NaN at 16: PR 31).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .pallas._common import _keep
from .pallas.kda import CHUNK, kda_prepare, kda_recurrence


def recurrent_kda(q, k, v, g, beta):
    """The recurrence, token by token. q, k [B, S, Hk, dk]; v [B, S, H,
    dv]; g [B, S, H, dk] or [B, S, H] (log-decay a channel, a head); beta
    [B, S, H]. ``H`` is a multiple of ``Hk``: a key head serves ``H / Hk``
    consecutive value heads (repeated here, in float32). Returns o
    [B, S, H, dv] float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    if g.ndim == 3:
        g = g[..., None]
    b, _, h, dv = v.shape
    q, k = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (q, k))
    dk = q.shape[-1]

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None]       # [B, H, dk | 1, 1]
        u = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t * b_t[..., None], u)
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    xs = tuple(x.swapaxes(0, 1) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), f32), xs)
    return o.swapaxes(0, 1)


def chunk_kda(q, k, v, g, beta, *, chunk: int = CHUNK, head_groups: int = 1,
              by_head: bool = False):
    """The chunked form; arguments as ``recurrent_kda``. The matmuls run in
    ``q``'s dtype with float32 accumulation, the decays, the score
    matrices' inverse and the carried state in float32. Returns o
    [B, S, H, dv] in ``v``'s dtype. ``S`` must be a multiple of ``chunk``.

    ``by_head``: o as the recurrence's kernel writes it and the head
    groups' map stacks it, [G, B, H / G, S, dv] (G = ``head_groups``), for
    a consumer that reads a head where it lies (``ops/layers.py``
    ``gated_norm``): the relayout to [B, S, H, dv], 2 B S H dv bytes each
    way behind the kernel, in a rematted layer's rerun and in front of the
    scan's backward, is then never made.

    q and k come at their own head count ``Hk`` (``q.shape[2]`` against
    ``v.shape[2]``: Qwen3-Next's 16 key heads serve 32 value heads, Kimi's
    are as many): the preparation's kernels read a key head where each of
    its value heads needs it and sum those heads' ``dq`` and ``dk`` before
    they store them, so no repeated q or k is ever written (19.1 ms of the
    Qwen3-Next cell's 437 ms step until PR 53: my chip runs, PR 53).

    The heads (they are independent) run in ``head_groups`` groups, one
    after the other under ``lax.map``, each under its own
    ``jax.checkpoint``: the six operands (the residuals of the
    recurrence's ``custom_vjp``, 184 MB a group of 8 heads at 16384 tokens)
    live for one group at a time. A group's backward runs its preparation
    again, then the recurrence's checkpoint form and the two backward
    kernels; ``ds_kda_fwd`` itself is not run again (its ``o`` is dead in
    the rerun). Without that checkpoint the engine's train step of the
    Kimi cell peaked at 13.19 GiB, with it at 12.62 (AOT for one v5e chip,
    PR 44).

    With more than one group the map is a loop, and the result is declared
    kept (``_kept``) OUTSIDE it and the groups' checkpoints, where the
    policy of a layer's ``jax.checkpoint`` sees the name
    (``models/transformer.py`` ``_remat_policy``): the layer's rerun then
    holds no kernel of the scan, and the Kimi cell's step peaks at 12.88
    GiB of 15.75 (PR 51; 12.63 before). With ONE group nothing is kept:
    XLA already merges the layer's rerun of the preparation with the
    group's (no loop hides it), so one ``ds_kda_fwd`` a layer is all a kept
    ``o`` saves (5.4 ms of the Qwen3-Next cell's 437 ms step), and with it
    kept XLA lays ``ds.mix_post``'s backward out as the projections are
    and pays four more float32 [S, H dv] relayouts a layer for it (14.6
    ms): the step read 442.7 ms for 437.4 and 1.2% fewer tokens/s in four
    pairs of four (my chip runs, PR 51)."""
    h, hk = v.shape[2], q.shape[2]
    if h % head_groups or hk % head_groups:
        raise ValueError(
            f"chunk_kda: {h} heads ({hk} of q and k) in {head_groups} groups")

    def split(x):       # [B, S, H, ...] -> [G, B, S, H/G, ...], H its own
        x = x.reshape(*x.shape[:2], head_groups, x.shape[2] // head_groups,
                      *x.shape[3:])
        return jnp.moveaxis(x, 2, 0)

    one = jax.checkpoint(
        lambda xs: _chunk_kda(*xs, chunk=chunk, by_head=by_head),
        prevent_cse=False)
    with jax.named_scope("ds.kda_scan"):
        o = jax.lax.map(one, tuple(split(x) for x in (q, k, v, g, beta)))
    if head_groups > 1:
        o = _kept(o)
    if by_head:
        return o                                    # [G, B, H/G, S, dv]
    o = jnp.moveaxis(o, 0, 2)                       # [B, S, G, H/G, dv]
    return o.reshape(*o.shape[:2], h, o.shape[-1])


@jax.custom_vjp
def _kept(o):
    """``o`` as it is; differentiated, ``o`` declared kept (``_keep``)
    where a rematted layer's policy sees the name: outside the head
    groups' ``lax.map`` and their checkpoints. It is the map's own result
    [G, B, S, H/G, dv] ([G, B, H/G, S, dv] ``by_head``) that is named, not
    its relayout to [B, S, H, dv],
    which a layer's rerun makes again: named after the relayout, the Kimi
    cell's compiled step kept two float32 [S, H dv] tensors of
    ``ds.mix_post``'s backward alive through the scan's (13.56 GiB for
    12.88, AOT, PR 51)."""
    return o


_kept.defvjp(lambda o: (_keep("kda", o)[0], None), lambda _, do: (do,))


def sharded_chunk_kda(act_sharding):
    """``chunk_kda`` for a multi-device mesh: per shard of the batch under
    a shard_map, because GSPMD cannot partition the kernels' Mosaic calls
    (``parallel.mesh.per_batch_shard``, which see: heads and sequences are
    independent, so the per-shard result is exact)."""
    from ..parallel.mesh import per_batch_shard
    return per_batch_shard(chunk_kda, act_sharding, (True,) * 5)


def _chunk_kda(q, k, v, g, beta, *, chunk, by_head=False):
    b, s, h, _ = v.shape
    with jax.named_scope("ds.kda_scan"):
        o = kda_recurrence(*kda_prepare(q, k, v, g, beta, chunk=chunk),
                           out_dtype=v.dtype)           # [B, H, N, C, dv]
    if by_head:
        return o.reshape(b, h, s, v.shape[-1])
    return jnp.moveaxis(o, 1, 3).reshape(b, s, h, v.shape[-1])

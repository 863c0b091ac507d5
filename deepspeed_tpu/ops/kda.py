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
the PREPARATION: a Pallas kernel pair (``ops/pallas/kda.py``
``_prepare_forward`` / ``_prepare_backward``) builds it chunk by chunk in
VMEM from q, k, v, g and beta, which it reads once in the model's layout,
and writes six operands (the inverse's float32 products two heads to a
product 128 lanes wide, PR 44); its backward rebuilds a chunk's forward
from the same five inputs. What needs ``S`` is serial in the chunks and
runs in a second kernel pair (``_forward`` / ``_backward``): the forward
carries ``S`` in VMEM across the chunks and writes ``o`` alone; the
backward rebuilds the states by segments of ``SEG`` chunks from float32
segment checkpoints and carries ``dS`` in VMEM. No score matrix, inverse,
state history or per-chunk residual reaches HBM; the six operands cross it
once each way. (Until PR 32 the recurrence was a ``lax.scan`` under
autodiff, until PR 35 the preparation ``jax.numpy`` under autodiff:
``tests/helpers/kda_reference.py`` keeps that form as the kernels'
reference.)

The heads are independent and run in HEAD GROUPS, one after the other, so
that the six operands (184 MB a group of 8 heads at 16384 tokens) live for
one group at a time. A group is an OFFSET in the four calls' index maps
(ISSUE 59), not a slice of the arrays: every call reads the whole q, k, v,
g, beta at the group's first head block (a scalar-prefetch operand), the
recurrence writes the group's ``o`` into the heads' stack [G, B, H / G, S,
dv] and reads its ``do`` from the whole stack, and the preparation's
backward writes the group's dq, dk, dv, dg, dbeta where the group's heads
lie in the whole gradients, each through ``input_output_aliases`` on a
buffer the groups carry along. ONE ``jax.custom_vjp`` (``_scan``) spans
the grouped scan: its forward rule runs the groups (prepare, recur), its
residuals are the five inputs, its backward rule runs the groups again
(the preparation's forward, the recurrence's checkpoint form,
``ds_kda_bwd``, ``ds_kda_prep_bwd``). The groups are ONE rolled loop
(``_each_group``) over buffers that are not initialised, so a step holds
each call once whatever the count; one group carries nothing and is no
loop. (Until
PR 59 the groups were slices under ``lax.map``, each under its own
``jax.checkpoint``: the split, the map's slices and stacks and their
mirror image in the backward were 59 ms of the Kimi cell's 637 ms step
and none of the algorithm's work: ``PERF.md`` section 6.)

A layer of the Kimi cell (``train-kda-s16k-1chip``: four KDA layers of 32
heads in four head groups) so runs the preparation's forward twice (the
forward, and the backward rule's) and its backward once; ``ds_kda_fwd``
once, its checkpoint form once and ``ds_kda_bwd`` once (``PERF.md``
section 5 has their times). The layer's own remat does not run the scan
again: the forward rule declares ``o`` kept (``_keep``), 2 B S H dv bytes
a layer (134 MB) alive across the backward, and the rerun lacked nothing
else (until PR 51 it ran both forward kernels a third time). A layer of
the Qwen3-Next cell (``train-gdn-s16k-1chip``: three Gated DeltaNet layers
of 32 value heads in ONE group) keeps nothing and reruns the scan: the
compiled step holds the preparation's forward twice a layer all the same
(with no loop round it XLA merges the layer's rerun of it with the
backward rule's) and ``ds_kda_fwd`` twice and its checkpoint form once.
The operand shapes are the same in both cells (dk = dv = 128, 16384
tokens); the second reads its gate as rows, a number a token, as both read
beta.

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

from .pallas import kda as kernels
from .pallas._common import _keep
from .pallas.kda import CHUNK


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

    ``by_head``: o as the recurrence's kernel writes it, the head groups'
    stack [G, B, H / G, S, dv] (G = ``head_groups``), for
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
    after the other: the six operands between the two kernel pairs (184
    MB a group of 8 heads at 16384 tokens) live for one group at a time
    (without that the engine's train step of the Kimi cell peaked at
    13.19 GiB, with it at 12.62: AOT for one v5e chip, PR 44). A group
    costs what its kernels cost: it is an offset in their index maps, and
    they read the inputs and write ``o`` and the gradients where they lie
    (the module docstring). The backward runs a group's preparation
    again, then the recurrence's checkpoint form and the two backward
    kernels; ``ds_kda_fwd`` itself is not run again.

    With more than one group the result is declared kept (``_keep``, in
    the forward rule, where the policy of a layer's ``jax.checkpoint`` sees
    the name: ``models/transformer.py`` ``_remat_policy``): the layer's
    rerun then holds no kernel of the scan, and the Kimi cell's step peaks
    at 12.88 GiB of 15.75 (PR 51; 12.63 before). It is the heads' stack
    that is named, not its relayout to [B, S, H, dv], which a layer's rerun
    makes again (named after the relayout the compiled step kept two
    float32 [S, H dv] tensors of ``ds.mix_post``'s backward alive through
    the scan's: 13.56 GiB for 12.88, AOT, PR 51). With ONE group nothing
    is kept: XLA already merges the layer's rerun of the preparation with
    the backward rule's (no loop hides it), so one ``ds_kda_fwd`` a layer
    is all a kept ``o`` saves (5.4 ms of the Qwen3-Next cell's 437 ms
    step), and with it kept XLA lays ``ds.mix_post``'s backward out as the
    projections are and pays four more float32 [S, H dv] relayouts a layer
    for it (14.6 ms): the step read 442.7 ms for 437.4 and 1.2% fewer
    tokens/s in four pairs of four (my chip runs, PR 51)."""
    h, hk = v.shape[2], q.shape[2]
    if h % head_groups or hk % head_groups:
        raise ValueError(
            f"chunk_kda: {h} heads ({hk} of q and k) in {head_groups} groups")
    with jax.named_scope("ds.kda_scan"):
        o = _scan(q, k, v, g, beta, chunk, head_groups)
    if by_head:
        return o                                    # [G, B, H/G, S, dv]
    o = o.transpose(1, 3, 0, 2, 4)                  # [B, S, G, H/G, dv]
    return o.reshape(*o.shape[:2], h, o.shape[-1])


def _each_group(a_group, buffers, groups: int):
    """``carry = a_group(grp, carry)`` group after group, as ONE rolled loop
    whose counter is the calls' scalar-prefetch operand: a step holds one
    copy of each call whatever the count (a Python loop held one a group:
    four times the scan's calls in the Kimi cell's step and its compile),
    and XLA cannot run two groups' preparations side by side, which is
    what the groups are for. ``buffers()`` makes what the groups write
    into, NOT initialised (``lax.empty``: ``AllocateBuffer`` on the chip;
    every block is written by exactly one group, so nothing is zeroed and
    nothing is added). One group has nothing to carry: its calls make
    their own outputs (``carry`` None), and no loop hides them from XLA."""
    if groups == 1:
        return a_group(0, None)
    return jax.lax.fori_loop(0, groups, a_group, buffers())


def _scan_groups(q, k, v, g, beta, chunk, groups):
    """The scan in ``groups`` head groups, each an offset in the four
    calls' index maps: o as the heads' stack [G, B, H / G, S, dv]. What
    the loop carries is in the kernels' layout (``_prep_inputs``, made
    once): across a loop's boundary XLA would not cancel the model's
    [B, S, H, d] against the kernels' [B, S, H d], two tilings of the
    same bytes, and relaid every tensor out on the way in (AOT, PR 59)."""
    b, s, h, dv = v.shape
    args, prep = kernels._prep_inputs(q, k, v, g, beta, chunk, groups)

    def group(grp, o):
        ops = kernels._prepare_forward(args, prep, grp)
        return kernels._forward(ops, v.dtype, states=False, grp=grp, into=o)

    o = _each_group(group, lambda: kernels._stack(
        b * h, s // chunk, chunk, dv, v.dtype), groups)[:, :s // chunk]
    return o.reshape(groups, b, h // groups, s, dv)


_scan = jax.custom_vjp(_scan_groups, nondiff_argnums=(5, 6))


def _scan_fwd(q, k, v, g, beta, chunk, groups):
    o = _scan_groups(q, k, v, g, beta, chunk, groups)
    if groups > 1:
        o, = _keep("kda", o)
    return o, (q, k, v, g, beta)


def _scan_bwd(chunk, groups, inputs, do):
    v = inputs[2]
    # opened here: a custom_vjp's backward function is traced outside the
    # scope its forward was called under
    with jax.named_scope("ds.kda_scan"):
        do = do.reshape(-1, v.shape[1] // chunk, chunk, v.shape[-1])
        args, prep = kernels._prep_inputs(*inputs, chunk, groups)

        def group(grp, grads):
            ops = kernels._prepare_forward(args, prep, grp)
            ck = kernels._forward(ops, v.dtype, states=True)
            cts = kernels._backward(ops, ck, do, grp)
            return kernels._prepare_backward(args, cts, prep, grp,
                                             into=grads)

        grads = _each_group(group, lambda: tuple(
            jax.lax.empty(x.shape, x.dtype) for x in args), groups)
        return kernels._prep_gradients(grads, *inputs)


_scan.defvjp(_scan_fwd, _scan_bwd)


def sharded_chunk_kda(act_sharding):
    """``chunk_kda`` for a multi-device mesh: per shard of the batch under
    a shard_map, because GSPMD cannot partition the kernels' Mosaic calls
    (``parallel.mesh.per_batch_shard``, which see: heads and sequences are
    independent, so the per-shard result is exact)."""
    from ..parallel.mesh import per_batch_shard
    return per_batch_shard(chunk_kda, act_sharding, (True,) * 5)

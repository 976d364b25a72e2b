"""Pallas TPU kernel: batched JSQ-MaxWeight claim scoring (weighted argmax).

The MaxWeight baseline's hot loop: each of B idle servers scans all N queues
for ``argmax_n w(m,n) * Q_n`` where the weight depends on server/queue
identity and the deepest hierarchy level the pair shares — derived from the
``(depth, .)`` ancestor tables (`Topology.ancestors`), with the depth loop
unrolled at trace time so the K=3 instance lowers to exactly one rack
comparison.  The empty-queue mask is folded into the score.

This is a masked reduction on the vector unit, not a matmul, so the MXU
is idle.  The kernel tiles the (B, N) score surface in 8x128-aligned
blocks on the grid (B/bb, N/bn), queue axis innermost, streams the queue
axis through VMEM and keeps a running (max, argmax) per idle server in
its output block, revisited across the inner axis (the standard Pallas
reduction pattern).  The hierarchy level of each pair is derived on the
fly from depth integer comparisons, so no (B, N) weight matrix is
materialized in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -3.0e38


def _claim_kernel(queues_ref, qanc_ref, idle_ref, ianc_ref, rates_ref,
                  score_ref, queue_ref, *, block_n: int, depth: int):
    """One (idle-server-block, queue-block) tile.

    queues_ref: (bn,)      f32  queue lengths of this block
    qanc_ref:   (D, bn)    i32  ancestor table of each queue's owner
    idle_ref:   (bb,)      i32  idle server ids
    ianc_ref:   (D, bb)    i32  ancestor table of each idle server
    rates_ref:  (bb, K)    f32  per-idle-server estimated tier rates
    score_ref:  (bb,)      f32  running max score (output, revisited)
    queue_ref:  (bb,)      i32  running argmax    (output, revisited)
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        score_ref[...] = jnp.full_like(score_ref, NEG_INF)
        queue_ref[...] = jnp.zeros_like(queue_ref)

    q = queues_ref[...]
    idle = idle_ref[...]
    rates = rates_ref[...]

    bb, bn = idle.shape[0], q.shape[0]
    qid = j * block_n + jax.lax.broadcasted_iota(jnp.int32, (bb, bn), 1)

    is_self = qid == idle[:, None]
    # remote weight by default; sharpen level by level, deepest first
    w = jnp.broadcast_to(rates[:, depth + 1:depth + 2], (bb, bn))
    for lvl in range(depth - 1, -1, -1):
        qrow = qanc_ref[lvl, :]                # (bn,)
        irow = ianc_ref[lvl, :]                # (bb,)
        share = jnp.broadcast_to(qrow[None, :], (bb, bn)) == irow[:, None]
        w = jnp.where(share, rates[:, lvl + 1:lvl + 2], w)
    w = jnp.where(is_self, rates[:, 0:1], w)
    score = jnp.where(q[None, :] > 0, w * q[None, :], NEG_INF)

    blk_max = jnp.max(score, axis=1)
    blk_arg = jnp.argmax(score, axis=1).astype(jnp.int32)

    best = score_ref[...]
    better = blk_max > best  # strict: lowest queue index on ties
    score_ref[...] = jnp.where(better, blk_max, best)
    queue_ref[...] = jnp.where(better, j * block_n + blk_arg, queue_ref[...])


@functools.partial(jax.jit, static_argnames=("block_idle", "block_queues",
                                             "interpret"))
def maxweight_claim_pallas(queues: jnp.ndarray, queue_anc: jnp.ndarray,
                           idle_servers: jnp.ndarray, idle_anc: jnp.ndarray,
                           est_rates: jnp.ndarray, *, block_idle: int = 128,
                           block_queues: int = 512, interpret: bool = False):
    """Padded, tiled argmax claims.  See ref.maxweight_claim for semantics.
    queue_anc (depth, N) / idle_anc (depth, B) are ancestor tables;
    est_rates (B, depth + 2).  Padding queues must carry Q=0 (masked),
    padding idle rows are sliced off by ops.maxweight_claim."""
    b = idle_servers.shape[0]
    n = queues.shape[0]
    depth = queue_anc.shape[0]
    grid = (b // block_idle, n // block_queues)

    kernel = functools.partial(_claim_kernel, block_n=block_queues,
                               depth=depth)
    score, queue = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_queues,), lambda i, j: (j,)),
            pl.BlockSpec((depth, block_queues), lambda i, j: (0, j)),
            pl.BlockSpec((block_idle,), lambda i, j: (i,)),
            pl.BlockSpec((depth, block_idle), lambda i, j: (0, i)),
            pl.BlockSpec((block_idle, depth + 2), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_idle,), lambda i, j: (i,)),
            pl.BlockSpec((block_idle,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b,), jnp.float32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
        ],
        interpret=interpret,
    )(queues.astype(jnp.float32), queue_anc.astype(jnp.int32),
      idle_servers.astype(jnp.int32), idle_anc.astype(jnp.int32),
      est_rates.astype(jnp.float32))
    return queue, score

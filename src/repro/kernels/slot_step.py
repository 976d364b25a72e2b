"""Pallas TPU kernel: fused fleet slot-step routing (workload + private argmin).

The fleet backend (`sharding/sim.py`) splits Balanced-PANDAS routing of a
B-task arrival batch into a *private* phase (each task scores the servers
that are local / rack-local / ... / anything better than the remote tier)
and a shared *pool* phase (the remote tier is filled globally by a
water-level computation, outside this kernel — it couples all tasks in the
slot).  This kernel fuses the private phase with the workload computation
it consumes:

    W_m     = sum_k q[m, k] / est[m, k]  (+ in-service residual)
    score   = W_m / est[m, tier(m, task)] - est[...] * 1e-6
    out_b   = argmin over servers with tier(m, task) < K-1

so one kernel launch scores the whole (B, M) surface tile by tile: tasks
on sublanes, servers on lanes.  Per-server inputs arrive transposed,
one row per tier or level ((K, M) queues and rates, (1, M) serving,
(D, M) ancestors), so every per-tier value is a static row slice, and the
tier of each (task, server) pair is derived by an unrolled `jnp.where`
chain over the levels — the kernel body holds no gather.  Per-task
results are (B, 1) columns.  Compare `wwl_route.py`, which scores ALL
servers (including the remote tier) against a precomputed workload
vector: the fused kernel reads the raw policy state (q, serving) instead,
and masks the remote tier out, because the fleet path assigns remote
traffic by water-filling rather than per-task argmin (B tasks hitting the
same remote argmin would pile onto one server — see docs/scaling.md).

The ``- rate * 1e-6`` term is the same infinitesimal faster-tier
preference the sequential simulator applies on exact workload ties
(`core/balanced_pandas.route_one`); tie-breaking among equal scores is
lowest-server-index (deterministic), as in the other scheduling kernels.

Semantics contract: `ref.fleet_route`.  `sharding/sim.py` runs this
kernel on TPU and an XLA segment-min realization elsewhere; both are
exact against the same oracle and bitwise equal to each other in the
fleet loop (tests/test_fleet_scale.py), and tests/test_chip_compile.py
compiles this kernel for a TPU v5e at fleet size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LARGE = 3.0e38  # +inf surrogate inside min-accumulators (matches wwl_route)


def _fleet_route_kernel(q_ref, rates_ref, serving_ref, anc_ref, tasks_ref,
                        score_ref, server_ref, tier_ref, *,
                        block_m: int, depth: int):
    """One (task-block, server-block) tile.

    q_ref:       (K, bm)        f32   waiting tasks per (tier, server)
    rates_ref:   (K, bm)        f32   est tier rates (K = depth + 2)
    serving_ref: (1, bm)        i32   class in service (0 idle, 1..K)
    anc_ref:     (D, bm)        i32   ancestor table slice of this block
    tasks_ref:   (bt, 3(D+1))   i32   task locals, then their level-l
                                      ancestor groups, 3 columns per level
    score_ref:   (bt, 1)        f32   running min private score (revisited)
    server_ref:  (bt, 1)        i32   running argmin server     (revisited)
    tier_ref:    (bt, 1)        i32   tier at argmin            (revisited)
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        score_ref[...] = jnp.full_like(score_ref, LARGE)
        server_ref[...] = jnp.zeros_like(server_ref)
        tier_ref[...] = jnp.zeros_like(tier_ref)

    q = q_ref[...]
    rates = rates_ref[...]
    serving = serving_ref[...]
    tasks = tasks_ref[...]
    k = q.shape[0]
    row = [rates[t:t + 1, :] for t in range(k)]            # K x (1, bm)

    # fused workload: left-associative tier sum + in-service residual,
    # matching core/balanced_pandas.workload bit-for-bit
    w = q[0:1, :] / row[0]
    for t in range(1, k):
        w = w + q[t:t + 1, :] / row[t]
    resid_idx = jnp.clip(serving - 1, 0, k - 1)
    resid_rate = row[0]
    for t in range(1, k):
        resid_rate = jnp.where(resid_idx == t, row[t], resid_rate)
    w = w + jnp.where(serving > 0, 1.0 / resid_rate, 0.0)

    bt = tasks.shape[0]
    bm = w.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (bt, bm), 1)
    sid = j * block_m + col

    def hits(ids, c):
        """(bt, bm) mask: ids equal one of the task's columns c..c+2."""
        return ((ids == tasks[:, c:c + 1]) | (ids == tasks[:, c + 1:c + 2])
                | (ids == tasks[:, c + 2:c + 3]))

    # remote by default; sharpen tier/rate level by level, deepest first —
    # the depth loop is unrolled at trace time (static shape)
    tier = jnp.full((bt, bm), depth + 1, jnp.int32)
    rate = jnp.broadcast_to(row[depth + 1], (bt, bm))
    for lvl in range(depth - 1, -1, -1):
        share = hits(anc_ref[lvl:lvl + 1, :], 3 * (lvl + 1))
        tier = jnp.where(share, lvl + 1, tier)
        rate = jnp.where(share, row[lvl + 1], rate)
    local = hits(sid, 0)
    tier = jnp.where(local, 0, tier)
    rate = jnp.where(local, row[0], rate)
    score = jnp.broadcast_to(w, (bt, bm)) / rate - rate * 1e-6
    # the private mask: the remote tier (K-1 = depth+1) is pool-filled
    score = jnp.where(tier <= depth, score, LARGE)

    # lowest-index argmin and its tier as masked minima (no gather)
    blk_min = jnp.min(score, axis=1, keepdims=True)                # (bt, 1)
    blk_arg = jnp.min(jnp.where(score == blk_min, col, bm), axis=1,
                      keepdims=True)
    blk_tier = jnp.min(jnp.where(col == blk_arg, tier, depth + 1), axis=1,
                       keepdims=True)

    best = score_ref[...]
    better = blk_min < best                    # strict: keeps lowest index
    score_ref[...] = jnp.where(better, blk_min, best)
    server_ref[...] = jnp.where(better, j * block_m + blk_arg, server_ref[...])
    tier_ref[...] = jnp.where(better, blk_tier, tier_ref[...])


@functools.partial(jax.jit, static_argnames=("block_tasks", "block_servers",
                                             "interpret"))
def fleet_route_pallas(q: jnp.ndarray, serving: jnp.ndarray,
                       est_rates: jnp.ndarray, server_anc: jnp.ndarray,
                       task_locals: jnp.ndarray, *, block_tasks: int = 128,
                       block_servers: int = 512, interpret: bool = False):
    """Padded, tiled fused workload + private-route.  See ref.fleet_route.

    q (M, K) f32, serving (M,) i32, est_rates (M, K) f32, server_anc the
    (depth, M) ancestor table.  Caller guarantees M % block_servers == 0
    and B % block_tasks == 0 (ops.fleet_route pads; padding servers carry
    pad ancestor ids that collide only with each other, so they land on
    the masked remote tier and never win).  Returns (server, tier, score),
    each (B,).
    """
    b = task_locals.shape[0]
    m, k = q.shape
    depth = server_anc.shape[0]
    grid = (b // block_tasks, m // block_servers)
    locs = task_locals.astype(jnp.int32)
    anc = server_anc.astype(jnp.int32)
    # (B, 3(D+1)): the locals, then each level's ancestor groups of them
    tasks = jnp.concatenate(
        [locs] + [anc[lvl][locs] for lvl in range(depth)], axis=1)

    kernel = functools.partial(_fleet_route_kernel, block_m=block_servers,
                               depth=depth)
    per_server = lambda rows: pl.BlockSpec((rows, block_servers),
                                           lambda i, j: (0, j))
    per_task = lambda cols: pl.BlockSpec((block_tasks, cols),
                                         lambda i, j: (i, 0))
    score, server, tier = pl.pallas_call(
        kernel,
        # the kernel's name in compiled HLO and in profiler traces, kept
        # whatever the wrapper is called
        name="fleet_route_kernel",
        grid=grid,
        in_specs=[per_server(k), per_server(k), per_server(1),
                  per_server(depth), per_task(tasks.shape[1])],
        out_specs=[per_task(1)] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q.astype(jnp.float32).T, est_rates.astype(jnp.float32).T,
      serving.astype(jnp.int32)[None, :], anc, tasks)
    return server[:, 0], tier[:, 0], score[:, 0]

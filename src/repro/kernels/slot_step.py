"""Pallas TPU kernel: fused fleet slot-step routing (workload + private argmin).

The fleet backend (`sharding/sim.py`) splits Balanced-PANDAS routing of a
B-task arrival batch into a *private* phase (each task scores the servers
that are local / rack-local / ... / anything better than the remote tier)
and a shared *pool* phase (the remote tier is filled globally by a
water-level computation, outside this kernel — it couples all tasks in the
slot).  This kernel fuses the private phase with the workload computation
it consumes:

    W_m     = sum_k q[m, k] / est[m, k]  (+ in-service residual)
    s_t[m]  = W_m / est[m, t] - est[m, t] * 1e-6      for tiers t < K-1
    score   = s_{tier(m, task)}[m]
    out_b   = argmin over servers with tier(m, task) < K-1

so one kernel launch scores the whole (B, M) surface tile by tile: tasks
on sublanes, servers on lanes.  Per-server inputs arrive transposed, one
row per tier or level ((K, M) queues and rates, (1, M) serving, (D, M)
ancestors), so every per-tier value is a static row slice.  What depends
on one axis is computed on that axis alone: the workload and the
per-tier score rows s_t once per server block, (1, bm) each; the task's
locals shifted into block coordinates once a grid step, (bt, 3), not per
pair.  The (bt, bm) surface then holds only compares, selects and
minima: each pair's tier tests (an unrolled chain over the levels,
deepest first, then the locals) select its score from the rows, starting
from the remote tier's +LARGE, and the block folds into lane-dense
running minima, (bt, 128), (score, lowest server index) held in VMEM
scratch across the server axis.  The cross-lane minima, the tier at the
argmin and every (bt, 1) column run once per task block, at its last
server block: on the chip those per-row operations cost a grid step more
than the surface does.  The kernel body holds no gather.  Per-task
results are (B, 1) columns.  The kernel reads the raw policy state
(q, serving) rather than a precomputed workload vector, and masks the
remote tier out, because the fleet path assigns remote traffic by
water-filling rather than per-task argmin (B tasks hitting the same
remote argmin would pile onto one server — see docs/scaling.md).

The ``- rate * 1e-6`` term is the same infinitesimal faster-tier
preference the sequential simulator applies on exact workload ties
(`core/balanced_pandas.route_one`); tie-breaking among equal scores is
lowest-server-index (deterministic), as in `maxweight.py`.

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

LARGE = 3.0e38  # +inf surrogate inside min-accumulators


def _fleet_route_kernel(q_ref, rates_ref, serving_ref, anc_ref, tasks_ref,
                        score_ref, server_ref, tier_ref, min_acc, idx_acc,
                        *anc_acc, block_m: int, depth: int):
    """One (task-block, server-block) tile.

    q_ref:       (K, bm)        f32   waiting tasks per (tier, server)
    rates_ref:   (K, bm)        f32   est tier rates (K = depth + 2)
    serving_ref: (1, bm)        i32   class in service (0 idle, 1..K)
    anc_ref:     (D, bm)        i32   ancestor table slice of this block
    tasks_ref:   (bt, 3(D+1))   i32   task locals, then their level-l
                                      ancestor groups, 3 columns per level
    score_ref:   (bt, 1)        f32   min private score      (last step)
    server_ref:  (bt, 1)        i32   lowest-index argmin    (last step)
    tier_ref:    (bt, 1)        i32   tier at the argmin     (last step)
    min_acc:     (bt, 128)      f32   scratch: running min per lane
    idx_acc:     (bt, 128)      i32   scratch: its lowest server index
    anc_acc:     D-1 x (bt, 128) i32  scratch: that server's level-l
                                      ancestor, l = 0..D-2
    """
    j = pl.program_id(1)
    lanes = min_acc.shape[1]

    @pl.when(j == 0)
    def _init():
        min_acc[...] = jnp.full_like(min_acc, LARGE)
        idx_acc[...] = jnp.zeros_like(idx_acc)
        for acc in anc_acc:
            acc[...] = jnp.zeros_like(acc)

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

    # per-tier private scores, once per server block: a server's score at
    # tier t depends on the server alone, so the (bt, bm) surface below
    # only selects among these rows (same f32 ops, element for element)
    s = [w / row[t] - row[t] * 1e-6 for t in range(depth + 1)]

    bt = tasks.shape[0]
    bm = w.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (bt, bm), 1)
    # the task's locals in this block's coordinates, (bt, 3) once a step
    locs = tasks[:, 0:3] - j * block_m

    def hits(ids, cols):
        """Mask: ids equal one of the three (bt, 1) columns of cols."""
        return ((ids == cols[:, 0:1]) | (ids == cols[:, 1:2])
                | (ids == cols[:, 2:3]))

    def groups(lvl):
        return tasks[:, 3 * (lvl + 1):3 * (lvl + 2)]

    # remote (pool-filled, masked out) by default; sharpen level by level,
    # deepest first — the depth loop is unrolled at trace time (static shape)
    score = jnp.full((bt, bm), LARGE, jnp.float32)
    for lvl in range(depth - 1, -1, -1):
        score = jnp.where(hits(anc_ref[lvl:lvl + 1, :], groups(lvl)),
                          s[lvl + 1], score)
    score = jnp.where(hits(col, locs), s[0], score)

    # Fold the block into the per-lane running minima, 128 lanes at a time
    # in increasing server order; the strict < keeps each lane's lowest
    # index among equal scores.  No lane crosses another here: the
    # cross-lane minima and every (bt, 1) column run once, at the last step.
    lane = jax.lax.broadcasted_iota(jnp.int32, (bt, lanes), 1)
    best, idx = min_acc[...], idx_acc[...]
    anc_at = [acc[...] for acc in anc_acc]
    for c in range(0, bm, lanes):
        part = score[:, c:c + lanes]
        better = part < best
        best = jnp.where(better, part, best)
        idx = jnp.where(better, j * block_m + c + lane, idx)
        anc_at = [jnp.where(better, anc_ref[lvl:lvl + 1, c:c + lanes], a)
                  for lvl, a in enumerate(anc_at)]
    min_acc[...] = best
    idx_acc[...] = idx
    for acc, a in zip(anc_acc, anc_at):
        acc[...] = a

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        # lowest index among the lanes that hold the minimum
        none = jnp.iinfo(jnp.int32).max
        low = jnp.min(best, axis=1, keepdims=True)                 # (bt, 1)
        at = best == low
        arg = jnp.min(jnp.where(at, idx, none), axis=1, keepdims=True)
        found = low < LARGE
        # Its tier: a found argmin is private, so tier 0 if local, else the
        # shallowest level whose group holds it; the deepest private level
        # needs no test, so only levels 0..depth-2 keep its ancestors.
        tier = jnp.full((bt, 1), depth, jnp.int32)
        at = at & (idx == arg)
        for lvl in range(depth - 2, -1, -1):
            anc = jnp.min(jnp.where(at, anc_at[lvl], none), axis=1,
                          keepdims=True)
            tier = jnp.where(hits(anc, groups(lvl)), lvl + 1, tier)
        tier = jnp.where(hits(arg, tasks[:, 0:3]), 0, tier)
        score_ref[...] = low
        server_ref[...] = jnp.where(found, arg, 0)
        tier_ref[...] = jnp.where(found, tier, 0)


@functools.partial(jax.jit, static_argnames=("block_tasks", "block_servers",
                                             "interpret"))
def fleet_route_pallas(q: jnp.ndarray, serving: jnp.ndarray,
                       est_rates: jnp.ndarray, server_anc: jnp.ndarray,
                       task_locals: jnp.ndarray, *, block_tasks: int = 128,
                       block_servers: int = 512, interpret: bool = False):
    """Padded, tiled fused workload + private-route.  See ref.fleet_route.

    q (M, K) f32, serving (M,) i32, est_rates (M, K) f32, server_anc the
    (depth, M) ancestor table.  Caller guarantees M % block_servers == 0,
    block_servers % 128 == 0 and B % block_tasks == 0 (ops.fleet_route
    pads; padding servers carry
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
        scratch_shapes=[pltpu.VMEM((block_tasks, 128), jnp.float32)]
        + [pltpu.VMEM((block_tasks, 128), jnp.int32)] * max(depth, 1),
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

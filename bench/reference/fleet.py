"""Plain reference of the fleet slot model (Balanced-PANDAS at 10k servers),
written from the model's definition and independent of the program.

Per slot: a truncated-Poisson number of arrivals on `lanes` lanes, each
task's three distinct replicas drawn inside rack 0 (hot) or from the whole
fleet; then two routing rounds against a workload snapshot, each of which
scores every (task, server) pair, gives every task its lowest-index best
private server (any tier but the remote one), fills the remote pool to a
water level, and keeps a task private only while its rank among the
claimants of that server, times 1/rate^2, keeps it under that level; the
losers of round one re-route after the winners are committed; what is
left goes to the pool in server order up to each server's share; then
Bernoulli service at the true rates and idle servers take new work.

Everything is written out the way the model states it: a task's private
options are the servers of its replicas' racks, scored one by one; no
segment minima, no sorting, no kernel.  The random
numbers are data drawn with `jax.random` under the model's key discipline,
so the sample path agrees with the program's decision by decision.  `dtype`
is the precision of rates, workloads, scores and water levels: float32 is
the model's, bfloat16 gives the control.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _arrivals(k_arr, lam, p_hot, hot_size, m, lanes):
    """Three distinct servers per task: the first uniform over the pool,
    the second uniform over the rest, the third uniform over what is left
    (each skip counted in index order); the pool is rack 0 for a hot task
    and the whole fleet otherwise."""
    k_n, k_t = jax.random.split(k_arr)
    n = jnp.minimum(jax.random.poisson(k_n, lam), lanes)
    active = jnp.arange(lanes) < n
    k_hot, k_u = jax.random.split(k_t)
    hot = jax.random.bernoulli(k_hot, p_hot, (lanes,))
    size = jnp.where(hot, hot_size, m).astype(jnp.float32)
    u = jax.random.uniform(k_u, (lanes, 3))
    a = jnp.minimum(jnp.floor(u[:, 0] * size), size - 1)
    b = jnp.minimum(jnp.floor(u[:, 1] * (size - 1)), size - 2)
    b = jnp.where(b >= a, b + 1, b)
    lo, hi = jnp.minimum(a, b), jnp.maximum(a, b)
    c = jnp.minimum(jnp.floor(u[:, 2] * (size - 2)), size - 3)
    c = jnp.where(c >= lo, c + 1, c)
    c = jnp.where(c >= hi, c + 1, c)
    return jnp.sort(jnp.stack([a, b, c], 1).astype(jnp.int32), 1), active


def _workload(q, serving, est, dt):
    k = q.shape[1]
    w = q[:, 0].astype(dt) / est[:, 0]
    for t in range(1, k):
        w = w + q[:, t].astype(dt) / est[:, t]
    resid = jnp.take_along_axis(est, jnp.maximum(serving - 1, 0)[:, None],
                                axis=1)[:, 0]
    return w + jnp.where(serving > 0, jnp.asarray(1, dt) / resid,
                         jnp.asarray(0, dt))


def _level(p, d, demand, top, lanes, iters):
    """Smallest water level (to `iters` halvings) at which the pool's
    capacity sum_m clip(ceil((y - p_m) / d_m), 0, lanes) covers demand(y)."""
    lo = p.min()
    hi = jnp.maximum(p.max(), top) + lanes * d.max()

    def halve(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) * jnp.asarray(0.5, lo.dtype)
        cap = jnp.clip(jnp.ceil((mid - p) / d), 0, lanes).sum()
        ok = cap >= demand(mid)
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    return jax.lax.fori_loop(0, iters, halve, (lo, hi))[1]


def _route(q, serving, est, rack_size, locs, active, rounds, iters, dt):
    m, k = q.shape
    lanes = locs.shape[0]
    # a task's private servers are every server in its replicas' racks:
    # (B, 3 * rack_size) candidates, tier 0 on a replica and 1 elsewhere
    offsets = jnp.arange(rack_size, dtype=jnp.int32)
    cand = ((locs // rack_size)[:, :, None] * rack_size
            + offsets[None, None, :]).reshape(lanes, -1)
    local = ((cand == locs[:, :1]) | (cand == locs[:, 1:2])
             | (cand == locs[:, 2:]))
    tier = jnp.where(local, 0, 1)
    rate = jnp.where(local, est[cand, 0], est[cand, 1])
    big = jnp.asarray(jnp.finfo(dt).max, dt)
    pending = active
    for r in range(rounds):
        w = _workload(q, serving, est, dt)
        score = w[cand] / rate - rate * jnp.asarray(1e-6, dt)
        best_v = score.min(axis=1)
        # the lowest server index among the minima
        best_i = jnp.where(score == best_v[:, None], cand, m).min(axis=1)
        best_t = jnp.where(cand == best_i[:, None], tier, k).min(axis=1)
        pr = est[:, k - 1]
        p = w / pr - pr * jnp.asarray(1e-6, dt)
        d = jnp.asarray(1, dt) / (pr * pr)
        top = jnp.where(pending, best_v, -big).max()
        y1 = _level(p, d, lambda y: (pending & (best_v > y)).sum().astype(dt),
                    top, lanes, iters)
        go = pending & (best_v <= y1)
        earlier = jnp.tril(jnp.ones((lanes, lanes), bool), -1)
        same = (best_i[:, None] == best_i[None, :]) & go[None, :] & earlier
        rank = same.sum(axis=1).astype(jnp.int32)
        e_at = est[best_i, best_t]
        stay = go & (best_v + rank.astype(dt) / (e_at * e_at) <= y1)
        if r < rounds - 1:
            q = q.at[best_i, best_t].add(stay.astype(jnp.int32))
            pending = pending & ~stay
    pool = pending & ~stay
    n_pool = pool.sum().astype(dt)
    y2 = _level(p, d, lambda y: n_pool, top, lanes, iters)
    caps = jnp.clip(jnp.ceil((y2 - p) / d), 0, lanes).astype(jnp.int32)
    filled = jnp.cumsum(caps)
    pool_rank = jnp.cumsum(pool.astype(jnp.int32)) - 1
    # the j-th pool task goes to the first server whose running capacity
    # exceeds j
    pool_srv = jnp.minimum((filled[None, :] <= pool_rank[:, None]).sum(1),
                           m - 1).astype(jnp.int32)
    srv = jnp.where(stay, best_i, pool_srv)
    tr = jnp.where(stay, best_t, k - 1)
    return q.at[srv, tr].add(pending.astype(jnp.int32))


def build(num_servers: int, rack_size: int, true_rates, p_hot: float,
          lanes: int, horizon: int, warmup: int, rounds: int,
          fill_iters: int, dtype=jnp.float32):
    """Returns jitted advance(carry, t0, slots, lam, est, seed) -> carry,
    carry = (q (M, K) i32, serving (M,) i32, mean_n f32, n_meas f32,
    completions i32), advancing `slots` slots from slot t0 (a static
    count); slots at or past the horizon leave the carry as it is."""
    dt = jnp.dtype(dtype)
    m = num_servers
    if m % rack_size:
        raise ValueError(f"{m} servers do not fill racks of {rack_size}")
    true_rates = jnp.asarray(true_rates, jnp.float32)

    def step(lam, est, base, carry, t):
        q, serving, mean_n, n_meas, completions = carry
        k_arr, k_algo = jax.random.split(jax.random.fold_in(base, t))
        locs, active = _arrivals(k_arr, lam, p_hot, rack_size, m, lanes)
        k_route, k_serve = jax.random.split(k_algo)
        q2 = _route(q, serving, est, rack_size, locs, active, rounds,
                    fill_iters, dt)
        p_done = jnp.where(serving > 0,
                           true_rates[jnp.maximum(serving - 1, 0)], 0.0)
        done = jax.random.bernoulli(k_serve, p_done)
        s2 = jnp.where(done, 0, serving)
        waiting = q2 > 0
        first = jnp.argmax(waiting, axis=1)
        take = (s2 == 0) & waiting.any(axis=1)
        q2 = q2 - (take[:, None] & (jnp.arange(q.shape[1]) == first[:, None]))
        s2 = jnp.where(take, first + 1, s2)
        n = (q2.sum() + (s2 > 0).sum()).astype(jnp.float32)
        counted = t >= warmup
        n_meas2 = n_meas + counted
        mean_n2 = mean_n + counted * (n - mean_n) / jnp.maximum(n_meas2, 1.0)
        compl2 = completions + done.sum() * counted
        new = (q2, s2, mean_n2, n_meas2, compl2)
        live = t < horizon
        return tuple(jnp.where(live, a, b) for a, b in zip(new, carry)), None

    def advance(carry, t0, lam, est, seed, slots):
        est = est.astype(dt)
        base = jax.random.PRNGKey(seed)
        carry, _ = jax.lax.scan(lambda c, t: step(lam, est, base, c, t),
                                carry, t0 + jnp.arange(slots))
        return carry

    return jax.jit(advance, static_argnums=5)

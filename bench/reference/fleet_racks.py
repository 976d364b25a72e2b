"""Plain reference of the fleet slot model with hot traffic spread over
weighted racks (Balanced-PANDAS at 10k servers), written from the model's
definition and independent of the program.

The model is that of reference/fleet.py, whose routing it uses, except
for where a hot task's data lives.  The per-rack weights are cycled over
the racks (rack r takes weight r mod their number); their running sums
over the total, in float64 and rounded once to float32, are the
cumulative shares, set to 1 from the last rack of positive weight on.
Per slot: a truncated-Poisson number of arrivals on `lanes` lanes; each
task is hot with probability p_hot; a hot task's rack is the number of
cumulative shares at or below a uniform draw in [0, 1), found by scanning
them all, and its three distinct replicas are drawn inside that rack (by
offset from the rack's first server); a cold task's are drawn from the
whole fleet.  The random numbers are data drawn with `jax.random` under
the model's key discipline: the slot's arrival key splits into the
count's key and one that splits into three, for hot, rack and replicas.

Two counters ride with the carry, over the measured slots: the hot tasks
that arrived, and the tasks routed to the remote tier, which the remote
pool's water-fill places.  `dtype` is the precision of rates, workloads,
scores and water levels: float32 is the model's, bfloat16 gives the
control.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import fleet


def cumulative_shares(rack_weights, num_racks: int) -> np.ndarray:
    """(num_racks,) float32 cumulative shares of the cycled weights (each
    weight a float32, as the model states them)."""
    w = [float(np.float32(rack_weights[r % len(rack_weights)]))
         for r in range(num_racks)]
    total, run, cum = sum(w), 0.0, []
    for x in w:
        run += x
        cum.append(run / total)
    last = max(r for r, x in enumerate(w) if x > 0)
    for r in range(last, num_racks):
        cum[r] = 1.0
    return np.asarray(cum, np.float32)


def _arrivals(k_arr, lam, p_hot, cum, rack_size, m, lanes):
    """(replicas (lanes, 3) sorted, active, hot and active)."""
    k_n, k_t = jax.random.split(k_arr)
    n = jnp.minimum(jax.random.poisson(k_n, lam), lanes)
    active = jnp.arange(lanes) < n
    k_hot, k_rack, k_u = jax.random.split(k_t, 3)
    hot = jax.random.bernoulli(k_hot, p_hot, (lanes,))
    draw = jax.random.uniform(k_rack, (lanes,))
    rack = (cum[None, :] <= draw[:, None]).sum(axis=1)
    first = jnp.where(hot, rack * rack_size, 0)
    size = jnp.where(hot, rack_size, m).astype(jnp.float32)
    u = jax.random.uniform(k_u, (lanes, 3))
    a = jnp.minimum(jnp.floor(u[:, 0] * size), size - 1)
    b = jnp.minimum(jnp.floor(u[:, 1] * (size - 1)), size - 2)
    b = jnp.where(b >= a, b + 1, b)
    lo, hi = jnp.minimum(a, b), jnp.maximum(a, b)
    c = jnp.minimum(jnp.floor(u[:, 2] * (size - 2)), size - 3)
    c = jnp.where(c >= lo, c + 1, c)
    c = jnp.where(c >= hi, c + 1, c)
    locs = jnp.stack([a, b, c], 1).astype(jnp.int32) + first[:, None]
    return jnp.sort(locs, 1), active, hot & active


def build(num_servers: int, rack_size: int, true_rates, p_hot: float,
          rack_weights, lanes: int, horizon: int, warmup: int, rounds: int,
          fill_iters: int, dtype=jnp.float32):
    """Returns jitted advance(carry, t0, lam, est, seed, slots) -> carry,
    carry = (q (M, K) i32, serving (M,) i32, mean_n f32, n_meas f32,
    completions i32, hot arrivals i32, remote-tier placements i32),
    advancing `slots` slots from slot t0 (a static count); slots at or
    past the horizon leave the carry as it is."""
    dt = jnp.dtype(dtype)
    m = num_servers
    if m % rack_size:
        raise ValueError(f"{m} servers do not fill racks of {rack_size}")
    cum = jnp.asarray(cumulative_shares(rack_weights, m // rack_size))
    true_rates = jnp.asarray(true_rates, jnp.float32)

    def step(lam, est, base, carry, t):
        q, serving, mean_n, n_meas, completions, hot_n, pool_n = carry
        k_arr, k_algo = jax.random.split(jax.random.fold_in(base, t))
        locs, active, hot = _arrivals(k_arr, lam, p_hot, cum, rack_size, m,
                                      lanes)
        k_route, k_serve = jax.random.split(k_algo)
        q2 = fleet._route(q, serving, est, rack_size, locs, active, rounds,
                          fill_iters, dt)
        pooled = q2[:, -1].sum() - q[:, -1].sum()
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
        new = (q2, s2, mean_n2, n_meas2, completions + done.sum() * counted,
               hot_n + hot.sum() * counted, pool_n + pooled * counted)
        live = t < horizon
        return tuple(jnp.where(live, a, b) for a, b in zip(new, carry)), None

    def advance(carry, t0, lam, est, seed, slots):
        est = est.astype(dt)
        base = jax.random.PRNGKey(seed)
        carry, _ = jax.lax.scan(lambda c, t: step(lam, est, base, c, t),
                                carry, t0 + jnp.arange(slots))
        return carry

    return jax.jit(advance, static_argnums=5)

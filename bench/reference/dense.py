"""Plain reference of the paper-scale slot model (Balanced-PANDAS and
JSQ-MaxWeight), written from the model's definition and independent of the
program under test.

One configuration is one sample path: per slot, a truncated-Poisson number
of arrivals, each task's three replicas drawn from the hot-rack mixture,
sequential routing of the slot's arrival lanes against the live queues,
Bernoulli service at the true rates, then idle servers take new work.  The
random numbers are data: they come from `jax.random` under the key
discipline the model fixes (`fold_in(PRNGKey(seed), t)` per slot, and the
documented splits below), so the reference and the program see the same
arrivals and coin flips and their sample paths agree decision by decision.

`dtype` is the precision of every rate, workload and score.  float32 is the
model's; bfloat16 gives the control that the comparison has to fail.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _tiers(task, rack_of):
    """(M,) tier of every server for one task: 0 on a replica, 1 in a
    replica's rack, 2 elsewhere."""
    m = rack_of.shape[0]
    sid = jnp.arange(m)
    local = (sid[:, None] == task[None, :]).any(axis=1)
    same_rack = (rack_of[:, None] == rack_of[task][None, :]).any(axis=1)
    return jnp.where(local, 0, jnp.where(same_rack, 1, 2))


def _pick_min(key, score):
    """Uniformly random index among the exact minima of `score`."""
    g = jax.random.gumbel(key, score.shape)
    return jnp.argmax(jnp.where(score == score.min(), g, -jnp.inf))


def _pick_max(key, score):
    g = jax.random.gumbel(key, score.shape)
    return jnp.argmax(jnp.where(score == score.max(), g, -jnp.inf))


def _arrivals(k_arr, lam, p_hot, rack_of, lanes):
    """(lanes, 3) sorted replica sets and the (lanes,) mask of lanes that
    carry a task this slot."""
    m = rack_of.shape[0]
    k_n, k_t = jax.random.split(k_arr)
    n = jnp.minimum(jax.random.poisson(k_n, lam), lanes)
    active = jnp.arange(lanes) < n
    k_hot, k_gum = jax.random.split(k_t)
    hot = jax.random.bernoulli(k_hot, p_hot, (lanes,))
    g = jax.random.gumbel(k_gum, (lanes, m))
    allowed = ~hot[:, None] | (rack_of[None, :] == 0)
    keyed = jnp.where(allowed, g, -jnp.inf)
    top3 = jnp.argsort(-keyed, axis=1)[:, :3]
    return jnp.sort(top3, axis=1), active


def _serve(k_serve, serving, true_rates):
    """Bernoulli completions of the tasks in service (class 1..K, 0 idle)."""
    p = jnp.where(serving > 0, true_rates[jnp.maximum(serving - 1, 0)], 0.0)
    return jax.random.bernoulli(k_serve, p)


def _bp_slot(state, k_algo, types, active, est, true_rates, rack_of, dt):
    q, serving = state
    k_route, k_serve = jax.random.split(k_algo)
    rows = jnp.arange(q.shape[0])

    def route(i, q):
        tier = _tiers(types[i], rack_of)
        rate = est[rows, tier]
        w = q[:, 0].astype(dt) / est[:, 0]
        w = w + q[:, 1].astype(dt) / est[:, 1]
        w = w + q[:, 2].astype(dt) / est[:, 2]
        resid = est[rows, jnp.maximum(serving - 1, 0)]
        w = w + jnp.where(serving > 0, jnp.asarray(1, dt) / resid,
                          jnp.asarray(0, dt))
        score = w / rate - rate * jnp.asarray(1e-6, dt)
        m = _pick_min(jax.random.fold_in(k_route, i), score)
        return q.at[m, tier[m]].add(active[i].astype(jnp.int32))

    q = jax.lax.fori_loop(0, types.shape[0], route, q)
    done = _serve(k_serve, serving, true_rates)
    serving = jnp.where(done, 0, serving)
    waiting = q > 0
    first = jnp.argmax(waiting, axis=1)
    take = (serving == 0) & waiting.any(axis=1)
    q = q - (take[:, None] & (jnp.arange(q.shape[1]) == first[:, None]))
    serving = jnp.where(take, first + 1, serving)
    return (q, serving), done.sum(), q.sum() + (serving > 0).sum()


def _mw_slot(state, k_algo, types, active, est, true_rates, rack_of, dt):
    q, serving = state
    m_total = q.shape[0]
    k_route, k_serve, k_claim = jax.random.split(k_algo, 3)

    def route(i, q):
        task = types[i]
        j = _pick_min(jax.random.fold_in(k_route, i), q[task].astype(jnp.float32))
        return q.at[task[j]].add(active[i].astype(jnp.int32))

    q = jax.lax.fori_loop(0, types.shape[0], route, q)
    done = _serve(k_serve, serving, true_rates)
    serving = jnp.where(done, 0, serving)
    k_perm, k_tie = jax.random.split(k_claim)
    order = jax.random.permutation(k_perm, m_total)
    sid = jnp.arange(m_total)

    def pair_tier(m, n):
        return jnp.where(m == n, 0, jnp.where(rack_of[m] == rack_of[n], 1, 2))

    def claim(i, qs):
        q, serving = qs
        m = order[i]
        score = est[m, pair_tier(m, sid)] * q.astype(dt)
        score = jnp.where(q > 0, score, -jnp.inf)
        n = _pick_max(jax.random.fold_in(k_tie, i), score)
        take = (serving[m] == 0) & (q > 0).any()
        q = q.at[n].add(-take.astype(jnp.int32))
        serving = serving.at[m].set(
            jnp.where(take, pair_tier(m, n) + 1, serving[m]))
        return q, serving

    q, serving = jax.lax.fori_loop(0, m_total, claim, (q, serving))
    return (q, serving), done.sum(), q.sum() + (serving > 0).sum()


SLOTS = {"balanced_pandas": _bp_slot, "jsq_maxweight": _mw_slot}


def build(policy: str, num_servers: int, rack_size: int, true_rates,
          p_hot: float, lanes: int, horizon: int, warmup: int,
          dtype=jnp.float32):
    """Returns jitted run(lam (N,), est (N, M, 3), seed (N,)) ->
    {mean_n, throughput, final_n}, each (N,), for N configurations."""
    slot = SLOTS[policy]
    dt = jnp.dtype(dtype)
    rack_of = jnp.asarray(np.arange(num_servers) // rack_size, jnp.int32)
    true_rates = jnp.asarray(true_rates, jnp.float32)

    def one(lam, est, seed):
        est = est.astype(dt)
        base = jax.random.PRNGKey(seed)
        if policy == "balanced_pandas":
            state0 = (jnp.zeros((num_servers, 3), jnp.int32),
                      jnp.zeros((num_servers,), jnp.int32))
        else:
            state0 = (jnp.zeros((num_servers,), jnp.int32),
                      jnp.zeros((num_servers,), jnp.int32))

        def step(carry, t):
            state, mean_n, n_meas, completions = carry
            k_arr, k_algo = jax.random.split(jax.random.fold_in(base, t))
            types, active = _arrivals(k_arr, lam, p_hot, rack_of, lanes)
            state, done, n = slot(state, k_algo, types, active, est,
                                  true_rates, rack_of, dt)
            counted = t >= warmup
            n_meas = n_meas + counted
            mean_n = mean_n + counted * (n.astype(jnp.float32) - mean_n) \
                / jnp.maximum(n_meas, 1.0)
            completions = completions + done * counted
            return (state, mean_n, n_meas, completions), None

        carry0 = (state0, jnp.float32(0), jnp.float32(0), jnp.int32(0))
        (state, mean_n, n_meas, completions), _ = jax.lax.scan(
            step, carry0, jnp.arange(horizon))
        q, serving = state
        return {"mean_n": mean_n,
                "throughput": completions / jnp.maximum(n_meas, 1.0),
                "final_n": (q.sum() + (serving > 0).sum()).astype(jnp.float32)}

    return jax.jit(jax.vmap(one))

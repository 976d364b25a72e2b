"""JSQ-MaxWeight (paper §3.3; Wang et al. 2016, extended by Xie et al. 2016).

One queue per server, holding tasks *local to that server*.  Routing: JSQ
among the arrival's 3 local queues.  Scheduling: an idle server m serves the
head task of

    argmax_n  rate(m, n) * Q_n(t)

where ``rate(m, n)`` is the estimated rate of the (m, n) pair tier (K=3:
alpha if n == m, beta if same rack, gamma otherwise) — tier-generic through
the `core/locality.py` seam.  The weight uses the scheduler's *estimated*
rates (robustness experiment); the realized service rate uses the true
rates via the (m,n)-relation proxy (exact for n=m; see DESIGN.md §3).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import claiming, locality as loc
from repro.core.policy import SlotPolicy, register_policy


class JsqMwState(NamedTuple):
    q: jnp.ndarray             # (M,) int32 waiting tasks (local to each server)
    serving_tier: jnp.ndarray  # (M,) int32 (m,n)-class in service; 0 idle


def init_state(topo: loc.Topology) -> JsqMwState:
    m = topo.num_servers
    return JsqMwState(jnp.zeros((m,), jnp.int32), jnp.zeros((m,), jnp.int32))


def num_in_system(s: JsqMwState) -> jnp.ndarray:
    return jnp.sum(s.q) + jnp.sum(s.serving_tier > 0)


def slot_step(s: JsqMwState, key: jax.Array, types: jnp.ndarray,
              active: jnp.ndarray, est: jnp.ndarray, true_rates: jnp.ndarray,
              ancestors: jnp.ndarray):
    """est: (M, K) per-server estimated rates; server m weighs queues with its
    own estimates est[m].  true_rates: (K,) shared or (M, K) per-server."""
    anc = loc.as_ancestors(ancestors)
    k_route, k_serve, k_claim = jax.random.split(key, 3)
    n_arr = types.shape[0]
    tmk = loc.per_server_rates(true_rates, s.q.shape[0])

    # 1. JSQ routing among each arrival's local servers.
    def body(i, q):
        return claiming.jsq_route_one(q, jax.random.fold_in(k_route, i),
                                      types[i], active[i])
    with jax.named_scope("sim.route"):
        q = jax.lax.fori_loop(0, n_arr, body, s.q)

    with jax.named_scope("sim.serve"):
        # 2. Service completions at the CURRENT true rates (re-derived from
        #    the stored class each slot, so scenario drift reaches
        #    in-flight tasks).
        done = jax.random.bernoulli(
            k_serve, claiming.tier_rates(s.serving_tier, tmk))
        completions = jnp.sum(done).astype(jnp.int32)
        serving_tier = jnp.where(done, 0, s.serving_tier)

    # 3. MaxWeight claims: weighted queue lengths with *estimated* rates.
    sid = jnp.arange(q.shape[0])

    def score_fn(m, qv):
        w = loc.pair_rate(m, sid, anc, est[m])
        return w * qv.astype(jnp.float32)

    def tier_fn(m, n):
        return claiming.pair_tier(m, n, anc)

    with jax.named_scope("sim.serve"):
        q, serving_tier = claiming.claim_loop(q, serving_tier, k_claim,
                                              score_fn, tier_fn)
    return JsqMwState(q, serving_tier), completions


@register_policy
class JsqMaxWeightPolicy(SlotPolicy):
    """JSQ-MaxWeight: join-shortest-queue routing + MaxWeight service over
    the (m, n) pair rates — throughput-optimal but NOT heavy-traffic
    delay-optimal, and the policy the paper shows degrades most under
    rate mis-estimation and drift.
    """

    name = "jsq_maxweight"

    def init_state(self, topo: loc.Topology, **opts) -> JsqMwState:
        return init_state(topo)

    def slot_step(self, s, key, types, active, est, true_rates, ancestors):
        return slot_step(s, key, types, active, est, true_rates, ancestors)

    def num_in_system(self, s: JsqMwState) -> jnp.ndarray:
        return num_in_system(s)

    def telemetry_gauges(self, s: JsqMwState):
        return claiming.telemetry_gauges(s.q, s.serving_tier)

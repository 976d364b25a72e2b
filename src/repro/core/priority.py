"""Priority algorithm (paper §3.1; Xie & Lu 2015).

Designed for TWO locality levels (local/remote); run here on the 3-level
rack-structured system exactly as the paper does, where it is no longer
throughput optimal.  One queue per server holding local tasks; JSQ routing
among the arrival's 3 local queues.  An idle server serves its own queue if
nonempty (local, rate alpha); otherwise it helps the LONGEST queue in the
system (unweighted argmax — the algorithm ignores rates entirely, so rate
mis-estimation does not change its decisions; it serves as the
rate-oblivious control arm in the robustness study).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import claiming, locality as loc
from repro.core.policy import SlotPolicy, register_policy


class PriorityState(NamedTuple):
    q: jnp.ndarray             # (M,) int32
    serving_tier: jnp.ndarray  # (M,) int32 (m,n)-class in service; 0 idle


def init_state(topo: loc.Topology) -> PriorityState:
    m = topo.num_servers
    return PriorityState(jnp.zeros((m,), jnp.int32),
                         jnp.zeros((m,), jnp.int32))


def num_in_system(s: PriorityState) -> jnp.ndarray:
    return jnp.sum(s.q) + jnp.sum(s.serving_tier > 0)


def slot_step(s: PriorityState, key: jax.Array, types: jnp.ndarray,
              active: jnp.ndarray, est: jnp.ndarray, true_rates: jnp.ndarray,
              ancestors: jnp.ndarray):
    del est  # the Priority algorithm never consults service rates
    anc = loc.as_ancestors(ancestors)
    k_route, k_serve, k_claim = jax.random.split(key, 3)
    n_arr = types.shape[0]
    tmk = loc.per_server_rates(true_rates, s.q.shape[0])

    def body(i, q):
        return claiming.jsq_route_one(q, jax.random.fold_in(k_route, i),
                                      types[i], active[i])
    with jax.named_scope("sim.route"):
        q = jax.lax.fori_loop(0, n_arr, body, s.q)

    with jax.named_scope("sim.serve"):
        done = jax.random.bernoulli(
            k_serve, claiming.tier_rates(s.serving_tier, tmk))
        completions = jnp.sum(done).astype(jnp.int32)
        serving_tier = jnp.where(done, 0, s.serving_tier)

    sid = jnp.arange(q.shape[0])
    big = jnp.float32(1e9)

    def score_fn(m, qv):
        # Own nonempty queue wins outright; otherwise longest queue.
        own = (sid == m) & (qv > 0)
        return jnp.where(own, big, qv.astype(jnp.float32))

    def tier_fn(m, n):
        return claiming.pair_tier(m, n, anc)

    with jax.named_scope("sim.serve"):
        q, serving_tier = claiming.claim_loop(q, serving_tier, k_claim,
                                              score_fn, tier_fn)
    return PriorityState(q, serving_tier), completions


@register_policy
class PriorityPolicy(SlotPolicy):
    """Priority: serve local tasks first, then rack-local, then remote —
    rate-oblivious 2-level design with a smaller capacity region than
    Balanced-PANDAS (its delay inside that region can still be excellent;
    see EXPERIMENTS.md §Reproduction).
    """

    name = "priority"

    def init_state(self, topo: loc.Topology, **opts) -> PriorityState:
        return init_state(topo)

    def slot_step(self, s, key, types, active, est, true_rates, ancestors):
        return slot_step(s, key, types, active, est, true_rates, ancestors)

    def num_in_system(self, s: PriorityState) -> jnp.ndarray:
        return num_in_system(s)

    def telemetry_gauges(self, s: PriorityState):
        return claiming.telemetry_gauges(s.q, s.serving_tier)

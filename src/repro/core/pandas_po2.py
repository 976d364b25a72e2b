"""Power-of-d-choices Balanced-PANDAS (``pandas_po2``).

A genuinely new point of comparison added through the unified policy
registry alone (no simulator or engine edits): instead of scanning all M
servers per arrival, the router samples ``d`` candidate servers uniformly
at random and compares weighted workloads only over the candidate set plus
the task's 3 local servers.  This is the affinity-scheduling reading of the
power-of-d-choices idea (Mitzenmacher 2001; Kavousi 2017, arXiv:1705.03125
for the locality-aware line): locals are always candidates — dropping them
would send almost every task remote at small d, which no locality-aware
sampler would do — and the d uniform samples provide the "second choice"
pressure that spills load off a hot rack.

Queueing structure, service dynamics and idle-server scheduling are exactly
Balanced-PANDAS (`core/balanced_pandas.py`); only the arrival routing rule
differs.  At d >= M the candidate set is the whole fleet and the score
surface coincides with full Balanced-PANDAS, so every decision is drawn
from the same score-minimal set — but tie-breaks use differently-split RNG
keys, so sample paths are not bitwise identical (the cross-check tests pin
score-level agreement per decision and statistical agreement on delays).
On the host path (`core/cluster.py::PandasPoDRouter`)
routing cost drops from O(M) to O(d): the interesting trade in the
robustness figures is how much heavy-traffic delay that buys back.

Like the full-scan policy, the *scheduler* sees estimated rates ``est``
while service runs at the true rates — so `pandas_po2` joins the
robustness-under-mis-estimation study as a rate-aware arm.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import balanced_pandas as bp
from repro.core import locality as loc
from repro.core.policy import SlotPolicy, register_policy


def route_one_po_d(s: bp.PandasState, key: jax.Array, task: jnp.ndarray,
                   active: jnp.ndarray, est: jnp.ndarray,
                   ancestors: jnp.ndarray, d: int) -> bp.PandasState:
    """Route one arrival over {3 locals} ∪ {d uniform samples}.

    Same score (W/rate with the infinitesimal faster-tier preference, see
    `bp.route_one`) restricted to the candidate mask; non-candidates score
    +inf so `random_argmin` never picks them.
    """
    anc = loc.as_ancestors(ancestors)
    m = anc.shape[1]
    k_cand, k_tie = jax.random.split(key)
    sampled = jax.random.choice(k_cand, m, (min(d, m),), replace=False)
    tier_m = loc.server_tiers(task, anc)
    cand = (tier_m == 0) | jnp.zeros((m,), bool).at[sampled].set(True)
    est_rate = jnp.take_along_axis(est, tier_m[:, None], axis=1)[:, 0]
    score = bp.workload(s, est) / est_rate - est_rate * 1e-6
    score = jnp.where(cand, score, jnp.inf)
    m_star = loc.random_argmin(k_tie, score)
    return bp.push_task(s, m_star, tier_m, active)


def slot_step(s: bp.PandasState, key: jax.Array, types: jnp.ndarray,
              active: jnp.ndarray, est: jnp.ndarray, true_rates: jnp.ndarray,
              ancestors: jnp.ndarray, d: int = 2):
    """One slot: po-d arrival routing, then shared PANDAS service/schedule."""
    anc = loc.as_ancestors(ancestors)
    k_route, k_serve = jax.random.split(key)
    n_arr = types.shape[0]

    def body(i, st):
        return route_one_po_d(st, jax.random.fold_in(k_route, i), types[i],
                              active[i], est, anc, d)
    with jax.named_scope("sim.route"):
        s = jax.lax.fori_loop(0, n_arr, body, s)

    with jax.named_scope("sim.serve"):
        return bp.serve_and_schedule(s, k_serve, true_rates)


@register_policy
class PandasPoDPolicy(SlotPolicy):
    """Power-of-d Balanced-PANDAS: score only the task's 3 locals plus d
    sampled candidates instead of all M servers — O(d) routing that
    trades a little exact-rate delay for a narrower error band.

    ``d`` is a static option (it shapes the candidate sample) carried by
    ``PolicyConfig("pandas_po2", {"d": ...})``; default 2, the classic
    power-of-two choices.
    """

    name = "pandas_po2"

    def __init__(self, d: int = 2):
        if d < 1:
            raise ValueError(f"need d >= 1 candidate samples, got {d}")
        self.d = d

    def init_state(self, topo: loc.Topology, **opts) -> bp.PandasState:
        return bp.init_state(topo)

    def slot_step(self, s, key, types, active, est, true_rates, ancestors):
        return slot_step(s, key, types, active, est, true_rates, ancestors,
                         d=self.d)

    def num_in_system(self, s: bp.PandasState) -> jnp.ndarray:
        return bp.num_in_system(s)

    def telemetry_gauges(self, s: bp.PandasState):
        return bp.telemetry_gauges(s)

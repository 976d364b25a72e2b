"""Balanced-PANDAS (paper §3.2; Xie et al. 2016, Yekkehkhany et al. 2018).

Queueing structure: K queues per server — one per locality tier, stored as
one (M, K) matrix ``q`` (column k holds tasks at tier k *to that server*;
the classic 3-tier instance is columns (local, rack-local, remote)).
Workload

    W_m = sum_k  q[m, k] / rates[m, k].

Routing: a type-``L`` arrival joins the queue of

    argmin_m  W_m / rate(m, L)

where ``rate(m, L)`` is the estimated rate at server m's tier for the
task, with random tie-breaking.  Scheduling: an idle server serves its
fastest-tier nonempty queue first (local > rack-local > ... > remote; the
class of the queue a task sits in is, by construction, its true service
class — PANDAS dynamics here are exact, unlike the (m,n)-proxy needed for
JSQ-MW).

Robustness experiment: the *scheduler* computes W and the routing rates with
estimated rates ``est`` of shape (M, K) — per-server per-tier, supporting
per-tier and per-server error models — while the *service* dynamics use the
true rates.

Scale-invariance note (beyond-paper analytical finding, see EXPERIMENTS.md):
if every estimate is scaled by one constant c, W scales by 1/c and the
routing score W/rate by 1/c^2, so the argmin — and hence the entire sample
path — is unchanged.  The same holds for MaxWeight (scores scale by c).  The
paper's robustness experiment is therefore only meaningful for errors that
are NOT a global rescaling (per-tier-subset or per-server errors).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import claiming, locality as loc
from repro.core.policy import SlotPolicy, register_policy


class PandasState(NamedTuple):
    q: jnp.ndarray        # (M, K) int32 waiting tasks per (server, tier)
    serving: jnp.ndarray  # (M,) int32 class in service (0 idle, 1..K)


def init_state(topo: loc.Topology) -> PandasState:
    m, k = topo.num_servers, topo.num_tiers
    return PandasState(jnp.zeros((m, k), jnp.int32),
                       jnp.zeros((m,), jnp.int32))


def num_in_system(s: PandasState) -> jnp.ndarray:
    return jnp.sum(s.q) + jnp.sum(s.serving > 0)


def telemetry_gauges(s: PandasState):
    """Per-tier queued counts + busy servers for the telemetry series —
    shared by every policy on the PANDAS (M, K) queue structure."""
    k = s.q.shape[1]
    out = {f"queued_tier{t}": s.q[:, t].sum().astype(jnp.float32)
           for t in range(k)}
    out["in_service"] = jnp.sum(s.serving > 0).astype(jnp.float32)
    return out


def workload(s: PandasState, est: jnp.ndarray) -> jnp.ndarray:
    """(M,) estimated weighted workload W_m (waiting + in-service share).

    est: (M, K) per-server estimated tier rates.  The in-service task
    contributes its expected residual 1/rate in the class it is being
    served at, matching the paper's W definition over queue contents (queues
    here exclude the in-service task, so we add it back).  The tier sum is
    accumulated left-associatively so the K=3 instance is bit-identical to
    the pre-refactor (q_local, q_rack, q_remote) formulation.
    """
    k = s.q.shape[1]
    w = s.q[:, 0] / est[:, 0]
    for t in range(1, k):
        w = w + s.q[:, t] / est[:, t]
    resid_rate = jnp.take_along_axis(
        est, jnp.clip(s.serving - 1, 0, k - 1)[:, None], axis=1)[:, 0]
    return w + jnp.where(s.serving > 0, 1.0 / resid_rate, 0.0)


def push_task(s: PandasState, m_star: jnp.ndarray, tier_m: jnp.ndarray,
              active: jnp.ndarray) -> PandasState:
    """Enqueue one (possibly inactive) arrival at server `m_star`, whose
    tier for this task is ``tier_m[m_star]``."""
    inc = active.astype(jnp.int32)
    return PandasState(
        q=s.q.at[m_star, tier_m[m_star]].add(inc),
        serving=s.serving,
    )


def route_one(s: PandasState, key: jax.Array, task: jnp.ndarray,
              active: jnp.ndarray, est: jnp.ndarray,
              ancestors: jnp.ndarray, server_mask=None) -> PandasState:
    """Route a single arrival against the live workloads (estimated rates).

    Tie-break: among minimal scores, prefer the faster tier (then random).
    The paper says "ties are broken randomly", but read literally that
    routes most arrivals REMOTE whenever workloads tie at 0 (any idle
    fleet), which no real scheduler does and which inverts the Fig. 1
    ordering at sub-critical load — see EXPERIMENTS.md §Reproduction.  The
    infinitesimal rate preference only discriminates exact ties.

    ``server_mask`` ((M,) bool, autoscaling seam) is a Python-level
    option: None compiles the exact classic program; a mask sends
    descaled servers' scores to +inf so they take no new work (their
    queues keep draining through the service phase).
    """
    tier_m = loc.server_tiers(task, ancestors)  # (M,) tier of each server
    est_rate = jnp.take_along_axis(est, tier_m[:, None], axis=1)[:, 0]
    score = workload(s, est) / est_rate - est_rate * 1e-6
    if server_mask is not None:
        score = jnp.where(server_mask, score, jnp.inf)
    m_star = loc.random_argmin(key, score)
    return push_task(s, m_star, tier_m, active)


def service_completions(s: PandasState, k_serve: jax.Array,
                        true_rates: jnp.ndarray):
    """Bernoulli service completions at the *true* rates.

    `true_rates` is the shared ``(K,)`` vector or a per-server ``(M, K)``
    matrix (scenario fault injection).  Returns (done (M,) bool,
    completions int32) — the per-server mask is what the blind policy's
    estimator consumes.
    """
    tmk = loc.per_server_rates(true_rates, s.serving.shape[0])
    done = jax.random.bernoulli(k_serve, claiming.tier_rates(s.serving, tmk))
    return done, jnp.sum(done).astype(jnp.int32)


def schedule_idle(s: PandasState, done: jnp.ndarray) -> PandasState:
    """Idle servers (post-completion) pick their fastest nonempty tier
    queue (local > rack-local > ... > remote, conflict-free)."""
    k = s.q.shape[1]
    serving = jnp.where(done, 0, s.serving)
    nonempty = s.q > 0                              # (M, K)
    first = jnp.argmax(nonempty, axis=1)            # fastest nonempty tier
    has_task = jnp.any(nonempty, axis=1)
    take = (serving == 0) & has_task
    dec = take[:, None] & (jnp.arange(k)[None, :] == first[:, None])
    return PandasState(
        q=s.q - dec.astype(jnp.int32),
        serving=jnp.where(take, first + 1, serving).astype(jnp.int32),
    )


def serve_and_schedule(s: PandasState, k_serve: jax.Array,
                       true_rates: jnp.ndarray):
    """Service completions (true rates) + idle-server scheduling.

    Shared by every PANDAS-queue-structure policy (full-scan, power-of-d
    and blind routing only differ in the arrival phase / rate source).
    Returns (state, completions).
    """
    done, completions = service_completions(s, k_serve, true_rates)
    return schedule_idle(s, done), completions


def slot_step(s: PandasState, key: jax.Array, types: jnp.ndarray,
              active: jnp.ndarray, est: jnp.ndarray, true_rates: jnp.ndarray,
              ancestors: jnp.ndarray, server_mask=None):
    """One time slot: arrivals -> service completions -> scheduling.

    Returns (state, completions_this_slot).  ``server_mask=None`` (the
    default) compiles the exact classic step; see `route_one`.
    """
    anc = loc.as_ancestors(ancestors)
    k_route, k_serve = jax.random.split(key)
    n_arr = types.shape[0]

    # Sequential routing of the slot's arrivals (workloads update in-slot).
    def body(i, st):
        return route_one(st, jax.random.fold_in(k_route, i), types[i],
                         active[i], est, anc, server_mask=server_mask)
    with jax.named_scope("sim.route"):
        s = jax.lax.fori_loop(0, n_arr, body, s)

    with jax.named_scope("sim.serve"):
        return serve_and_schedule(s, k_serve, true_rates)


@register_policy
class BalancedPandasPolicy(SlotPolicy):
    """Balanced-PANDAS: weighted-workload routing over estimated per-tier
    rates — the paper's headline throughput- and heavy-traffic-optimal
    policy.  Arrivals go to the server minimizing workload W / rate over
    the K locality tiers; robust to rate mis-estimation (paper §4) and
    the reference point every other arm is compared to.
    """

    name = "balanced_pandas"
    supports_server_mask = True

    def init_state(self, topo: loc.Topology, **opts) -> PandasState:
        return init_state(topo)

    def slot_step(self, s, key, types, active, est, true_rates, ancestors,
                  server_mask=None):
        return slot_step(s, key, types, active, est, true_rates, ancestors,
                         server_mask=server_mask)

    def num_in_system(self, s: PandasState) -> jnp.ndarray:
        return num_in_system(s)

    def telemetry_gauges(self, s: PandasState):
        return telemetry_gauges(s)

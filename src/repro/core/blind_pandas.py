"""Blind Balanced-PANDAS: online rate learning inside the simulator
(Blind GB-PANDAS, Yekkehkhany & Nagi 2020 — the paper's "future work" arm).

Identical queueing structure and service dynamics to `balanced_pandas`, but
the *scheduler's* rates are not an input: the policy starts from a prior,
observes every completed task's (server, tier, service time) and maintains
per-(server, tier) EWMA estimates in its own `lax.scan` state — the JAX
counterpart of the host-side `EwmaRateEstimator` that the serving engine
and data pipeline already run.  The ``est`` argument of `slot_step` is
deliberately ignored: a blind scheduler has no oracle.

This is the second arm of the drift study (`robustness.drift_study`): under
time-varying scenarios (stragglers, rack congestion, hotspot migration) a
fixed prior — even one exactly right at t=0 — goes stale, while the blind
EWMA tracks the drift.  The estimate floor keeps routing finite while a
(server, tier) pair is unobserved; like the host estimator, the service
TIME is EWMA'd and inverted on read (1/E[T] is the consistent estimator).

The prior is a strictly-decreasing K-vector matching the topology's tier
count (checked at `init_state`); the classic 3-tier default is
``(0.5, 0.45, 0.25)``.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import balanced_pandas as bp
from repro.core import locality as loc
from repro.core.estimator import ewma_time_update
from repro.core.policy import SlotPolicy, register_policy


class BlindPandasState(NamedTuple):
    core: bp.PandasState
    age: jnp.ndarray   # (M,) int32 completed slots of the in-service task
    tbar: jnp.ndarray  # (M, K) f32 EWMA'd service time per (server, tier)


@register_policy
class BlindPandasPolicy(SlotPolicy):
    """Blind GB-PANDAS: Balanced-PANDAS that starts from a prior and keeps
    per-(server, tier) EWMA rate estimates inside the scan state,
    re-learning online when the true rates drift.

    Options: ``prior`` — the (K,) tier rates the estimates start from;
    ``decay`` — EWMA decay per observation; ``floor`` — lower clamp on the
    read-side rate estimates.  Travel in
    ``PolicyConfig("blind_pandas", {"prior": (...), ...})``.
    """

    name = "blind_pandas"

    def __init__(self, prior: Sequence[float] = (0.5, 0.45, 0.25),
                 decay: float = 0.98, floor: float = 1e-3):
        prior = tuple(float(p) for p in prior)
        if len(prior) < 2 or any(not 0.0 < p <= 1.0 for p in prior):
            raise ValueError(f"prior must be >= 2 tier rates in (0, 1], "
                             f"got {prior}")
        if not 0.0 < decay < 1.0:
            raise ValueError(f"decay must be in (0, 1), got {decay}")
        self.prior: Tuple[float, ...] = prior
        self.decay = decay
        self.floor = floor

    def init_state(self, topo: loc.Topology, **opts) -> BlindPandasState:
        m = topo.num_servers
        if len(self.prior) != topo.num_tiers:
            raise ValueError(f"prior has {len(self.prior)} tiers but the "
                             f"topology has {topo.num_tiers}")
        tbar = jnp.tile(1.0 / jnp.asarray(self.prior, jnp.float32), (m, 1))
        return BlindPandasState(core=bp.init_state(topo),
                                age=jnp.zeros((m,), jnp.int32), tbar=tbar)

    def estimates(self, s: BlindPandasState) -> jnp.ndarray:
        """(M, K) current rate estimates the routing decisions use."""
        return jnp.clip(1.0 / jnp.maximum(s.tbar, 1e-9), self.floor, 1.0)

    def slot_step(self, s: BlindPandasState, key, types, active, est,
                  true_rates, ancestors):
        del est  # blind: the policy trusts only its own observations
        anc = loc.as_ancestors(ancestors)
        my_est = self.estimates(s)
        k_route, k_serve = jax.random.split(key)
        n_arr = types.shape[0]

        core = s.core

        def body(i, st):
            return bp.route_one(st, jax.random.fold_in(k_route, i), types[i],
                                active[i], my_est, anc)
        with jax.named_scope("sim.route"):
            core = jax.lax.fori_loop(0, n_arr, body, core)

        with jax.named_scope("sim.serve"):
            # Exactly balanced_pandas's service/scheduling dynamics, via
            # the shared helpers — only the estimator bookkeeping is new.
            done, completions = bp.service_completions(core, k_serve,
                                                       true_rates)

            # Observe: a task completing this slot took age+1 slots of
            # service.
            k = s.tbar.shape[1]
            tier = jnp.clip(core.serving - 1, 0, k - 1)
            tbar = ewma_time_update(s.tbar, done, tier,
                                    (s.age + 1).astype(jnp.float32),
                                    self.decay)

            new_core = bp.schedule_idle(core, done)
            # Tasks that survived the slot age one slot; completed / fresh
            # / idle servers reset to zero.
            age = jnp.where((core.serving > 0) & ~done, s.age + 1, 0)
        return BlindPandasState(new_core, age, tbar), completions

    def num_in_system(self, s: BlindPandasState) -> jnp.ndarray:
        return bp.num_in_system(s.core)

    def extra_metrics(self, s: BlindPandasState):
        """Mean learned local-tier rate — a cheap observability hook for the
        drift figures (tracks straggler windows opening and closing)."""
        return {"est_alpha_mean": jnp.mean(self.estimates(s)[:, 0])}

    def telemetry_gauges(self, s: BlindPandasState):
        gauges = bp.telemetry_gauges(s.core)
        gauges["est_alpha_mean"] = jnp.mean(self.estimates(s)[:, 0])
        return gauges

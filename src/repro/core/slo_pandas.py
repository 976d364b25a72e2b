"""SLO-conditioned Balanced-PANDAS: p99-aware routing and draining.

Balanced-PANDAS optimizes the MEAN workload; PR 7's tail study found that
at rho = 0.99 the mean-optimal policy is no longer the p99 winner.  This
policy closes the loop: it reads the in-scan telemetry recorder's running
sojourn-p99 estimate (`SimTelemetry.live_quantile`, delivered by the
simulator as the ``signals`` kwarg) and switches behaviour only while the
estimate breaches ``slo_target``:

  * **routing** — the score gains a ``drain_bias * W_m`` penalty, i.e.
    arrivals weigh a server's total backlog ``drain_bias`` x more heavily
    relative to its locality rate.  Under breach the policy trades
    locality for equalizing the longest workloads — exactly the regime
    where the tail lives in a few deep queues;
  * **scheduling** — idle servers serve their LONGEST queue (most tasks)
    instead of their fastest tier, draining the backlog that holds the
    oldest work (queues are FIFO within a tier, so the longest queue
    bounds the oldest waiting task).

Outside a breach — and whenever ``signals`` is absent (``telemetry=None``:
there is nothing to read) — every decision compiles to the exact
Balanced-PANDAS program: same key splits, same scores, same tie-breaks.
The signal-free path is pinned bitwise against ``balanced_pandas`` in
tests/test_control.py.  This is the documented exception to the
telemetry-purity invariant: enabling telemetry deliberately changes this
policy's sample path (``uses_signals = True``; the purity test skips it).

The breach flag is NaN-safe by construction: the live p99 is NaN until
the first completion is binned (NaN > target is False -> no breach) and
inf once the estimate passes the histogram range (inf > target is True
-> breach, correctly).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import balanced_pandas as bp
from repro.core import locality as loc
from repro.core.policy import SlotPolicy, register_policy


def _route_one_slo(s, key, task, active, est, ancestors, server_mask,
                   breach, drain_bias: float):
    """`bp.route_one` with the breach-gated workload penalty (identical
    decisions — same key, same tie-break — when ``breach`` is False)."""
    tier_m = loc.server_tiers(task, ancestors)
    est_rate = jnp.take_along_axis(est, tier_m[:, None], axis=1)[:, 0]
    w = bp.workload(s, est)
    score = w / est_rate - est_rate * 1e-6
    score = jnp.where(breach, score + drain_bias * w, score)
    if server_mask is not None:
        score = jnp.where(server_mask, score, jnp.inf)
    m_star = loc.random_argmin(key, score)
    return bp.push_task(s, m_star, tier_m, active)


def _schedule_idle_slo(s, done, breach):
    """`bp.schedule_idle` whose tier pick flips to the LONGEST nonempty
    queue under breach (fastest nonempty tier otherwise)."""
    k = s.q.shape[1]
    serving = jnp.where(done, 0, s.serving)
    nonempty = s.q > 0
    fastest = jnp.argmax(nonempty, axis=1)
    longest = jnp.argmax(s.q, axis=1)
    first = jnp.where(breach, longest, fastest)
    has_task = jnp.any(nonempty, axis=1)
    take = (serving == 0) & has_task
    dec = take[:, None] & (jnp.arange(k)[None, :] == first[:, None])
    return bp.PandasState(
        q=s.q - dec.astype(jnp.int32),
        serving=jnp.where(take, first + 1, serving).astype(jnp.int32),
    )


@register_policy
class SloPandasPolicy(SlotPolicy):
    """SLO-conditioned Balanced-PANDAS: while the in-scan sojourn-p99
    estimate breaches ``slo_target`` (slots), routing adds a
    ``drain_bias`` x workload penalty and idle servers drain their
    longest queue; otherwise — and always when telemetry is off — it IS
    Balanced-PANDAS, bitwise.  Requires ``telemetry=`` to act
    (``signals`` carry the live p99); without it the breach can never be
    observed and the policy silently degrades to the base program.
    """

    name = "slo_pandas"
    supports_server_mask = True
    uses_signals = True

    def __init__(self, slo_target: float = 96.0, drain_bias: float = 0.25):
        if slo_target <= 0.0:
            raise ValueError(f"slo_target must be > 0, got {slo_target}")
        if drain_bias < 0.0:
            raise ValueError(f"drain_bias must be >= 0, got {drain_bias}")
        self.slo_target = float(slo_target)
        self.drain_bias = float(drain_bias)

    def init_state(self, topo: loc.Topology, **opts) -> bp.PandasState:
        return bp.init_state(topo)

    def slot_step(self, s, key, types, active, est, true_rates, ancestors,
                  server_mask=None, signals=None):
        if signals is None:
            # No telemetry -> nothing to condition on: the exact
            # Balanced-PANDAS program (bitwise; pinned in tests).
            return bp.slot_step(s, key, types, active, est, true_rates,
                                ancestors, server_mask=server_mask)
        breach = signals["delay_p99"] > self.slo_target
        anc = loc.as_ancestors(ancestors)
        k_route, k_serve = jax.random.split(key)

        def body(i, st):
            return _route_one_slo(st, jax.random.fold_in(k_route, i),
                                  types[i], active[i], est, anc, server_mask,
                                  breach, self.drain_bias)
        with jax.named_scope("sim.route"):
            s = jax.lax.fori_loop(0, types.shape[0], body, s)
        with jax.named_scope("sim.serve"):
            done, completions = bp.service_completions(s, k_serve,
                                                       true_rates)
            return _schedule_idle_slo(s, done, breach), completions

    def num_in_system(self, s: bp.PandasState) -> jnp.ndarray:
        return bp.num_in_system(s)

    def telemetry_gauges(self, s: bp.PandasState):
        return bp.telemetry_gauges(s)

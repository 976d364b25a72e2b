"""FIFO — Hadoop's default scheduler (paper §1, §4 comparison baseline).

A single global FIFO queue of tasks; an idle server takes the head task
regardless of locality, so the realized service rate is the task's true
locality tier w.r.t. the serving server (exact — the ring buffer stores task
types).  FIFO ignores both queue state and rates, so estimation errors do not
change its decisions; it is neither heavy-traffic delay optimal nor
throughput optimal on the rack model, and its queue diverges inside the other
algorithms' capacity region (paper Fig. 1).  The ring buffer is bounded
(``cap``); arrivals beyond it are dropped and counted, which caps the
measured delay at saturation instead of overflowing.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import locality as loc
from repro.core.claiming import tier_rates
from repro.core.policy import SlotPolicy, register_policy


class FifoState(NamedTuple):
    buf: jnp.ndarray           # (cap, 3) int32 ring buffer of task types
    head: jnp.ndarray          # () int32 index of oldest task
    count: jnp.ndarray         # () int32 number queued
    serving_tier: jnp.ndarray  # (M,) int32 class in service; 0 idle
    drops: jnp.ndarray         # () int32 arrivals dropped (buffer full)


def init_state(topo: loc.Topology, cap: int = 32768) -> FifoState:
    return FifoState(
        buf=jnp.zeros((cap, 3), jnp.int32),
        head=jnp.zeros((), jnp.int32),
        count=jnp.zeros((), jnp.int32),
        serving_tier=jnp.zeros((topo.num_servers,), jnp.int32),
        drops=jnp.zeros((), jnp.int32),
    )


def num_in_system(s: FifoState) -> jnp.ndarray:
    return s.count + jnp.sum(s.serving_tier > 0).astype(jnp.int32)


def slot_step(s: FifoState, key: jax.Array, types: jnp.ndarray,
              active: jnp.ndarray, est: jnp.ndarray, true_rates: jnp.ndarray,
              ancestors: jnp.ndarray):
    del est  # FIFO consults nothing
    anc = loc.as_ancestors(ancestors)
    cap = s.buf.shape[0]
    k_serve, k_perm = jax.random.split(key)
    n_arr = types.shape[0]
    tmk = loc.per_server_rates(true_rates, s.serving_tier.shape[0])

    # 1. Push arrivals (drop when full).
    def push(i, st):
        buf, head, count, drops = st
        fits = active[i] & (count < cap)
        pos = (head + count) % cap
        buf = buf.at[pos].set(jnp.where(fits, types[i], buf[pos]))
        count = count + fits.astype(jnp.int32)
        drops = drops + (active[i] & ~fits).astype(jnp.int32)
        return buf, head, count, drops

    with jax.named_scope("sim.route"):
        buf, head, count, drops = jax.lax.fori_loop(
            0, n_arr, push, (s.buf, s.head, s.count, s.drops))

    with jax.named_scope("sim.serve"):
        # 2. Service completions at the CURRENT true rates (class stored,
        #    rate re-derived each slot -> scenario drift reaches in-flight
        #    tasks).
        done = jax.random.bernoulli(k_serve, tier_rates(s.serving_tier, tmk))
        completions = jnp.sum(done).astype(jnp.int32)
        serving_tier = jnp.where(done, 0, s.serving_tier)

        # 3. Idle servers pop heads in random server order.
        order = jax.random.permutation(k_perm, serving_tier.shape[0])

    def pop(i, st):
        head, count, serving_tier = st
        m = order[i]
        take = (serving_tier[m] == 0) & (count > 0)
        task = buf[head % cap]
        tier = loc.server_tiers(task, anc)[m] + 1  # service class 1..K
        serving_tier = serving_tier.at[m].set(
            jnp.where(take, tier, serving_tier[m]).astype(jnp.int32))
        head = (head + take.astype(jnp.int32)) % cap
        count = count - take.astype(jnp.int32)
        return head, count, serving_tier

    with jax.named_scope("sim.serve"):
        head, count, serving_tier = jax.lax.fori_loop(
            0, serving_tier.shape[0], pop, (head, count, serving_tier))

    return FifoState(buf, head, count, serving_tier, drops), completions


@register_policy
class FifoPolicy(SlotPolicy):
    """Global-FIFO: one shared rate-oblivious queue, idle servers pull in
    arrival order (the Hadoop-default floor every comparison stands on).

    `cap` (ring-buffer bound, a static shape) is the policy option that used
    to be special-cased in the simulator; it now travels in a
    ``PolicyConfig("fifo", {"cap": ...})``, and the drop counter surfaces
    through `extra_metrics`.
    """

    name = "fifo"

    def __init__(self, cap: int = 32_768):
        self.cap = cap

    def init_state(self, topo: loc.Topology, **opts) -> FifoState:
        return init_state(topo, cap=self.cap)

    def slot_step(self, s, key, types, active, est, true_rates, ancestors):
        return slot_step(s, key, types, active, est, true_rates, ancestors)

    def num_in_system(self, s: FifoState) -> jnp.ndarray:
        return num_in_system(s)

    def extra_metrics(self, s: FifoState):
        return {"drops": s.drops.astype(jnp.float32)}

    def telemetry_gauges(self, s: FifoState):
        # one global queue: its depth plus busy servers (tiers resolve
        # only when an idle server pulls the head task)
        return {"queued": s.count.astype(jnp.float32),
                "in_service": jnp.sum(s.serving_tier > 0)
                .astype(jnp.float32)}

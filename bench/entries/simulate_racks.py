"""Entry: `repro.core.simulator.simulate` of one configuration on the fleet
backend under hot traffic spread over weighted racks (the `hot_racks`
scenario); entries/simulate.py's entry otherwise, with its window, seeds
and check.

The configuration states the data layout: the hot share of tasks
`p_hot`, and `rack_weights`, cycled over the racks; a hot task's
replicas lie in one rack drawn by those weights.  The arrival rate is the
workload's load times the fluid capacity of the cluster (bench/fluid.py)
whose hot pool is the racks of positive weight, taken as equally loaded,
and the arrival lanes are `int(lanes_per_lambda * rate)`.

The check is that of entries/simulate.py, with the plain reference
bench/reference/fleet_racks.py and two counters of the carry besides:
the hot tasks that arrived and the tasks the remote pool placed over the
measured slots.  `replay_gap` also compares the window's `hot_share` and
`pool_share` (those counters per task offered), and `state_gap` adds the
relative gap of each counter to the reference's.
"""

from __future__ import annotations

import numpy as np

from entries import simulate


class Entry(simulate.Entry):

    def __init__(self, config: dict, workload: dict, seed: int, device):
        import jax
        from fluid import capacity
        from repro import workloads as wl
        from repro.core import locality as loc, simulator as sim
        from repro.sharding import sim as fleet_sim

        self.jax, self.sim, self.fleet_sim = jax, sim, fleet_sim
        self.config, self.workload, self.seed = config, workload, seed
        self.device = device
        self.policy = workload["policy"]
        m, size = config["num_servers"], config["rack_size"]
        weights = config["rack_weights"]
        hot_racks = sum(weights[r % len(weights)] > 0
                        for r in range(m // size))
        self.lam = float(workload["load"] * capacity(
            m, hot_racks * size, config["rates"], config["p_hot"]))
        self.cfg = sim.SimConfig(
            topo=loc.Topology(m, size),
            true_rates=loc.Rates(*config["rates"]), p_hot=config["p_hot"],
            max_arrivals=int(config["lanes_per_lambda"] * self.lam),
            horizon=config["horizon"], warmup=config["warmup"])
        # the segment the `hot_racks` scenario builds, spelled out so that
        # a fleet path without per-rack weights refuses it as unsupported
        self.scenario = wl.Scenario("hot_racks", (wl.Segment(
            start=0.0, rack_weights=tuple(weights), p_hot=config["p_hot"]),))
        self.fc = fleet_sim.FleetConfig(rounds=config["rounds"],
                                        fill_iters=config["fill_iters"])
        true = np.asarray(config["rates"], np.float32)
        self.est = np.ascontiguousarray(np.broadcast_to(true, (m, len(true))))
        self.outputs = []            # (seed, metrics) of each window call

    def _simulate(self, seed: int):
        return self.sim.simulate(self.policy, self.cfg, self.lam, self.est,
                                 seed=seed, scenario=self.scenario,
                                 fleet=self.fc)

    def facts(self) -> dict:
        shares = {k: float(np.mean([out[k] for _, out in self.outputs]))
                  for k in ("hot_share", "pool_share")}
        return {**super().facts(), **shares}

    # -- the check ---------------------------------------------------------

    def _host(self, carry):
        """The carry's seven leaves on the host: the five of
        entries/simulate.py, then hot arrivals and pool placements."""
        return tuple(np.asarray(x) for x in self.jax.tree.leaves(carry))

    def _replay(self, seed: int, picks):
        jnp = self.jax.numpy
        cfg, weights = self.fleet_sim.stationary_traffic(self.cfg,
                                                         self.scenario)
        init, chunk = self.fleet_sim._jitted_chunk(self.policy, cfg, self.fc,
                                                   weights)
        size = self.fc.chunk
        carry, kept = init(), {}
        for ci in range(-(-self.cfg.horizon // size)):
            if ci in picks:
                start = self._host(carry)
            carry = chunk(carry, jnp.int32(ci * size), *self._chunk_args(seed))
            if ci in picks:
                kept[ci] = (start, self._host(carry))
        return kept, self._host(carry), chunk

    def _metrics(self, carry) -> dict:
        out = super()._metrics(carry[:5])
        offered = np.float32(self.lam) * carry[3]
        per_offered = np.where(offered > 0, offered, 1.0)
        for name, count in (("hot_share", carry[5]), ("pool_share", carry[6])):
            out[name] = float(np.where(offered > 0, count / per_offered,
                                       np.nan))
        return out

    def _advance(self, carry, ci, seed, dtype="float32", rounds=None,
                 fill_iters=None):
        jnp = self.jax.numpy
        from reference import fleet_racks

        adv = fleet_racks.build(
            self.config["num_servers"], self.config["rack_size"],
            self.config["rates"], self.cfg.p_hot, self.config["rack_weights"],
            self.cfg.max_arrivals, self.cfg.horizon, self.cfg.warmup,
            rounds or self.fc.rounds, fill_iters or self.fc.fill_iters,
            dtype=dtype)
        with self.jax.default_device(self.device):
            out = adv(tuple(jnp.asarray(x) for x in carry),
                      jnp.int32(ci * self.fc.chunk), *self._chunk_args(seed),
                      self.fc.chunk)
        return tuple(np.asarray(x) for x in out)

    @staticmethod
    def _state_gap(a, b) -> float:
        """Gap of carry `a` from the reference's `b`: that of
        entries/simulate.py, plus each counter's relative gap."""
        counters = sum(abs(int(x) - int(y)) / max(abs(int(y)), 1)
                       for x, y in zip(a[5:], b[5:]))
        return simulate.Entry._state_gap(a[:5], b[:5]) + counters

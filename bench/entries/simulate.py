"""Entry: `repro.core.simulator.simulate` of one configuration on the fleet
backend (`sharding/sim.fleet_simulate`): the horizon is run as donated
chunks of a compiled chunk program, which holds the Pallas route kernel on
the TPU.

Inputs from the run's seed: the simulation seed of every call.  A call is
one `simulate`; its work is `horizon` slots of one configuration.  The
arrival rate is the workload's load times the fluid capacity of the
configuration's cluster under the workload's `p_hot` (bench/fluid.py), and
the arrival lanes are `int(lanes_per_lambda * rate)`.

The check, after the window, on one call drawn from the seed:

* `replay_gap`: the call is replayed through the same compiled chunk
  program the window drove (the program's own chunk cache hands back the
  same object), keeping the carry at the start and end of `chunks` chunks
  drawn from the measured part of the horizon.  The metrics of the
  replay's last carry must equal what the window's call returned: an
  exact comparison, limit 0.
* `state_gap`: from each kept start carry, the plain reference
  (bench/reference/fleet.py) advances the same chunk with the same random
  numbers; the number is the L1 distance of the queues, plus the servers
  whose class in service differs, plus the difference in completions,
  over the tasks in the system at the chunk's end, plus the relative gap
  of the running mean of tasks in the system (Little's law: mean_n is
  what mean_delay divides) and the gap in measured slots (what throughput
  divides), the largest over the kept chunks.  It reaches every routing
  decision of the chunk (private argmin, rank clamp, water-fill), service,
  and the accumulators behind the metrics users read.
* `route_kernel` (on the TPU): 1 when the compiled chunk holds the route
  kernel (`tpu_custom_call`), else 0; limit 1.
"""

from __future__ import annotations

import numpy as np


def seed_seq(seed: int, *path: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([seed % 2**64, *path])


class Entry:
    backend = "fleet"

    def __init__(self, config: dict, workload: dict, seed: int, device):
        import jax
        from fluid import capacity
        from repro.core import locality as loc, simulator as sim
        from repro.sharding import sim as fleet_sim

        self.jax, self.sim, self.fleet_sim = jax, sim, fleet_sim
        self.config, self.workload, self.seed = config, workload, seed
        self.device = device
        self.policy = workload["policy"]
        m = config["num_servers"]
        self.lam = float(workload["load"] * capacity(
            m, config["rack_size"], config["rates"], workload["p_hot"]))
        self.cfg = sim.SimConfig(
            topo=loc.Topology(m, config["rack_size"]),
            true_rates=loc.Rates(*config["rates"]), p_hot=workload["p_hot"],
            max_arrivals=int(config["lanes_per_lambda"] * self.lam),
            horizon=config["horizon"], warmup=config["warmup"])
        self.fc = fleet_sim.FleetConfig(rounds=config["rounds"],
                                        fill_iters=config["fill_iters"])
        true = np.asarray(config["rates"], np.float32)
        self.est = np.ascontiguousarray(np.broadcast_to(true, (m, len(true))))
        self.outputs = []            # (seed, metrics) of each window call

    def _seed(self, tag: int, i: int) -> int:
        return int(seed_seq(self.seed, tag, i).generate_state(1, np.uint32)[0])

    def _simulate(self, seed: int):
        return self.sim.simulate(self.policy, self.cfg, self.lam, self.est,
                                 seed=seed, fleet=self.fc)

    def warmup(self) -> None:
        self._simulate(self._seed(3, 0))

    def call(self, i: int) -> int:
        seed = self._seed(1, i)
        self.outputs.append((seed, self._simulate(seed)))
        return self.cfg.horizon

    def attempted(self, stats: dict) -> int:
        return stats["calls"]

    def facts(self) -> dict:
        return {"backend": self.backend, "kernel": "fleet_route",
                "kernel_shape": {"b": self.cfg.max_arrivals,
                                 "m": self.config["num_servers"],
                                 "k": len(self.config["rates"]),
                                 "depth": 1}}

    # -- the check ---------------------------------------------------------

    def _chunk_args(self, seed: int):
        jnp = self.jax.numpy
        return (jnp.float32(self.lam), jnp.asarray(self.est, jnp.float32),
                jnp.asarray(seed, jnp.uint32))

    def _replay(self, seed: int, picks):
        """Run the call again through the window's compiled chunk program;
        returns ({chunk: (start carry, end carry)}, last carry) on the host."""
        jnp = self.jax.numpy
        init, chunk = self.fleet_sim._jitted_chunk(self.policy, self.cfg,
                                                   self.fc)
        size = self.fc.chunk
        carry, kept = init(), {}
        for ci in range(-(-self.cfg.horizon // size)):
            if ci in picks:
                start = tuple(np.asarray(x) for x in carry)
            carry = chunk(carry, jnp.int32(ci * size), *self._chunk_args(seed))
            if ci in picks:
                kept[ci] = (start, tuple(np.asarray(x) for x in carry))
        last = tuple(np.asarray(x) for x in carry)
        return kept, last, chunk

    def _metrics(self, carry) -> dict:
        q, serving, mean_n, n_meas, compl = carry
        return {"mean_n": float(mean_n),
                "mean_delay": float(mean_n / np.float32(self.lam)),
                "throughput": float(compl / np.maximum(n_meas, 1.0)),
                "final_n": float(q.sum() + (serving > 0).sum())}

    def _advance(self, carry, ci, seed, dtype="float32", rounds=None,
                 fill_iters=None):
        jnp = self.jax.numpy
        from reference import fleet

        adv = fleet.build(self.config["num_servers"], self.config["rack_size"],
                          self.config["rates"], self.cfg.p_hot,
                          self.cfg.max_arrivals, self.cfg.horizon,
                          self.cfg.warmup, rounds or self.fc.rounds,
                          fill_iters or self.fc.fill_iters, dtype=dtype)
        with self.jax.default_device(self.device):
            out = adv(tuple(jnp.asarray(x) for x in carry),
                      jnp.int32(ci * self.fc.chunk), *self._chunk_args(seed),
                      self.fc.chunk)
        return tuple(np.asarray(x) for x in out)

    @staticmethod
    def _state_gap(a, b) -> float:
        """Gap of carry `a` from the reference's `b`."""
        qa, sa, mean_a, meas_a, ca = a
        qb, sb, mean_b, meas_b, cb = b
        diff = (np.abs(qa.astype(np.int64) - qb).sum() + (sa != sb).sum()
                + abs(int(ca) - int(cb)))
        tasks = diff / max(int(qb.sum() + (sb > 0).sum()), 1)
        mean_gap = abs(float(mean_a) - float(mean_b)) / max(abs(float(mean_b)),
                                                             1.0)
        return float(tasks + mean_gap + abs(float(meas_a) - float(meas_b)))

    def check(self, control=None) -> list:
        """The compared numbers.  With `control` (the workload's
        check.control, e.g. {"dtype": "bfloat16"}) the reference so changed
        stands in the program's place."""
        jnp = self.jax.numpy
        limits = self.workload["check"]["limits"]
        rng = np.random.default_rng(seed_seq(self.seed, 2))
        call = int(rng.integers(len(self.outputs)))
        seed, out = self.outputs[call]
        first = -(-self.cfg.warmup // self.fc.chunk)
        n_chunks = -(-self.cfg.horizon // self.fc.chunk)
        picks = sorted(int(c) for c in rng.choice(
            np.arange(first, n_chunks), self.workload["check"]["chunks"],
            replace=False))
        kept, last, chunk = self._replay(seed, picks)
        replay = self._metrics(last)
        replay_gap = max(abs(replay[k] - out[k]) / max(abs(out[k]), 1.0)
                         for k in replay)
        checks = [{"name": "replay_gap", "value": replay_gap,
                   "limit": limits["replay_gap"],
                   "ok": bool(replay_gap <= limits["replay_gap"])}]
        if self.device.platform == "tpu":
            shapes = tuple(self.jax.ShapeDtypeStruct(x.shape, x.dtype)
                           for x in last)
            text = chunk.lower(shapes, jnp.int32(0), *self._chunk_args(seed)
                               ).compile().as_text()
            has = int("tpu_custom_call" in text)
            checks.append({"name": "route_kernel", "value": has, "limit": 1,
                           "ok": has >= 1})
        gaps = []
        for ci in picks:
            start, end = kept[ci]
            ref = self._advance(start, ci, seed)
            cand = end if control is None else \
                self._advance(start, ci, seed, **control)
            gaps.append(self._state_gap(cand, ref))
        state_gap = max(gaps)
        checks.append({"name": "state_gap", "value": state_gap,
                       "limit": limits["state_gap"],
                       "ok": bool(state_gap <= limits["state_gap"])})
        return checks

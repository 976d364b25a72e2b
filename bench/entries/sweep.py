"""Entry: `repro.core.simulator.sweep` over a (load x estimate x seed) grid
on the dense backend, the paper's robustness study.

The loads are shares of the cluster's fluid capacity (bench/fluid.py).
Inputs from the run's seed: the per_server estimate errors of the grid
(one stack for the run) and the simulation seeds of every call, so each
call is a new sample path of the same study.  A call is one `sweep`; its
work is configurations x horizon slots.

The check: after the window, a sample drawn from the seed of the window's
configurations, `sample_per_load` at each load, is run again by the plain
reference (bench/reference/dense.py) on the same inputs, and each sampled
configuration's mean_n, throughput and final_n are compared with what the
window's call returned.  The number compared, `metric_gap`, is the largest
gap |program - reference| / max(|reference|, 1) over the sample.  The
reference and the program draw the same random numbers, so a sound run
agrees decision by decision and the gap is f32 rounding at most; one
routing decision that differs parts the sample path and moves mean_n by
percents.
"""

from __future__ import annotations

import numpy as np

METRICS = ("mean_n", "throughput", "final_n")


def seed_seq(seed: int, *path: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([seed % 2**64, *path])


class Entry:
    backend = "dense"

    def __init__(self, config: dict, workload: dict, seed: int, device):
        import jax
        from fluid import capacity
        from repro.core import locality as loc, simulator as sim

        self.jax, self.sim = jax, sim
        self.config, self.workload, self.seed = config, workload, seed
        self.device = device
        self.policy = workload["policy"]
        self.cfg = sim.SimConfig(
            topo=loc.Topology(config["num_servers"], config["rack_size"]),
            true_rates=loc.Rates(*config["rates"]),
            p_hot=workload.get("p_hot", config["p_hot"]),
            max_arrivals=config["max_arrivals"],
            horizon=config["horizon"], warmup=config["warmup"])
        cap = capacity(config["num_servers"], config["rack_size"],
                       config["rates"], self.cfg.p_hot)
        self.lam = (np.asarray(workload["loads"], np.float32)
                    * np.float32(cap))
        self.ests = self._estimates()
        self.per_call = len(self.lam) * len(self.ests) \
            * workload["seeds_per_call"]
        self.outputs = []            # (seeds, metrics) of each window call

    def _estimates(self) -> np.ndarray:
        """(E, M, K) estimated rates, one per error setting."""
        true = np.asarray(self.config["rates"], np.float32)
        m, k = self.config["num_servers"], len(true)
        rng = np.random.default_rng(seed_seq(self.seed, 0))
        out = []
        for err in self.workload["errors"]:
            if err["mode"] == "exact":
                mult = np.ones((m, k), np.float32)
            elif err["mode"] == "per_server":
                mult = 1.0 + err["sign"] * rng.uniform(
                    0.0, err["eps"], (m, k)).astype(np.float32)
            else:
                raise ValueError(f"unknown error mode {err['mode']!r}")
            out.append(np.clip(true[None, :] * mult, 1e-3, 1.0))
        return np.stack(out).astype(np.float32)

    def _seeds(self, tag: int, i: int) -> np.ndarray:
        return seed_seq(self.seed, tag, i).generate_state(
            self.workload["seeds_per_call"], np.uint32)

    def _sweep(self, seeds):
        return self.sim.sweep(self.policy, self.cfg, self.lam, self.ests, seeds)

    def warmup(self) -> None:
        self._sweep(self._seeds(3, 0))

    def call(self, i: int) -> int:
        seeds = self._seeds(1, i)
        self.outputs.append((seeds, self._sweep(seeds)))
        return self.per_call * self.cfg.horizon

    def attempted(self, stats: dict) -> int:
        return stats["calls"] * self.per_call

    def facts(self) -> dict:
        return {"backend": self.backend}

    # -- the check ---------------------------------------------------------

    def _sample(self):
        """(call, load, estimate, seed) indices of the compared sample."""
        rng = np.random.default_rng(seed_seq(self.seed, 2))
        n = self.workload["check"]["sample_per_load"]
        picks = []
        for li in range(len(self.lam)):
            for _ in range(n):
                picks.append((int(rng.integers(len(self.outputs))), li,
                              int(rng.integers(len(self.ests))),
                              int(rng.integers(self.workload["seeds_per_call"]))))
        return picks

    def _reference(self, picks, dtype="float32"):
        import jax.numpy as jnp
        from reference import dense

        run = dense.build(self.policy, self.config["num_servers"],
                          self.config["rack_size"], self.config["rates"],
                          self.cfg.p_hot, self.cfg.max_arrivals,
                          self.cfg.horizon, self.cfg.warmup, dtype=dtype)
        lam = np.asarray([self.lam[l] for _, l, _, _ in picks], np.float32)
        est = np.stack([self.ests[e] for _, _, e, _ in picks])
        seeds = np.asarray([self.outputs[c][0][s] for c, _, _, s in picks],
                           np.uint32)
        with self.jax.default_device(self.device):
            out = run(jnp.asarray(lam), jnp.asarray(est), jnp.asarray(seeds))
        return {k: np.asarray(out[k], np.float64) for k in METRICS}

    def check(self, control=None) -> list:
        """The compared numbers.  With `control` (the workload's
        check.control, e.g. {"dtype": "bfloat16"}) the reference so changed
        stands in the program's place."""
        picks = self._sample()
        ref = self._reference(picks)
        if control is None:
            cand = {k: np.asarray([self.outputs[c][1][k][l, e, s]
                                   for c, l, e, s in picks], np.float64)
                    for k in METRICS}
        else:
            cand = self._reference(picks, **control)
        gap = max(float(np.max(np.abs(cand[k] - ref[k])
                               / np.maximum(np.abs(ref[k]), 1.0)))
                  for k in METRICS)
        limit = self.workload["check"]["limits"]["metric_gap"]
        return [{"name": "metric_gap", "value": gap, "limit": limit,
                 "ok": bool(gap <= limit)}]

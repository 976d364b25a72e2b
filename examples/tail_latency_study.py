"""Tail-latency study: p50/p95/p99 sojourn vs the Little's-law mean in
heavy traffic.

The paper's comparison is stated in mean delay, but a production SLO is a
percentile — and mean ordering between schedulers need not match tail
ordering.  The in-scan telemetry recorder (`repro.telemetry`) measures
per-task sojourns inside the `lax.scan` via an FCFS-coupled arrival-slot
ring and a fixed-bin histogram, so this study sweeps
rho in {0.90, 0.95, 0.99} of the static fluid capacity for
Balanced-PANDAS vs JSQ-MaxWeight vs FIFO at K=3 and reports where the
p99 winner diverges from the mean winner (EXPERIMENTS.md §Tail latency).

    PYTHONPATH=src python examples/tail_latency_study.py [--full | --smoke]

Writes experiments/figures/tail_latency.csv and prints the per-load
table.  ``--smoke`` is the CI job: a tiny horizon with a bitwise gate
(the telemetry recorder is pure observation — every metric the plain run
produces is bitwise identical with telemetry on) and a percentile sanity
gate (p99 >= p95 >= p50 > 0 on a stable arm).
"""

import argparse
import csv
from pathlib import Path

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale horizons (slow on 1 CPU core)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny horizon, bitwise + sanity gates")
    ap.add_argument("--loads", type=float, nargs="+",
                    default=(0.90, 0.95, 0.99))
    args = ap.parse_args()

    from repro.core import locality as loc, robustness as rb, simulator as sim

    if args.smoke:
        # Bitwise gate: telemetry is pure observation (consumes no RNG,
        # mutates no policy state) — the plain run's every metric must be
        # bitwise identical with the recorder compiled in, for every
        # registered policy except the ones that OPT IN to reading the
        # live signals (`uses_signals`, e.g. slo_pandas — the documented
        # exception, pinned separately in tests/test_control.py).
        from repro.core.policy import available_policies, get_policy_cls
        cfg_s = sim.SimConfig(topo=loc.Topology(12, 4),
                              true_rates=loc.Rates(), max_arrivals=16,
                              horizon=400, warmup=100)
        est = sim.make_estimates(cfg_s, "network", 0.0, -1)
        for pol in available_policies():
            if getattr(get_policy_cls(pol), "uses_signals", False):
                continue
            off = sim.simulate(pol, cfg_s, 3.0, est, seed=0)
            on = sim.simulate(pol, cfg_s, 3.0, est, seed=0, telemetry=True)
            for k, v in off.items():
                assert np.array_equal(np.asarray(v), np.asarray(on[k])), \
                    (pol, k)
            assert "delay_p99" in on and "delay_p99" not in off

        cfg = rb.StudyConfig(
            sim=sim.SimConfig(topo=loc.Topology(12, 4),
                              true_rates=loc.Rates(), max_arrivals=16,
                              horizon=1500, warmup=400),
            seeds=(0,))
        study = rb.tail_study(cfg, loads=(0.9,))
        print(rb.summarize_tail(study))
        # Percentile sanity on the delay-optimal arm: finite, ordered,
        # positive, and the p50 brackets the mean's order of magnitude.
        p50, p95, p99 = (float(study[m]["balanced_pandas"][0].mean())
                         for m in ("p50", "p95", "p99"))
        assert 0.0 < p50 <= p95 <= p99 < float("inf"), (p50, p95, p99)
        assert float(study["unmatched"]["balanced_pandas"][0].mean()) == 0.0
        print("tail-latency smoke OK")
        return

    horizon, warmup = (40_000, 10_000) if args.full else (12_000, 3_000)
    seeds = (0, 1) if args.full else (0,)
    outdir = Path("experiments/figures")
    outdir.mkdir(parents=True, exist_ok=True)
    cfg = rb.StudyConfig(
        sim=sim.default_config(horizon=horizon, warmup=warmup),
        seeds=seeds)
    study = rb.tail_study(cfg, loads=tuple(args.loads))
    print(rb.summarize_tail(study))
    path = outdir / "tail_latency.csv"
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["policy", "load", "seed", "mean_delay", "delay_p50",
                    "delay_p95", "delay_p99"])
        for pol in study["policies"]:
            for li, rho in enumerate(study["loads"]):
                for si, seed in enumerate(seeds):
                    w.writerow([pol, float(rho), seed]
                               + [float(study[m][pol][li][si])
                                  for m in ("mean", "p50", "p95", "p99")])
    print(f"wrote {path}")


if __name__ == "__main__":
    from repro.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    main()

"""Placement x policy study: what hierarchy-aware replica placement buys
each scheduler, at K=3 (flat racks) and K=4 (pods).

The uniform model hard-codes the one knob Hadoop operators actually turn:
where the 3 replicas of each chunk live.  This study sweeps the registered
placements (uniform / hdfs / spread / hot_aware) against one policy per
family (full-scan PANDAS, blind-EWMA PANDAS, MaxWeight) under the
scenarios that move locality and network structure (hot_shift,
rack_congestion), at the same offered load — `0.7 x` the uniform static
fluid capacity — so every delta is a placement effect.

    PYTHONPATH=src python examples/placement_study.py [--full | --smoke]
    PYTHONPATH=src python examples/placement_study.py --topology k4

Writes experiments/figures/placement_study_{k3,k4}.csv and prints the
per-scenario tables (the numbers behind EXPERIMENTS.md §Placement).
``--smoke`` is the CI job: one topology, one scenario, tiny horizon, with
a stability gate (every arm's throughput tracks the offered load) and a
bitwise gate (placement="uniform" reproduces the default sample path).
"""

import argparse
import csv
from pathlib import Path


def _topologies(which: str):
    from repro.core import locality as loc
    k3 = ("k3", loc.Topology(24, 6), loc.Rates())
    k4 = ("k4", loc.Topology(24, (6, 12)), loc.Rates((0.5, 0.45, 0.35,
                                                      0.25)))
    return {"k3": (k3,), "k4": (k4,), "both": (k3, k4)}[which]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale horizons (slow on 1 CPU core)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: one topology/scenario, tiny horizon")
    ap.add_argument("--topology", default="both", choices=("k3", "k4",
                                                           "both"))
    ap.add_argument("--load", type=float, default=0.7)
    args = ap.parse_args()

    import numpy as np
    from repro.core import locality as loc, robustness as rb, simulator as sim

    if args.smoke:
        # bitwise gate: the uniform placement IS the default sample path
        cfg_s = sim.SimConfig(topo=loc.Topology(12, 4),
                              true_rates=loc.Rates(), max_arrivals=16,
                              horizon=400, warmup=100)
        est = sim.make_estimates(cfg_s, "network", 0.0, -1)
        base = sim.simulate("balanced_pandas", cfg_s, 3.0, est, seed=0)
        unif = sim.simulate("balanced_pandas", cfg_s, 3.0, est, seed=0,
                            placement="uniform")
        assert base == unif, (base, unif)

        cfg = rb.StudyConfig(
            sim=sim.SimConfig(topo=loc.Topology(12, 4),
                              true_rates=loc.Rates(), max_arrivals=16,
                              horizon=1500, warmup=400),
            seeds=(0,))
        study = rb.placement_study(cfg, scenarios=("hot_shift",),
                                   load=args.load, capacity_samples=500)
        print(rb.summarize_placement(study))
        lam = study["load"] * study["capacity_uniform"]
        for plc in study["placements"]:
            for pol in study["policies"]:
                thr = float(study["throughput"][plc]["hot_shift"][pol].mean())
                assert thr > 0.9 * lam, (plc, pol, thr, lam)
        print("placement smoke OK")
        return

    horizon, warmup = (30_000, 8_000) if args.full else (8_000, 2_000)
    seeds = (0, 1) if args.full else (0,)
    outdir = Path("experiments/figures")
    outdir.mkdir(parents=True, exist_ok=True)
    for label, topo, rates in _topologies(args.topology):
        cfg = rb.StudyConfig(
            sim=sim.SimConfig(topo=topo, true_rates=rates, p_hot=0.5,
                              max_arrivals=24, horizon=horizon,
                              warmup=warmup),
            seeds=seeds)
        study = rb.placement_study(cfg, load=args.load)
        print(f"== {label}: M={topo.num_servers}, K={topo.num_tiers} ==")
        print(rb.summarize_placement(study))
        path = outdir / f"placement_study_{label}.csv"
        with open(path, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(["topology", "placement", "fluid_capacity",
                        "scenario", "policy", "seed", "mean_delay",
                        "throughput", "final_n"])
            for plc in study["placements"]:
                cap = study["capacity"][plc]
                for scen in study["scenarios"]:
                    for pol in study["policies"]:
                        for si, seed in enumerate(seeds):
                            w.writerow([
                                label, plc,
                                "" if cap is None else f"{cap:.4f}",
                                scen, pol, seed,
                                float(study["delay"][plc][scen][pol][si]),
                                float(study["throughput"][plc][scen][pol][si]),
                                float(study["final_n"][plc][scen][pol][si]),
                            ])
        print(f"wrote {path}")


if __name__ == "__main__":
    from repro.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    main()

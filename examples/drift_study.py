"""Drift study: fixed-prior vs blind-EWMA Balanced-PANDAS under the
registered time-varying scenarios (the experiment the paper motivates —
"the change of traffic over time in addition to estimation errors" — but
never runs).

    PYTHONPATH=src python examples/drift_study.py [--full | --smoke]
    PYTHONPATH=src python examples/drift_study.py --scenarios stragglers,mmpp
    PYTHONPATH=src python examples/drift_study.py --topology k4

Both arms start from the exact static rates, so the fixed prior is the best
possible frozen estimate; any blind win is pure drift-tracking.  Writes
experiments/figures/drift_study{,_k4}.csv and prints the per-scenario
table.  ``--topology k4`` runs the same study on the pod topology
(Topology(24, (6, 12)), 4-tier rates) — the K=4 robustness sweep behind
EXPERIMENTS.md §Tier-generic.  ``--smoke`` is the CI job: 2 scenarios x 2
policies at a tiny horizon, asserting only that every run stays stable
(throughput tracks arrivals).
"""

import argparse
import csv
from pathlib import Path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale horizons (slow on 1 CPU core)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: 2 scenarios x 2 policies, tiny horizon")
    ap.add_argument("--scenarios", default=None,
                    help="comma list (default: all registered drift scenarios)")
    ap.add_argument("--topology", default="k3", choices=("k3", "k4"),
                    help="k3: the paper's flat racks; k4: pods "
                         "(Topology(24, (6, 12)), 4-tier rates)")
    args = ap.parse_args()

    from repro.core import locality as loc, robustness as rb, simulator as sim

    def sim_cfg(horizon, warmup):
        if args.topology == "k4":
            return sim.SimConfig(topo=loc.Topology(24, (6, 12)),
                                 true_rates=loc.Rates((0.5, 0.45, 0.35,
                                                       0.25)),
                                 max_arrivals=24, horizon=horizon,
                                 warmup=warmup)
        return sim.default_config(horizon=horizon, warmup=warmup)

    if args.smoke:
        cfg = rb.StudyConfig(
            sim=sim.SimConfig(topo=loc.Topology(12, 4),
                              true_rates=loc.Rates(), max_arrivals=16,
                              horizon=1500, warmup=400),
            seeds=(0,))
        scenarios = ("stragglers", "rack_congestion")  # 2 x 2 arms in CI
    elif args.full:
        cfg = rb.StudyConfig(sim=sim_cfg(30_000, 8_000), seeds=(0, 1))
        scenarios = rb.DRIFT_SCENARIOS
    else:
        cfg = rb.StudyConfig(sim=sim_cfg(8_000, 2_000), seeds=(0,))
        scenarios = rb.DRIFT_SCENARIOS
    if args.scenarios:
        scenarios = tuple(s.strip() for s in args.scenarios.split(","))

    study = rb.drift_study(cfg, scenarios=scenarios)
    print(rb.summarize_drift(study))

    if args.smoke:
        # Stability gate for CI: every arm must keep up with the offered
        # load (no divergence under any smoke scenario).
        lam = study["load"] * study["capacity"]
        for scen in scenarios:
            for arm in study["arms"]:
                thr = float(study["throughput"][scen][arm].mean())
                assert thr > 0.9 * lam, (scen, arm, thr, lam)
        print("scenario smoke OK")
        return

    outdir = Path("experiments/figures")
    outdir.mkdir(parents=True, exist_ok=True)
    suffix = "" if args.topology == "k3" else f"_{args.topology}"
    with open(outdir / f"drift_study{suffix}.csv", "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["scenario", "arm", "seed", "mean_delay", "throughput",
                    "final_n"])
        for scen in study["scenarios"]:
            for arm in study["arms"]:
                for si, seed in enumerate(cfg.seeds):
                    w.writerow([
                        scen, arm, seed,
                        float(study["delay"][scen][arm][si]),
                        float(study["throughput"][scen][arm][si]),
                        float(study["final_n"][scen][arm][si]),
                    ])
    print(f"wrote {outdir / f'drift_study{suffix}.csv'}")


if __name__ == "__main__":
    from repro.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    main()

"""Replication-lifecycle study: what adaptive replication and failure
repair buy (and cost) when the scenario actually kills servers.

PR 5 made *where* the replicas start a policy choice; the replication
lifecycle makes what happens to them afterwards one too.  This study
sweeps the registered controllers (fixed / popularity / repair) against
the failure scenarios (server_loss, rack_loss) for the two schedulers
whose robustness gap the paper cares about (Balanced-PANDAS vs JSQ-MW),
at rho in {0.7, 0.95} of the *healthy* static fluid capacity — so the
delay deltas decompose into capacity lost to dead servers and foreground
slots consumed by the re-replication storm.

    PYTHONPATH=src python examples/replication_study.py [--full | --smoke]

Writes experiments/figures/replication_study.csv and prints the
per-scenario tables (the numbers behind EXPERIMENTS.md §Replication).
``--smoke`` is the CI job: one scenario, tiny horizon, with a bitwise
gate (replication="fixed" under a static scenario reproduces the default
sample path) and a repair gate (the repair controller actually restores
the replication factor the loss window destroyed).
"""

import argparse
import csv
from pathlib import Path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale horizons (slow on 1 CPU core)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: one scenario, tiny horizon")
    ap.add_argument("--loads", type=float, nargs="+", default=(0.7, 0.95))
    args = ap.parse_args()

    from repro.core import locality as loc, robustness as rb, simulator as sim

    if args.smoke:
        # bitwise gate: fixed + static IS the default sample path
        cfg_s = sim.SimConfig(topo=loc.Topology(12, 4),
                              true_rates=loc.Rates(), max_arrivals=16,
                              horizon=400, warmup=100)
        est = sim.make_estimates(cfg_s, "network", 0.0, -1)
        base = sim.simulate("balanced_pandas", cfg_s, 3.0, est, seed=0)
        fixed = sim.simulate("balanced_pandas", cfg_s, 3.0, est, seed=0,
                             replication="fixed")
        assert base == fixed, (base, fixed)

        cfg = rb.StudyConfig(
            sim=sim.SimConfig(topo=loc.Topology(12, 4),
                              true_rates=loc.Rates(), max_arrivals=16,
                              horizon=1200, warmup=300),
            seeds=(0,))
        study = rb.replication_study(cfg, scenarios=("server_loss",),
                                     policies=("balanced_pandas",),
                                     loads=(args.loads[0],))
        print(rb.summarize_replication(study))
        # repair gate: the repair controller ends the run back at factor 3,
        # the no-repair control arm does not
        rep = study["mean_replication"]["server_loss"]
        fix_r = float(rep["fixed"]["balanced_pandas"][0].mean())
        rep_r = float(rep["repair"]["balanced_pandas"][0].mean())
        assert rep_r > fix_r, (fix_r, rep_r)
        mv = study["repair_moves"]["server_loss"]
        assert float(mv["repair"]["balanced_pandas"][0].mean()) > 0
        assert float(mv["fixed"]["balanced_pandas"][0].mean()) == 0
        print("replication smoke OK")
        return

    horizon, warmup = (30_000, 8_000) if args.full else (8_000, 2_000)
    seeds = (0, 1) if args.full else (0,)
    outdir = Path("experiments/figures")
    outdir.mkdir(parents=True, exist_ok=True)
    topo, rates = loc.Topology(24, 6), loc.Rates()
    cfg = rb.StudyConfig(
        sim=sim.SimConfig(topo=topo, true_rates=rates, p_hot=0.5,
                          max_arrivals=24, horizon=horizon, warmup=warmup),
        seeds=seeds)
    study = rb.replication_study(cfg, loads=tuple(args.loads))
    print(rb.summarize_replication(study))
    path = outdir / "replication_study.csv"
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["scenario", "controller", "policy", "load", "seed",
                    "mean_delay", "throughput", "availability",
                    "data_loss_frac", "mean_replication", "repair_moves"])
        for scen in study["scenarios"]:
            for ctrl in study["replications"]:
                for pol in study["policies"]:
                    for li, rho in enumerate(study["loads"]):
                        for si, seed in enumerate(seeds):
                            cell = [study[m][scen][ctrl][pol]
                                    for m in ("delay", "throughput",
                                              "availability", "data_loss",
                                              "mean_replication",
                                              "repair_moves")]
                            w.writerow([scen, ctrl, pol, float(rho), seed]
                                       + [float(c[li][si]) for c in cell])
    print(f"wrote {path}")


if __name__ == "__main__":
    from repro.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    main()

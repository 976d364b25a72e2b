"""SLO control study: what each control-plane lever buys at the tail.

The paper compares schedulers under a FIXED offered load; a production
cluster also gets to refuse and reshape that load.  This study runs the
control-plane arms {none, admission, autoscale, both} for the
mean-optimal scheduler (``balanced_pandas``) and its SLO-conditioned
variant (``slo_pandas``) at rho in {0.90, 0.95, 0.99} of the static
fluid capacity, telemetry on (EXPERIMENTS.md §SLO control):

  * **admission** — a token bucket refilling at 93% of capacity: at
    rho = 0.99 it sheds the few percent of arrivals that push the system
    past the stability knee, collapsing the p99;
  * **autoscale** — the proactive headroom planner: a no-op at the knee
    (everything stays on) but the descale floor shows up at moderate rho;
  * **slo_pandas** — scheduling-only control: drains the longest queues
    while the live p99 estimate breaches the SLO, shedding nothing.

Means use the MEASURED admitted rate as the Little's-law denominator, so
they stay comparable across arms.

    PYTHONPATH=src python examples/slo_control_study.py [--full | --smoke]

Writes experiments/figures/slo_control.csv and prints the per-load
table.  ``--smoke`` is the CI job: a tiny horizon with a bitwise gate
(``control=None`` compiles NOTHING — every metric of every registered
policy is bitwise identical to the pre-control simulator) and a
shed-rate sanity gate (the admission arm sheds at rho = 0.99).
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
                    help="CI smoke: tiny horizon, bitwise + shed gates")
    ap.add_argument("--loads", type=float, nargs="+",
                    default=(0.90, 0.95, 0.99))
    args = ap.parse_args()

    from repro.core import locality as loc, robustness as rb, simulator as sim

    if args.smoke:
        # Bitwise gate: control=None must compile to the exact
        # pre-control program for every registered policy — the scan
        # carry gains no slots, the RNG consumes nothing.  (slo_pandas
        # without telemetry is included: signals are absent, so it IS
        # balanced_pandas by construction.)
        from repro.core.policy import available_policies
        cfg_s = sim.SimConfig(topo=loc.Topology(12, 4),
                              true_rates=loc.Rates(), max_arrivals=16,
                              horizon=400, warmup=100)
        est = sim.make_estimates(cfg_s, "network", 0.0, -1)
        for pol in available_policies():
            off = sim.simulate(pol, cfg_s, 3.0, est, seed=0)
            on = sim.simulate(pol, cfg_s, 3.0, est, seed=0, control=None)
            for k, v in off.items():
                assert np.array_equal(np.asarray(v), np.asarray(on[k])), \
                    (pol, k)

        # Shed gate: one overloaded arm with the study's token bucket
        # must shed and stay conserved (offered == admitted + shed).
        cap = loc.capacity_hot_rack(cfg_s.topo, cfg_s.true_rates, cfg_s.p_hot)
        res = sim.simulate(
            "balanced_pandas", cfg_s, 1.2 * cap, est, seed=0,
            control=rb.control_arm_spec("admission", cap))
        shed = float(res["ctl_shed_rate"])
        assert 0.0 < shed < 1.0, shed
        assert int(res["ctl_offered"]) == \
            int(res["ctl_admitted"]) + int(res["ctl_shed"])

        cfg = rb.StudyConfig(
            sim=sim.SimConfig(topo=loc.Topology(12, 4),
                              true_rates=loc.Rates(), max_arrivals=16,
                              horizon=1500, warmup=400),
            seeds=(0,))
        study = rb.control_study(cfg, loads=(0.99,))
        print(rb.summarize_control(study))
        adm = study["shed_rate"]["balanced_pandas"]["admission"]
        assert float(np.mean(adm)) > 0.0, "admission arm shed nothing"
        print("slo-control smoke OK")
        return

    horizon, warmup = (40_000, 10_000) if args.full else (12_000, 3_000)
    seeds = (0, 1) if args.full else (0,)
    outdir = Path("experiments/figures")
    outdir.mkdir(parents=True, exist_ok=True)
    cfg = rb.StudyConfig(
        sim=sim.default_config(horizon=horizon, warmup=warmup),
        seeds=seeds)
    study = rb.control_study(cfg, loads=tuple(args.loads))
    print(rb.summarize_control(study))
    path = outdir / "slo_control.csv"
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["policy", "arm", "load", "seed", "mean_delay",
                    "delay_p50", "delay_p95", "delay_p99", "shed_rate",
                    "throughput"])
        for pol in study["policies"]:
            for arm in study["arms"]:
                for li, rho in enumerate(study["loads"]):
                    for si, seed in enumerate(seeds):
                        w.writerow(
                            [pol, arm, float(rho), seed]
                            + [float(study[m][pol][arm][li][si])
                               for m in ("mean", "p50", "p95", "p99",
                                         "shed_rate", "throughput")])
    print(f"wrote {path}")


if __name__ == "__main__":
    from repro.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    main()

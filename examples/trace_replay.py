"""Trace replay: one recorded cluster trace drives every layer of the
stack — the discrete-time simulator, the live serving engine, and the
bench_serving harness — from a single compiled Scenario.

    PYTHONPATH=src python examples/trace_replay.py [--smoke | --full]
    PYTHONPATH=src python examples/trace_replay.py --trace flash_day
    PYTHONPATH=src python examples/trace_replay.py --trace path/to/my.jsonl

The pipeline:

  1. load a bundled (or user-supplied JSONL/CSV) trace and compile it to a
     piecewise `Scenario` (`repro.workloads.trace`: unit-mean arrival
     normalization + change-point merging);
  2. simulator leg — the paper's drift experiment on recorded traffic:
     fixed-prior vs blind-EWMA Balanced-PANDAS replaying the trace
     (`robustness.drift_study`), results to
     experiments/figures/trace_replay.csv;
  3. serving leg — the same Scenario times request submission and replica
     slowdowns on the live continuous-batching engine
     (`bench_serving.replay_trace`), and the run is re-recorded through
     the engine's trace export hook;
  4. the re-recorded trace is loaded back and compiled again, closing the
     record -> replay -> re-record loop deterministically.

``--smoke`` is the CI gate: tiny horizons, plus assertions that every arm
stays stable and that the export hook round-trips bit-for-bit.
"""

import argparse
import csv
import sys
from pathlib import Path

# the serving leg reuses the bench harness, which lives outside src/
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default="diurnal_week",
                    help="bundled trace name, or a path to a .jsonl/.csv "
                         "trace file")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale horizons (slow on 1 CPU core)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny horizons + determinism assertions")
    ap.add_argument("--max-segments", type=int, default=64)
    args = ap.parse_args()

    from repro import workloads as wl
    from repro.core import locality as loc, robustness as rb, simulator as sim

    # -- 1. one Scenario for every layer ----------------------------------
    if args.trace in wl.bundled_traces():
        trace = wl.load_bundled(args.trace)
    else:
        trace = wl.load_trace(args.trace)
    scn = wl.trace_to_scenario(trace, max_segments=args.max_segments)
    print(f"trace {trace.name!r}: {trace.num_intervals} intervals "
          f"({trace.duration / 3600.0:.1f} h) -> {len(scn.segments)} "
          f"segments, mean lam_mult {scn.mean_lam_mult:.4f}")

    # -- 2. simulator: fixed prior vs blind EWMA on recorded traffic ------
    if args.smoke:
        cfg = rb.StudyConfig(
            sim=sim.SimConfig(topo=loc.Topology(12, 4),
                              true_rates=loc.Rates(), max_arrivals=16,
                              horizon=1500, warmup=400),
            seeds=(0,))
    elif args.full:
        cfg = rb.StudyConfig(sim=sim.default_config(horizon=30_000,
                                                    warmup=8_000),
                             seeds=(0, 1))
    else:
        cfg = rb.StudyConfig(sim=sim.default_config(horizon=8_000,
                                                    warmup=2_000),
                             seeds=(0,))
    study = rb.drift_study(cfg, scenarios={"static": "static",
                                           scn.name: scn})
    print(rb.summarize_drift(study))

    # -- 3. serving engine + bench harness on the same Scenario -----------
    outdir = Path("experiments")
    export = outdir / "traces" / "replay_rerecorded.jsonl"
    from benchmarks import bench_serving
    rows = bench_serving.replay_trace(scn, fast=not args.full,
                                      export_path=str(export))
    for name, steps, derived in rows:
        print(f"{name}: drained in {steps:.0f} engine steps ({derived})")

    # -- 4. the re-recorded run replays deterministically ------------------
    rerec = wl.load_trace(export)
    rescn = wl.trace_to_scenario(rerec, max_segments=args.max_segments)
    again = wl.load_trace(export)
    assert again == rerec, "trace export must round-trip bit-for-bit"
    assert wl.trace_to_scenario(again, max_segments=args.max_segments) \
        == rescn, "recompiling the same trace must be deterministic"
    print(f"re-recorded {rerec.num_intervals} intervals "
          f"({int(rerec.arrivals.sum())} arrivals) -> "
          f"{len(rescn.segments)} segments; replay round-trip OK")

    if args.smoke:
        lam = study["load"] * study["capacity"]
        for scen in study["scenarios"]:
            for arm in study["arms"]:
                thr = float(study["throughput"][scen][arm].mean())
                assert thr > 0.9 * lam, (scen, arm, thr, lam)
        print("trace-replay smoke OK")
        return

    figdir = outdir / "figures"
    figdir.mkdir(parents=True, exist_ok=True)
    csv_path = figdir / f"trace_replay_{trace.name}.csv"
    with open(csv_path, "w", newline="") as f:
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
    print(f"wrote {csv_path}")


if __name__ == "__main__":
    from repro.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    main()

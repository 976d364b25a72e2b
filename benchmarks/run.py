"""Study orchestrator: one section per paper figure plus the serving
studies.  Prints ``name,us_per_call,derived`` CSV and writes figure data to
experiments/figures/*.csv.  Speed is measured on the chip by `bench/run.py`,
not here.

    PYTHONPATH=src python -m benchmarks.run [--full]

``--help`` lists every registered scenario and policy with its one-line
description (the registries are self-describing; see
`workloads.scenario_descriptions` / `core.policy.policy_descriptions`).
"""

from __future__ import annotations

import argparse
import csv
import sys
import time
from pathlib import Path


def _registry_epilog() -> str:
    """Render the scenario/policy/placement registries for --help."""
    from repro import control as ctl, placement as plc, replication as rep
    from repro import workloads as wl
    from repro.core import policy as pol

    def block(title, entries):
        lines = [f"{title}:"]
        for name, desc in entries.items():
            lines.append(f"  {name:18s} {desc}")
        return lines

    lines = block("registered scenarios", wl.scenario_descriptions())
    lines += block("registered policies (simulator)",
                   pol.policy_descriptions())
    lines += block("registered routers (serving engine / data pipeline)",
                   pol.router_descriptions())
    lines += block("registered replica placements (simulator / engine / "
                   "pipeline)", plc.placement_descriptions())
    lines += block("registered replication controllers (lifecycle: "
                   "migration / repair)", rep.replication_descriptions())
    lines += block("registered control-plane controllers (load generation / "
                   "admission / autoscaling)", ctl.controller_descriptions())
    return "\n".join(lines)


def main() -> None:
    # the epilog imports every registry module — only pay that for --help
    wants_help = any(a in ("-h", "--help") for a in sys.argv[1:])
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        epilog=_registry_epilog() if wants_help else None,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale horizons (slow on 1 CPU core)")
    ap.add_argument("--only", default=None,
                    help="comma list: fig1,fig2,fig34,fig56,drift,serving,"
                         "serving_scenarios,serving_control,trace_replay")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the run "
                         "(section spans, engine route/admit/decode "
                         "events) — load it at https://ui.perfetto.dev")
    args = ap.parse_args()
    fast = not args.full
    only = set(args.only.split(",")) if args.only else None

    from repro.utils.cache import enable_persistent_cache

    print(f"# persistent compilation cache: {enable_persistent_cache()}",
          file=sys.stderr)

    from benchmarks import bench_serving, figures

    tracer = None
    if args.trace:
        from repro.telemetry import EventRecorder
        tracer = EventRecorder()
        tracer.metadata("process_name", name="benchmarks.run")

    outdir = Path("experiments/figures")
    outdir.mkdir(parents=True, exist_ok=True)
    csv_rows = []
    fig_rows = []

    def section(name, fn):
        if only and name not in only:
            return
        t0 = time.time()
        if tracer is None:
            rows = fn()
        else:
            with tracer.span(f"section:{name}", cat="section"):
                rows = fn()
        print(f"# {name} ({time.time() - t0:.1f}s)", file=sys.stderr)
        if rows and isinstance(rows[0], dict):
            fig_rows.extend(rows)
            # summarize per figure/algo: worst-case delay
            import collections
            worst = collections.defaultdict(float)
            for r in rows:
                worst[(r["figure"], r["algo"])] = max(
                    worst[(r["figure"], r["algo"])], r["mean_delay"])
            for (fig, algo), d in sorted(worst.items()):
                csv_rows.append((f"{fig}_{algo}_worst_delay_slots",
                                 d * 1e6, "delay(slots)*1e6=us@1us-slot"))
        else:
            csv_rows.extend(rows)

    section("fig1", lambda: figures.fig1_precise(fast))
    section("fig2", lambda: figures.fig2_highload(fast))
    section("fig34", lambda: figures.fig34_under(fast))
    section("fig56", lambda: figures.fig56_over(fast))
    section("drift", lambda: figures.fig_drift(fast))
    section("serving", lambda: bench_serving.bench(fast, tracer=tracer))
    section("serving_scenarios", lambda: bench_serving.bench_scenarios(fast))
    section("serving_control",
            lambda: bench_serving.bench_control(fast, tracer=tracer))
    section("trace_replay", lambda: bench_serving.replay_trace(
        fast=fast, export_path="experiments/traces/replayed.jsonl"))

    if fig_rows:
        keys = sorted({k for r in fig_rows for k in r})
        with open(outdir / "figures.csv", "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=keys)
            w.writeheader()
            w.writerows(fig_rows)
        claims = figures.headline_claims(fig_rows)
        for k, v in claims.items():
            csv_rows.append((f"claim_{k}", 1.0 if v else 0.0, str(v)))
        print(f"# wrote {outdir / 'figures.csv'} ({len(fig_rows)} rows); "
              f"claims: {claims}", file=sys.stderr)

    if tracer is not None:
        tracer.save(args.trace)
        print(f"# wrote {args.trace} ({len(tracer.events())} events, "
              f"{tracer.dropped} dropped)", file=sys.stderr)

    print("name,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()

"""Per-layer readings of the program's named scopes and host spans, from
one traced call of a benchmark cell.

    python3 bench/scope_report.py --workload <cell> --seed <n>

It needs the chips the cell asks for.  Set-up is that of bench/run.py (the
cell's configuration, traffic and entry, one warm-up call); then one call
of the entry inside a `bench.call` span with the profiler on, and one JSON
line of readings.  The device's self time under each scope (an operation
belongs to the innermost `sim.*` element of its name stack, read by
bench/xplane_scopes.py; time nested in an operation goes to the nested
one, as `Trace.op_seconds` counts it) over the slots the call advanced:

    dense   dense_arrivals_us  dense_route_us  dense_serve_us
    fleet   fleet_arrivals_us  fleet_private_us  fleet_fill_us
            fleet_route_us (sim.route less its private phase and fill)
            fleet_serve_us

and, from the host spans of `sweep` (dense only), on the same clock:

    sweep_prepare_idle_s, sweep_fetch_idle_s   device-idle seconds a call
        inside the `sim.prepare` / `sim.fetch` spans (idle gaps of the
        window intersected with the spans' union)
    sweep_traces_per_call   `sim.trace` spans over calls: the body that
        opens it runs only while JAX traces the program

A reading the trace holds nothing for (a program without the scopes or
spans) is null.  The line also gives the busy microseconds a slot
(`slot_us`, as dense_slot_us / fleet_slot_us read it), the share of it the
scope readings cover, the device-idle seconds a call, the self seconds per
scope and the largest operations under no scope.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

SCOPES = {   # reading -> scope, per backend
    "dense": {"dense_arrivals_us": "sim.arrivals",
              "dense_route_us": "sim.route",
              "dense_serve_us": "sim.serve"},
    "fleet": {"fleet_arrivals_us": "sim.arrivals",
              "fleet_private_us": "sim.private",
              "fleet_fill_us": "sim.fill",
              "fleet_route_us": "sim.route",
              "fleet_serve_us": "sim.serve"},
}


def overlap(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def span_idle_s(trace, name: str, lo, hi, calls: int):
    """Device-idle seconds a call inside the host spans called `name`, or
    None where there is no such span."""
    from trace_reduce import clip, union

    spans = union(clip([(s, e) for n, s, e, _ in trace.host if n == name],
                       lo, hi))
    if not spans or not trace.devices:
        return None
    return overlap(trace.gaps(lo, hi), spans) / 1e9 / calls


def traces_per_call(trace, lo, hi, calls: int):
    """`sim.trace` spans that start in the window over the calls, or None
    where the program opens no `sim.prepare` span."""
    starts = {name: sum(1 for n, s, _, _ in trace.host
                        if n == name and lo <= s <= hi)
              for name in ("sim.prepare", "sim.trace")}
    if not starts["sim.prepare"]:
        return None
    return starts["sim.trace"] / calls


def readings(trace, scopes, backend: str, slots: int, calls: int) -> dict:
    """The readings of one traced window (see the module's docstring):
    `trace` from `Trace.from_file`, `scopes` from `xplane_scopes.read` of
    the same file."""
    lo, hi = trace.window() or (0, 0)
    own = scopes.op_seconds(lo, hi)
    out = {name: (1e6 * own[scope] / slots if scope in own else None)
           for name, scope in SCOPES[backend].items()}
    if backend == "dense":
        out["sweep_prepare_idle_s"] = span_idle_s(trace, "sim.prepare",
                                                  lo, hi, calls)
        out["sweep_fetch_idle_s"] = span_idle_s(trace, "sim.fetch",
                                                lo, hi, calls)
        out["sweep_traces_per_call"] = traces_per_call(trace, lo, hi, calls)
    return out


def report(trace, scopes, backend: str, slots: int, calls: int,
           top: int = 10) -> dict:
    """The JSON line: readings, then what they are held against."""
    lo, hi = trace.window() or (0, 0)
    busy = trace.busy_ns(lo, hi) / 1e9
    slot_us = 1e6 * busy / slots
    own = sorted(scopes.op_seconds(lo, hi).items(), key=lambda kv: -kv[1])
    got = readings(trace, scopes, backend, slots, calls)
    per_slot = sum(v for k, v in got.items() if k.endswith("_us") and v)
    return {"readings": got, "slot_us": slot_us,
            "attributed_pct": 100 * per_slot / slot_us if slot_us else None,
            "idle_s_per_call": ((hi - lo) / 1e9 - busy) / calls,
            "scope_s": {k: v for k, v in own if k.startswith("sim.")},
            "unscoped": [[k, v] for k, v in own
                         if not k.startswith("sim.")][:top]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    import run
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = {w["name"]: w for w in spec["workloads"]}[args.workload]
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    import xplane_scopes
    from repro.utils.cache import enable_persistent_cache
    from trace_reduce import Trace

    enable_persistent_cache()
    devices = run.find_chips(jax, cell["chips"])
    if devices is None:
        return 3
    workload = run.load_json(BENCH / "workloads" / f"{cell['name']}.json")
    config = run.load_json(BENCH / "configs" / f"{cell['config']}.json")
    entry = run.load_module("entries", workload["entry"]).Entry(
        config, workload, args.seed, devices[0])
    entry.warmup()

    out_dir = BENCH / "out" / "scope_report"
    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    window = run.Window(entry, float("inf"), jax, run.CompileClock(jax))
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        stats = window.run(max_calls=1)
    finally:
        jax.profiler.stop_trace()
    path = sorted(out_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    trace, scopes = Trace.from_file(path), xplane_scopes.read(path)
    shutil.rmtree(out_dir, ignore_errors=True)

    line = {"workload": cell["name"], "seed": args.seed,
            "calls": stats["calls"], "slots": stats["slots"],
            "wall_s": stats["wall_s"]}
    line.update(report(trace, scopes, entry.facts()["backend"],
                       stats["slots"], stats["calls"]))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

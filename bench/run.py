"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json.  Everything that
belongs to one cell, configuration, entry or metric sits in a file of its
own under bench/, found by name:

    bench/workloads/<cell>.json     traffic, the entry it drives, the check
    bench/configs/<config>.json     the deployment (cluster, rates, horizon)
    bench/entries/<entry>.py        drives the program: warm-up, calls, check
    bench/metrics/<metric>.py       reads one per-layer metric
    bench/kernels/<kernel>.py       operations and bytes of one kernel
    bench/peaks.json                the chip's peaks, keyed by device kind

A run: set-up (program import, device, inputs from --seed, one warm-up
call of the entry at the cell's shapes); then the window, which starts
calls back to back while fewer than --seconds have passed and finishes the
call it has begun; then the check of the window's answers against the plain
reference under bench/reference/.  With --trace 0 the line carries the
cell's end-to-end metrics; with --trace 1 the profiler records the window
and the line carries the per-layer metrics, the device's busy and window
seconds, and a breakdown.  The numbers compared by the check are printed
with their limits as the last lines of standard error and under "checks",
the last key of the line.

It needs the chips the cell asks for.  Where JAX finds no TPU, or too few,
it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib.util
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# A traced run records this many calls of the window: one call's device
# operations number millions here, and collecting them takes minutes.
TRACED_CALLS = 1


def process_age() -> float:
    """Seconds since this process started (0 where /proc cannot say)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


AGE_AT_START = process_age()


def since_start() -> float:
    return AGE_AT_START + time.perf_counter() - T_START


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """bench/<kind>/<name>.py as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, cell: str):
    """(end-to-end, per-layer) metric entries the cell reports."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


class CompileClock:
    """Seconds JAX spends lowering, compiling and reading the persistent
    compilation cache, from its own monitoring events.  Tracing is left
    out: its events nest, one per inner jit, and would count twice."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration


def find_chips(jax, chips: int):
    """The first `chips` TPU devices, or None after saying why not."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU, JAX found {devices[0].platform!r} "
              f"({devices[0].device_kind})", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"run.py: the cell needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return None
    return devices[:chips]


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class Window:
    """Calls the entry back to back: starts a call while fewer than
    `seconds` have passed, and finishes the call it has begun."""

    def __init__(self, entry, seconds: float, jax, clock: CompileClock):
        self.entry, self.seconds, self.jax, self.clock = entry, seconds, jax, clock

    def run(self, max_calls=None):
        calls = slots = 0
        call_s = []
        c0 = self.clock.total
        t0 = time.perf_counter()
        while (time.perf_counter() - t0 < self.seconds
               and (max_calls is None or calls < max_calls)):
            t1 = time.perf_counter()
            with self.jax.profiler.TraceAnnotation("bench.call", call=calls):
                slots += self.entry.call(calls)
            call_s.append(time.perf_counter() - t1)
            calls += 1
        wall = time.perf_counter() - t0
        return {"calls": calls, "slots": slots, "wall_s": wall,
                "call_s": call_s, "compile_s": self.clock.total - c0}


def traced_window(window: Window, jax, out_dir: Path, calls: int):
    """The window with the profiler on, cut to its first `calls` calls;
    returns (window stats, trace)."""
    import shutil
    from trace_reduce import Trace

    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        stats = window.run(max_calls=calls)
    finally:
        jax.profiler.stop_trace()
    paths = sorted(out_dir.glob("plugins/profile/*/*.xplane.pb"))
    trace = Trace.from_file(paths[-1])
    shutil.rmtree(out_dir, ignore_errors=True)
    return stats, trace


def report_checks(checks) -> dict:
    """Print each compared number beside its limit (last lines of stderr)
    and return them for the result line."""
    out = {}
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr)
        out[c["name"]] = {"value": c["value"], "limit": c["limit"]}
    sys.stderr.flush()
    return out


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, find_chip: bool = True, overrides=None) -> int:
    """One run of one cell.  `find_chip=False` and `overrides` (a function
    that shrinks the config and workload dicts) serve the CPU tests: they
    drive the rest of a run at small sizes and print no device metric."""
    args = parse_args(argv)
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        print(f"run.py: no cell {args.workload!r} in BENCHMARK.json "
              f"(cells: {sorted(cells)})", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    e2e, per_layer = cell_metrics(spec, cell["name"])
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    try:
        import jax
        from repro.utils.cache import enable_persistent_cache
    except ImportError as e:
        print(f"run.py: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    enable_persistent_cache()
    if find_chip:
        devices = find_chips(jax, cell["chips"])
        if devices is None:
            return 3
        peaks = load_json(BENCH / "peaks.json")["devices"]
        kind = devices[0].device_kind
        if kind not in peaks:
            print(f"run.py: device kind {kind!r} is not in bench/peaks.json",
                  file=sys.stderr)
            return 4
        peak = peaks[kind]
    else:
        devices, peak = jax.devices()[:cell["chips"]], None
    clock = CompileClock(jax)

    workload = load_json(BENCH / "workloads" / f"{cell['name']}.json")
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    if overrides is not None:
        config, workload = overrides(config, workload)
    entry = load_module("entries", workload["entry"]).Entry(
        config, workload, args.seed, devices[0])
    with jax.profiler.TraceAnnotation("bench.setup"):
        entry.warmup()
    setup_s = since_start()

    window = Window(entry, args.seconds, jax, clock)
    if args.trace:
        stats, trace = traced_window(window, jax, BENCH / "out" / "trace",
                                     TRACED_CALLS)
    else:
        stats = window.run()
    peak_bytes = memory_peak(devices)

    with jax.profiler.TraceAnnotation("bench.check"):
        checks = entry.check()
    correct = all(c["ok"] for c in checks)

    metrics = {}
    if args.trace:
        from trace_reduce import reduce_run
        ctx = reduce_run(trace, stats, entry, peak)
        for m in per_layer:
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        # each backend's rate is a metric of its own, so that each has a
        # bound set from its own spread; all are the window's slots over
        # its wall time
        rate = stats["slots"] / stats["wall_s"]
        for m in e2e:
            if m["name"] == "setup_s":
                value = setup_s
            elif m["unit"] == "slots/s":
                value = rate
            else:
                raise ValueError(f"no reading for {m['name']!r}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": entry.attempted(stats),
              "failed": sum(not c["ok"] for c in checks),
              "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        result["breakdown"] = ctx.breakdown
    print(f"window: {stats['calls']} calls, {stats['slots']} config-slots in "
          f"{stats['wall_s']!r} s, compiling {stats['compile_s']!r} s; "
          f"set-up {setup_s!r} s; seconds a call {stats['call_s']!r}",
          file=sys.stderr, flush=True)
    result["checks"] = report_checks(checks)
    if not find_chip:
        # a rehearsal without the chip reports no device metric
        result["metrics"] = {}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

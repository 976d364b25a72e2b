"""Readings of a cell's compared numbers, for the program and for the
control, on many seeds in one process at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--calls 1]

For each seed the entry is built with that seed's inputs and driven as a
run drives it: `--calls` calls of the timed path (the first compiles, once
for the process), then the check.  That gives the program's reading of each
compared number.  Then the check again with the plain reference computed in
bfloat16, the precision below the model's float32, put in the program's
place: the control's reading.  The lower reading of a limit is the largest
the program gives over the seeds; the upper is the smallest the control
gives.  One JSON line per seed goes to standard output.  The benchmark's
own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402


def readings(cell: str, seeds, calls: int = 1, controls=None, *,
             find_chip=True, overrides=None):
    """Yields {seed, program: {name: value}, control: {tag: {name: value}}}
    for the cell's control, or for each of `controls` where given."""
    import jax
    from repro.utils.cache import enable_persistent_cache

    spec = run.load_json(BENCH.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    enable_persistent_cache()
    if find_chip:
        devices = run.find_chips(jax, cells[cell]["chips"])
        if devices is None:
            raise SystemExit(3)
    else:
        devices = jax.devices()
    workload = run.load_json(BENCH / "workloads" / f"{cell}.json")
    config = run.load_json(BENCH / "configs" / f"{cells[cell]['config']}.json")
    if overrides is not None:
        config, workload = overrides(config, workload)
    entry_cls = run.load_module("entries", workload["entry"]).Entry
    for seed in seeds:
        entry = entry_cls(config, workload, seed, devices[0])
        t0 = time.perf_counter()
        for i in range(calls):
            entry.call(i)
        t1 = time.perf_counter()
        program = {c["name"]: c["value"] for c in entry.check()}
        t2 = time.perf_counter()
        control = {}
        for ctl in controls or [workload["check"]["control"]]:
            tag = ",".join(f"{k}={v}" for k, v in sorted(ctl.items()))
            control[tag] = {c["name"]: c["value"]
                            for c in entry.check(control=ctl)}
        yield {"cell": cell, "seed": seed, "program": program,
               "control": control, "calls_s": t1 - t0, "check_s": t2 - t1,
               "control_s": time.perf_counter() - t2}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--calls", type=int, default=1)
    ap.add_argument("--control", action="append", type=json.loads,
                    help="a JSON object of reference overrides, in place of "
                         "the cell's own control (repeatable)")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    for r in readings(args.workload, seeds, args.calls, args.control):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Small sizes for the CPU tests: each cell's configuration and traffic cut
down so that a whole run (set-up, window, check) takes seconds on one CPU
core.  The fleet keeps M >= 1024, where `simulate` engages the fleet
backend."""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402


def shrink(config: dict, workload: dict):
    config, workload = dict(config), json.loads(json.dumps(workload))
    if workload["entry"] == "sweep":
        config.update(horizon=160, warmup=40)
        workload["loads"] = workload["loads"][-2:]
        workload["errors"] = workload["errors"][:1] + workload["errors"][-1:]
        workload["check"]["sample_per_load"] = 2
    else:
        config.update(num_servers=4008, horizon=384, warmup=128)
        workload["check"]["chunks"] = 2
    return config, workload


def run_cell(cell: str, seed: int = 1234567890123, capsys=None) -> dict:
    """One whole run of `cell` at the small size without the chip; returns
    the result line."""
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "0.01", "--trace", "0"], find_chip=False, overrides=shrink)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(out)

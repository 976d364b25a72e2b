"""The control comes out not correct, and the program correct, on three seeds
at the small sizes of `small.py` (CPU).  At each cell's own size the same
readings come from the chip:

    python3 bench/control.py --workload <cell> --seeds 4294967311,2147483659,3000000019

The control is the plain reference with the cell's `check.control` put in
the program's place: bfloat16 rates, workloads and scores, the precision
below the float32 that both configurations state.

    python -m pytest -q bench/tests/test_control.py
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

import small
import control

SEEDS = (4294967311, 2147483659, 3000000019)
BENCH = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("cell", ["paper24.bp_fig3", "paper24.mw_fig3",
                                  "borg10k.bp_uniform"])
def test_control_fails_and_program_passes(cell):
    limits = json.loads((BENCH / "workloads" / f"{cell}.json").read_text()
                        )["check"]["limits"]
    for r in control.readings(cell, SEEDS, find_chip=False,
                              overrides=small.shrink):
        assert all(v <= limits[k] for k, v in r["program"].items()), r
        (ctl,) = r["control"].values()
        assert any(v > limits[k] for k, v in ctl.items()), r

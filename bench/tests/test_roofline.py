"""The route kernel's operation and byte count against a hand count, and the
least time against the peaks table.

    python -m pytest -q bench/tests/test_roofline.py
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]


def _count():
    spec = importlib.util.spec_from_file_location(
        "fleet_route_count", BENCH / "kernels" / "fleet_route.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_hand_count_two_tasks_four_servers():
    # B=2 tasks, M=4 servers, K=3 tiers, one level (racks).
    # workload, per server: 3 divisions + 2 additions of the tier sum,
    #   1 comparison + 1 division + 1 selection of the residual, 1 addition
    #   = 9, times 4 servers = 36
    # per pair: locality 3 == and 2 or (5); the rack level 3 == and 2 or and
    #   2 selections (7); local override 2 selections; score div, mul, sub
    #   (3); private mask == and select (2); min (1); lowest index ==,
    #   select, min (3); tier at index ==, select, min (3) = 26, times 8 = 208
    k = _count()
    assert k.ops(b=2, m=4, k=3, depth=1) == 36 + 208
    # bytes: q and rates 2*4*3, serving 4, ancestors 4, tasks 2*6,
    #   results 3*2 = 50 elements of 4 bytes
    assert k.bytes_moved(b=2, m=4, k=3, depth=1) == 200


def test_cell_shape_is_bound_by_operations():
    k = _count()
    peak = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
    shape = {"b": 5474, "m": 10008, "k": 3, "depth": 1}
    least, bound = k.least_time(shape, peak)
    assert bound == "ops"
    assert least == pytest.approx(
        (10008 * 9 + 5474 * 10008 * 26) / 197e12)

"""The trace reduction: busy union, idle gaps named by host spans, and one
kernel's event sums, on a hand-made trace and on a small trace recorded on
a TPU v5e (bench/traces/small.xplane.pb: three calls of the fleet route
kernel and of a small jitted program, each inside a `bench.call` span, each
followed by 20 ms of host sleep inside a `bench.host_wait` span).

    python -m pytest -q bench/tests/test_trace_reduce.py
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from trace_reduce import MODULES, OPS, Trace, union  # noqa: E402

RECORDED = BENCH / "traces" / "small.xplane.pb"


def hand_made():
    ops = [("a", 100, 200), ("b", 200, 300), ("fleet_route_k", 400, 450),
           ("while", 700, 900), ("a", 700, 800), ("fleet_route_k", 850, 900)]
    modules = [("m1", 100, 300), ("m2", 400, 450), ("m3", 700, 900)]
    host = [("bench.call", 90, 460, "python3"), ("bench.call", 690, 910, "python3"),
            ("bench.host_wait", 470, 680, "python3"),
            ("jit_compile", 320, 390, "python3")]
    return Trace({"/device:TPU:0": {OPS: ops, MODULES: modules}}, host)


def test_union_merges_overlaps():
    assert union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]


def test_hand_made_busy_gaps_and_kernel():
    t = hand_made()
    lo, hi = t.window()
    assert (lo, hi) == (90, 910)
    # busy: the programs [100, 300] + [400, 450] + [700, 900]
    assert t.busy_ns(lo, hi) == 200 + 50 + 200
    gaps = t.gaps(lo, hi)
    assert gaps == [(90, 100), (300, 400), (450, 700), (900, 910)]
    assert t.name_gap(300, 400) == "jit_compile"
    assert t.name_gap(450, 700) == "bench.host_wait"
    assert t.matching("fleet_route", lo, hi) == [50e-9, 50e-9]
    ops = t.op_seconds(lo, hi)
    # self time: the while loop's 200 less its nested a (100) and kernel (50)
    assert ops["while"] == pytest.approx(50e-9)
    assert ops["a"] == pytest.approx(200e-9)
    assert ops["b"] == pytest.approx(100e-9)


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace():
    t = Trace.from_file(RECORDED)
    assert list(t.devices) == ["/device:TPU:0"]
    lo, hi = t.window()
    busy = t.busy_ns(lo, hi)
    # busy by brute force: every nanosecond that some program covers
    ops = [(s, e) for _, s, e in t.devices["/device:TPU:0"][MODULES]
           if e > lo and s < hi]
    edges = sorted({lo, hi, *[max(s, lo) for s, _ in ops],
                    *[min(e, hi) for _, e in ops]})
    brute = sum(b - a for a, b in zip(edges, edges[1:])
                if any(s <= a and b <= e for s, e in ops))
    assert busy == pytest.approx(brute)
    assert 0 < busy < hi - lo
    gaps = t.gaps(lo, hi)
    assert sum(e - s for s, e in gaps) == pytest.approx(hi - lo - busy)
    # the two longest gaps are the host sleeps between the three calls
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:2]
    assert [t.name_gap(s, e) for s, e in longest] == ["bench.host_wait"] * 2
    assert all(e - s >= 20e6 for s, e in longest)
    # the device clock runs about 1 ms behind the host's in this trace, so
    # the first call's kernel starts before the first host span: count
    # the kernel over the whole trace
    kernel = t.matching("fleet_route_pallas", 0, float("inf"))
    assert len(kernel) == 3 and all(0 < d < 1e-2 for d in kernel)
    assert sum(kernel) == pytest.approx((4092 + 4092 + 4093) * 1e-9)


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_each_cell_reads_its_per_layer_metrics():
    """Every per-layer metric BENCHMARK.json gives a cell has its reader, and
    the reader finds something in a trace of that cell's backend (the
    recorded trace stands in: it holds programs and the route kernel)."""
    import small  # noqa: F401  (puts src/ on the path)
    import run
    from trace_reduce import reduce_run

    spec = run.load_json(BENCH.parent / "BENCHMARK.json")
    peak = run.load_json(BENCH / "peaks.json")["devices"]["TPU v5 lite"]
    trace = Trace.from_file(RECORDED)
    for cell in spec["workloads"]:
        workload = run.load_json(BENCH / "workloads" / f"{cell['name']}.json")
        config = run.load_json(BENCH / "configs" / f"{cell['config']}.json")
        entry = run.load_module("entries", workload["entry"]).Entry(
            config, workload, 1, None)
        ctx = reduce_run(trace, {"slots": 3, "compile_s": 0.5}, entry, peak)
        _, per_layer = run.cell_metrics(spec, cell["name"])
        assert per_layer
        for m in per_layer:
            value = run.load_module("metrics", m["name"]).read(ctx)
            assert value is not None, (cell["name"], m["name"])

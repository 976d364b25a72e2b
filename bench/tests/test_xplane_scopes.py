"""The reader of the program's named scopes (bench/xplane_scopes.py) and
the readings of scopes and host spans (bench/scope_report.py).

Two recorded TPU v5e traces:

* bench/traces/small.xplane.pb (see test_trace_reduce.py): no scope, no
  program span.  On it the proto reader gives every operation event of
  `jax.profiler.ProfileData`, at the same times and under the same short
  names, and every reading of scopes and spans is null (a program without
  them reads nothing, and nothing raises).
* bench/traces/scopes.xplane.pb: written by bench/tests/record_scopes.py
  (`python3 bench/tests/record_scopes.py` on one chip): one call each of
  the `paper24.bp_fig3`, `paper24.mw_fig3` and `borg10k.bp_uniform`
  entries at tiny sizes (dense: one configuration, 16 slots; fleet: 1,008
  servers, one 16-slot chunk), each inside a `bench.call` span, host
  tracer level 1, then cut to what the readers use (`record_scopes.shrink`:
  0.68 MB of the 11.3 MB recorded; the same scope seconds and busy time in
  each call).  On it the scopes' self time in each call is at most the
  device's busy time, each dense call traces its program once, inside
  `sim.prepare`, and every reading of the call's backend is a number.

    python -m pytest -q bench/tests/test_xplane_scopes.py
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import scope_report  # noqa: E402
import xplane_scopes  # noqa: E402
from scope_report import overlap, span_idle_s  # noqa: E402
from trace_reduce import OPS, Trace, union  # noqa: E402

SMALL = BENCH / "traces" / "small.xplane.pb"
SCOPES = BENCH / "traces" / "scopes.xplane.pb"


def test_scope_of_takes_the_innermost():
    assert xplane_scopes.scope_of(
        "jit(chunk)/while/body/sim.route/sim.private/jit(f)/pallas_call"
    ) == "sim.private"
    assert xplane_scopes.scope_of("jit(f)/vmap(while)/body/sim.serve/add") \
        == "sim.serve"
    assert xplane_scopes.scope_of("jit(fleet_route)/select_n:") is None


def test_overlap_and_span_idle():
    assert overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert overlap([(0, 10)], [(10, 20)]) == 0
    # device busy [100, 300] and [400, 450]; idle gaps [90, 100],
    # [300, 400], [450, 500]; a span over [250, 480] holds 100 + 30 idle
    trace = Trace({"/device:TPU:0": {"XLA Modules": [("m", 100, 300),
                                                     ("m", 400, 450)]}},
                  [("bench.call", 90, 500, "py"),
                   ("sim.prepare", 250, 480, "py")])
    assert span_idle_s(trace, "sim.prepare", 90, 500, 2) \
        == pytest.approx(130e-9 / 2)
    assert span_idle_s(trace, "sim.fetch", 90, 500, 2) is None


@pytest.mark.skipif(not SMALL.exists(), reason="no recorded trace")
def test_reader_matches_profile_data():
    ref, got = Trace.from_file(SMALL), xplane_scopes.read(SMALL)
    assert list(got.devices) == list(ref.devices)
    for plane in ref.devices:
        want = ref.devices[plane][OPS]
        assert len(want) > 0
        assert got.devices[plane][OPS] == want


@pytest.mark.skipif(not SMALL.exists(), reason="no recorded trace")
@pytest.mark.parametrize("backend", ["dense", "fleet"])
def test_readings_null_without_scopes(backend):
    trace, scopes = Trace.from_file(SMALL), xplane_scopes.read(SMALL)
    got = scope_report.readings(trace, scopes, backend, slots=3, calls=1)
    assert got and all(v is None for v in got.values()), got


@pytest.mark.skipif(not SCOPES.exists(), reason="no recorded scopes trace")
def test_scopes_within_busy_in_each_call():
    trace, scopes = Trace.from_file(SCOPES), xplane_scopes.read(SCOPES)
    calls = sorted((s, e) for n, s, e, _ in trace.host if n == "bench.call")
    assert len(calls) == 3
    for i, (lo, hi) in enumerate(calls):
        own = scopes.op_seconds(lo, hi)
        in_scope = sum(v for k, v in own.items() if k.startswith("sim."))
        busy = trace.busy_ns(lo, hi) / 1e9
        assert 0 < in_scope <= busy * (1 + 1e-9), i
        # the fleet call (the last) routes privately and fills the pool
        want = {"sim.arrivals", "sim.route", "sim.serve"}
        if i == 2:
            want |= {"sim.private", "sim.fill"}
        assert {k for k in own if k.startswith("sim.")} == want, i
        prep = union([(s, e) for n, s, e, _ in trace.host
                      if n == "sim.prepare" and lo <= s <= hi])
        tr = [(s, e) for n, s, e, _ in trace.host
              if n == "sim.trace" and lo <= s <= hi]
        if i < 2:   # each dense sweep traces its program once, while it
            assert len(prep) == 1 and len(tr) == 1   # prepares
            assert prep[0][0] <= tr[0][0] and tr[0][1] <= prep[0][1]


@pytest.mark.skipif(not SCOPES.exists(), reason="no recorded scopes trace")
def test_every_reading_in_each_call():
    trace, scopes = Trace.from_file(SCOPES), xplane_scopes.read(SCOPES)
    calls = sorted((s, e) for n, s, e, _ in trace.host if n == "bench.call")
    for (lo, hi), backend in zip(calls, ["dense", "dense", "fleet"]):
        one = Trace(trace.devices, [h for h in trace.host
                                    if lo <= h[1] and h[2] <= hi])
        got = scope_report.report(one, scopes, backend, slots=16, calls=1)
        assert all(v is not None for v in got["readings"].values()), got
        assert got["readings"].get("sweep_traces_per_call", 1) == 1
        assert 0 < got["attributed_pct"] <= 100 * (1 + 1e-9)

"""The simulator names its layers inside the program, on the profiler's
clock: device scopes (`jax.named_scope`) in the compiled programs' op
metadata, and host spans (`telemetry.maybe_span`) in a `jax.profiler`
trace.  See docs/observability.md, "Profiler spans and scopes".

* every registered policy's dense slot step compiles with its arrivals,
  routing and service under `sim.arrivals`, `sim.route` and `sim.serve`;
  the fleet chunk (segment-min routing) adds `sim.private` and `sim.fill`;
* a profiler trace of one `sweep` holds `sim.prepare`, the one
  `sim.trace` of its program inside it, then `sim.fetch`; a repeated
  `sweep` of the same configuration reuses its program and opens no
  `sim.trace`;
* the span helper annotates the profiler trace with and without an
  `EventRecorder`, and still fills the recorder's ring.

The scopes change no result: the bitwise sample-path pins of
tests/test_topology.py and tests/test_fleet_scale.py run through them.
"""

from __future__ import annotations

import re

import numpy as np
import pytest

import jax

from repro.core import locality as loc, simulator as sim
from repro.core.policy import PolicyConfig, available_policies
from repro.sharding.sim import FleetConfig, _build_fleet_chunk, fleet_simulate
from repro.telemetry import EventRecorder, maybe_span

DENSE_SCOPES = {"sim.arrivals", "sim.route", "sim.serve"}
FLEET_SCOPES = DENSE_SCOPES | {"sim.private", "sim.fill"}


def _cfg(**kw):
    return sim.SimConfig(topo=loc.Topology(24, 6), true_rates=loc.Rates(),
                         p_hot=0.5, max_arrivals=8, horizon=12, warmup=4,
                         **kw)


def _policy(name):
    if name == "blind_pandas":
        return PolicyConfig(name, {"prior": loc.Rates().values})
    return name


def _scopes(compiled_text: str) -> set:
    """The `sim.*` elements of every op's name stack in compiled HLO."""
    out = set()
    for stack in re.findall(r'op_name="([^"]*)"', compiled_text):
        out.update(re.findall(r"sim\.[a-z_]+", stack))
    return out


@pytest.mark.parametrize("name", available_policies())
def test_dense_sweep_scopes_in_hlo(name):
    cfg = _cfg()
    run = sim._build_run(_policy(name), cfg)
    f = jax.vmap(jax.vmap(jax.vmap(run, (None, None, 0)), (None, 0, None)),
                 (0, None, None))
    est = np.stack([sim.make_estimates(cfg, "network", 0.0, -1)] * 2)
    text = jax.jit(f).lower(np.full((2,), 4.0, np.float32),
                            est.astype(np.float32),
                            np.arange(2, dtype=np.uint32)
                            ).compile().as_text()
    assert _scopes(text) == DENSE_SCOPES


@pytest.mark.parametrize("name", ["balanced_pandas", "pandas_po2"])
def test_fleet_chunk_scopes_in_hlo(name):
    cfg = sim.SimConfig(topo=loc.Topology(48, 6), true_rates=loc.Rates(),
                        p_hot=0.5, max_arrivals=16, horizon=8, warmup=2)
    init, chunk = _build_fleet_chunk(
        name, cfg, FleetConfig(chunk=4, unroll=1, use_pallas=False))
    est = loc.per_server_rates(loc.Rates().as_array(), 48).astype(np.float32)
    text = jax.jit(chunk).lower(init(), np.int32(0), np.float32(8.0), est,
                                np.uint32(0)).compile().as_text()
    # power-of-d routing has no private phase and no pool
    want = FLEET_SCOPES if name == "balanced_pandas" else DENSE_SCOPES
    assert _scopes(text) == want


def _host_events(logdir) -> list:
    """(name, start_ns, end_ns) of every host event of the trace."""
    from jax.profiler import ProfileData

    path = sorted(logdir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.end_ns)
                           for e in line.events)
    return out


def _named(events, name):
    return sorted((s, e) for n, s, e in events if n == name)


def test_sweep_host_spans(tmp_path):
    cfg = _cfg()
    est = np.stack([sim.make_estimates(cfg, "network", 0.0, -1)])
    sim.clear_program_cache()   # no earlier test's program for this key
    with jax.profiler.trace(str(tmp_path)):
        out = sim.sweep("balanced_pandas", cfg, np.asarray([4.0]), est,
                        np.arange(2, dtype=np.uint32))
    assert out["mean_n"].shape == (1, 1, 2)
    events = _host_events(tmp_path)
    (prep,), (fetch,) = _named(events, "sim.prepare"), _named(events,
                                                             "sim.fetch")
    traces = _named(events, "sim.trace")
    # one program, traced once, inside the preparation; then the fetch
    assert len(traces) == 1
    assert prep[0] <= traces[0][0] and traces[0][1] <= prep[1]
    assert prep[1] <= fetch[0]


def test_repeated_sweep_host_spans(tmp_path):
    """`sweep` keeps its program per configuration: a second call of the
    same configuration, with new seeds, opens no `sim.trace` span."""
    cfg = _cfg()
    est = np.stack([sim.make_estimates(cfg, "network", 0.0, -1)])
    sim.sweep("balanced_pandas", cfg, np.asarray([4.0]), est,
              np.arange(2, dtype=np.uint32))
    with jax.profiler.trace(str(tmp_path)):
        out = sim.sweep("balanced_pandas", cfg, np.asarray([4.0]), est,
                        np.arange(2, 4, dtype=np.uint32))
    assert out["mean_n"].shape == (1, 1, 2)
    events = _host_events(tmp_path)
    (prep,), (fetch,) = _named(events, "sim.prepare"), _named(events,
                                                             "sim.fetch")
    assert _named(events, "sim.trace") == []
    assert prep[1] <= fetch[0]


def test_fleet_chunk_traces_once(tmp_path):
    """The fleet path caches its jitted chunk: a second `simulate` of the
    same configuration opens no `sim.trace` span."""
    cfg = sim.SimConfig(topo=loc.Topology(48, 6), true_rates=loc.Rates(),
                        p_hot=0.5, max_arrivals=16, horizon=8, warmup=2)
    fc = FleetConfig(chunk=4, unroll=1, use_pallas=False)
    est = loc.per_server_rates(loc.Rates().as_array(), 48).astype(np.float32)
    with jax.profiler.trace(str(tmp_path)):
        fleet_simulate("balanced_pandas", cfg, 8.0, est, seed=1, fleet=fc)
        fleet_simulate("balanced_pandas", cfg, 8.0, est, seed=2, fleet=fc)
    assert len(_named(_host_events(tmp_path), "sim.trace")) <= 1


def test_span_helper_annotates_profiler(tmp_path):
    tr = EventRecorder(capacity=8)
    with jax.profiler.trace(str(tmp_path)):
        with maybe_span(None, "no_recorder"):
            pass
        with maybe_span(tr, "with_recorder", cat="host"):
            pass
        with tr.span("recorder_span"):
            pass
    events = _host_events(tmp_path)
    for name in ("no_recorder", "with_recorder", "recorder_span"):
        assert len(_named(events, name)) == 1, name
    assert [e["name"] for e in tr.events()] == ["with_recorder",
                                                "recorder_span"]

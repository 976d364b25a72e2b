"""Dense study programs are kept per configuration (`simulator._program`).

A repeated `sweep` or `simulate` of one configuration reuses its jitted
program: JAX does not trace it again (no `sim.trace` span opens), and the
results are bitwise those of a program built fresh.  A changed
configuration gets a program of its own; an argument with no value key is
built fresh every call; `jax.clear_caches()` sends a kept program through
a new trace, so a patched module function reaches it; and the cache holds
at most `_PROGRAMS_MAX` programs.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import locality as loc, simulator as sim
from repro.core.policy import PolicyConfig

CFG = sim.SimConfig(topo=loc.Topology(12, 4), true_rates=loc.Rates(),
                    p_hot=0.5, max_arrivals=8, horizon=60, warmup=10)
LAM = np.asarray([3.0, 4.0], np.float32)
EST = np.stack([sim.make_estimates(CFG, "network", 0.0, -1),
                sim.make_estimates(CFG, "per_server", 0.2, -1, seed=1)])


@pytest.fixture(autouse=True)
def no_kept_programs():
    sim.clear_program_cache()
    yield
    sim.clear_program_cache()


@pytest.fixture
def traces(monkeypatch):
    """Names of the spans the simulator opens; `sim.trace` opens only
    while JAX traces a program."""
    opened = []

    def span(tracer, name, *a, **k):
        opened.append(name)
        return contextlib.nullcontext()
    monkeypatch.setattr(sim, "maybe_span", span)
    return lambda: opened.count("sim.trace")


def _fresh_sweep(policy, cfg, seeds, **kw):
    """The sweep program built and jitted as every call did before
    programs were kept."""
    run = sim._build_run(policy, cfg, **kw)
    f = jax.jit(jax.vmap(jax.vmap(jax.vmap(run, (None, None, 0)),
                                  (None, 0, None)), (0, None, None)))
    out = f(jnp.asarray(LAM, jnp.float32), jnp.asarray(EST, jnp.float32),
            jnp.asarray(seeds, jnp.uint32))
    return {k: np.asarray(v) for k, v in out.items()}


def _assert_bitwise(a, b):
    assert sorted(a) == sorted(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_repeated_sweep_is_not_traced_again(traces):
    sim.sweep("balanced_pandas", CFG, LAM, EST, np.arange(2))
    assert traces() == 1
    seeds = np.asarray([7, 11], np.uint32)
    out = sim.sweep("balanced_pandas", CFG, LAM, EST, seeds)
    assert traces() == 1
    _assert_bitwise(out, _fresh_sweep("balanced_pandas", CFG, seeds))


@pytest.mark.parametrize("change", ["horizon", "policy", "scenario"])
def test_changed_configuration_traces_its_own_program(change, traces):
    sim.sweep("balanced_pandas", CFG, LAM, EST, np.arange(2))
    policy, cfg, kw = "balanced_pandas", CFG, {}
    if change == "horizon":
        cfg = sim.SimConfig(topo=CFG.topo, true_rates=CFG.true_rates,
                            p_hot=CFG.p_hot, max_arrivals=CFG.max_arrivals,
                            horizon=80, warmup=CFG.warmup)
    elif change == "policy":
        policy = "jsq_maxweight"
    else:
        kw = {"scenario": "hot_shift"}
    out = sim.sweep(policy, cfg, LAM, EST, np.arange(2), **kw)
    assert traces() == 2
    _assert_bitwise(out, _fresh_sweep(policy, cfg, np.arange(2), **kw))


def test_argument_without_value_key_is_built_fresh(traces):
    policy = PolicyConfig("fifo", {"cap": 64})
    a = sim.sweep(policy, CFG, LAM, EST, np.arange(2))
    b = sim.sweep(policy, CFG, LAM, EST, np.arange(2))
    assert traces() == 2
    assert not sim._PROGRAMS
    _assert_bitwise(a, _fresh_sweep(policy, CFG, np.arange(2)))
    _assert_bitwise(a, b)


def test_repeated_simulate_traces_once(traces):
    a = sim.simulate("balanced_pandas", CFG, 4.0, EST[0], seed=1)
    b = sim.simulate("balanced_pandas", CFG, 4.0, EST[0], seed=2)
    c = sim.simulate("balanced_pandas", CFG, 4.0, EST[0], seed=1)
    assert traces() == 1
    assert a == c and a["mean_n"] != b["mean_n"]
    # one program per kind: the sweep of the same configuration is its own
    sim.sweep("balanced_pandas", CFG, LAM, EST, np.arange(2))
    assert traces() == 2


def test_clear_caches_sends_kept_program_through_a_new_trace(monkeypatch):
    seeds = np.arange(2)
    sound = sim.sweep("balanced_pandas", CFG, LAM, EST, seeds)
    orig = loc.sample_arrivals_at

    def half(key, rack_of, lam, p_hot, hot_rack, max_arrivals, *a, **k):
        types, active = orig(key, rack_of, lam, p_hot, hot_rack,
                             max_arrivals, *a, **k)
        return types, active & (jnp.arange(max_arrivals) < max_arrivals // 2)
    monkeypatch.setattr(loc, "sample_arrivals_at", half)
    try:
        jax.clear_caches()
        patched = sim.sweep("balanced_pandas", CFG, LAM, EST, seeds)
        assert len(sim._PROGRAMS) == 1
        _assert_bitwise(patched, _fresh_sweep("balanced_pandas", CFG, seeds))
        assert not np.array_equal(patched["mean_n"], sound["mean_n"])
    finally:
        sim.clear_program_cache()
        jax.clear_caches()


def test_cache_holds_at_most_its_bound():
    def cfg(h):
        return sim.SimConfig(topo=CFG.topo, true_rates=CFG.true_rates,
                             horizon=h, warmup=CFG.warmup)
    first = sim._program("sweep", "balanced_pandas", cfg(20), None, None,
                         None, None, None)
    for h in range(21, 21 + sim._PROGRAMS_MAX + 8):
        sim._program("sweep", "balanced_pandas", cfg(h), None, None, None,
                     None, None)
        # the first, used every time, is the last to go
        assert sim._program("sweep", "balanced_pandas", cfg(20), None,
                            None, None, None, None) is first
        assert len(sim._PROGRAMS) <= sim._PROGRAMS_MAX
    assert len(sim._PROGRAMS) == sim._PROGRAMS_MAX
    # the least recently used went first
    assert sim._value_key(("sweep", "balanced_pandas", cfg(21), None, None,
                           None, None, None)) not in sim._PROGRAMS


@pytest.mark.parametrize("make", [
    lambda: None, lambda: "balanced_pandas", lambda: True, lambda: 3,
    lambda: 0.5, lambda: (1, 2.0, "a"), lambda: loc.Rates(0.5, 0.45, 0.25),
    lambda: loc.Topology(24, 6),
    lambda: sim.SimConfig(topo=loc.Topology(12, 4), true_rates=loc.Rates(),
                          p_hot=0.5, max_arrivals=8, horizon=60, warmup=10)])
def test_value_key_of_value_types(make):
    # an equal value that is another object finds the same program
    key = sim._value_key(make())
    assert key is not None
    assert key == sim._value_key(make())
    assert hash(key) == hash(sim._value_key(make()))


@pytest.mark.parametrize("value", [
    {"cap": 64}, PolicyConfig("fifo", {"cap": 64}), np.zeros(3),
    [1, 2], object(), (1, {"a": 1})])
def test_value_key_refuses_what_has_no_value(value):
    assert sim._value_key(value) is None


def test_value_key_keeps_types_apart():
    assert sim._value_key(1) != sim._value_key(1.0)
    assert sim._value_key(True) != sim._value_key(1)
    assert sim._value_key(CFG) != sim._value_key(
        sim.SimConfig(topo=CFG.topo, true_rates=CFG.true_rates, p_hot=0.5,
                      max_arrivals=8, horizon=60.0, warmup=10))

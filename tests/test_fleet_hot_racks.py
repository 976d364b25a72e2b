"""Hot traffic over weighted racks on the fleet path (`hot_racks`).

What is pinned here and why:

* the O(B) fleet sampler draws the `hot_racks` law: every hot task's
  three replicas are distinct and inside one rack of positive weight,
  the hot racks' counts fit the weights, cold tasks cover the fleet;
* without weights (``"static"``, ``None``) the fleet sample path is the
  one it always was: one program, bitwise-equal metrics;
* the fleet path under `hot_racks` stays inside the fleet-vs-dense delay
  band of tests/test_fleet_scale.py, the dense path being the
  statistical oracle, and serves the offered load;
* only stationary scenarios reach the fleet path; the others are
  refused with a reason;
* the chunk cache keys on the weights: a change of weights compiles a
  new program, a repeat call reuses its own.
"""

import numpy as np
import pytest

import jax

from repro import workloads as wl
from repro.core import locality as loc, simulator as sim
from repro.sharding import sim as fs
from repro.sharding.sim import (
    FleetCarry, FleetConfig, fleet_simulate, fleet_supported, make_ctx,
    stationary_traffic,
)

TOPO = loc.Topology(240, 6)          # 40 racks
RATES = loc.Rates()
EST = loc.per_server_rates(RATES.as_array(), 240)
# 240 servers are 10 copies of the paper's 24-server cluster, each with
# its one hot rack; with a hot task's rack uniform among those 10, the
# fluid capacity is 10 times the paper's
CAP = 10 * loc.capacity_hot_rack(loc.Topology(24, 6), RATES, 0.5)
LAM = 0.8 * CAP


def _cfg(horizon=2000, warmup=600, lam=LAM):
    return sim.SimConfig(topo=TOPO, true_rates=RATES, p_hot=0.5,
                         max_arrivals=int(2.05 * lam), horizon=horizon,
                         warmup=warmup)


def _draws(weights, slots=200, batch=256, p_hot=0.5):
    """(types (N, 3), hot (N,)) of the active lanes of `slots` slots."""
    cfg, w = stationary_traffic(_cfg(), wl.make_scenario(
        "hot_racks", weights=weights, p_hot=p_hot))
    ctx = make_ctx(TOPO, w)
    lam = 0.5 * batch

    def slot(key):
        types, active = fs._sample_arrivals(key, ctx, lam, cfg.p_hot, batch)
        return types, active, fs._hot_lanes(key, ctx, cfg.p_hot, batch)

    keys = jax.random.split(jax.random.PRNGKey(11), slots)
    types, active, hot = jax.vmap(slot)(keys)
    active = np.asarray(active).reshape(-1)
    return (np.asarray(types).reshape(-1, 3)[active],
            np.asarray(hot).reshape(-1)[active])


def test_hot_replicas_distinct_inside_one_hot_rack():
    types, hot = _draws((1.0, 0.0, 0.0, 0.0))
    assert hot.sum() > 1000 and (~hot).sum() > 1000
    h = types[hot]
    assert (h[:, 0] < h[:, 1]).all() and (h[:, 1] < h[:, 2]).all()
    racks = h // 6
    assert (racks == racks[:, :1]).all()
    assert set(np.unique(racks[:, 0])) == set(range(0, 40, 4))


def test_hot_rack_counts_fit_weights():
    weights = (3.0, 1.0, 0.0, 0.0)
    types, hot = _draws(weights, slots=400)
    racks = types[hot][:, 0] // 6
    w = np.array([weights[r % 4] for r in range(40)])
    assert not np.isin(racks, np.flatnonzero(w == 0)).any()
    live = np.flatnonzero(w > 0)
    observed = np.bincount(racks, minlength=40)[live]
    expected = len(racks) * w[live] / w.sum()
    chi2 = float(((observed - expected) ** 2 / expected).sum())
    # 19 degrees of freedom: the 0.999 quantile is 43.82
    assert chi2 < 43.82, (chi2, observed, expected)


def test_cold_tasks_cover_the_fleet():
    types, hot = _draws((1.0, 0.0, 0.0, 0.0))
    c = types[~hot]
    assert (c[:, 0] < c[:, 1]).all() and (c[:, 1] < c[:, 2]).all()
    counts = np.bincount(c.reshape(-1), minlength=240)
    expected = c.size / 240
    chi2 = float(((counts - expected) ** 2 / expected).sum())
    # 239 degrees of freedom: the 0.999 quantile is 312.3
    assert counts.min() > 0 and chi2 < 312.3, chi2


def test_p_hot_override_reaches_the_fleet_path():
    cfg, w = stationary_traffic(_cfg(), wl.make_scenario("hot_racks",
                                                         p_hot=0.3))
    assert cfg.p_hot == 0.3 and len(w) == 40
    _, hot = _draws((1.0, 0.0, 0.0, 0.0), p_hot=0.3)
    assert hot.mean() == pytest.approx(0.3, abs=0.02)


def test_no_weights_keeps_the_static_sample_path():
    cfg = _cfg(horizon=300, warmup=100)
    assert stationary_traffic(cfg, "static") == (cfg, None)
    assert stationary_traffic(cfg, None) == (cfg, None)
    static = sim.simulate("balanced_pandas", cfg, LAM, EST, seed=4,
                          scenario="static", fleet=True)
    default = sim.simulate("balanced_pandas", cfg, LAM, EST, seed=4,
                           fleet=True)
    direct = fleet_simulate("balanced_pandas", cfg, LAM, EST, seed=4)
    assert static == default == direct
    # a stationary segment that restates the configured p_hot is static
    restated = wl.Scenario("restated", (wl.Segment(start=0.0, p_hot=0.5),))
    assert sim.simulate("balanced_pandas", cfg, LAM, EST, seed=4,
                        scenario=restated, fleet=True) == static


def test_fleet_band_vs_dense_under_hot_racks():
    cfg = _cfg()
    dense = sim.simulate("balanced_pandas", cfg, LAM, EST, seed=0,
                         scenario="hot_racks", fleet=False)
    fleet = sim.simulate("balanced_pandas", cfg, LAM, EST, seed=0,
                         scenario="hot_racks", fleet=FleetConfig())
    assert dense["throughput"] == pytest.approx(LAM, rel=0.02)
    assert fleet["throughput"] == pytest.approx(LAM, rel=0.02)
    # the band of tests/test_fleet_scale.py
    assert fleet["mean_delay"] == pytest.approx(dense["mean_delay"],
                                                rel=0.15)
    assert fleet["hot_share"] == pytest.approx(0.5, abs=0.02)
    # the hot racks cannot serve their share at their local rate alone
    assert fleet["pool_share"] > 0.02


def test_kernel_and_segmin_bitwise_under_hot_racks():
    topo = loc.Topology(48, 6)
    cfg = sim.SimConfig(topo=topo, true_rates=RATES, p_hot=0.5,
                        max_arrivals=24, horizon=200, warmup=50)
    est = loc.per_server_rates(RATES.as_array(), 48)
    runs = [sim.simulate("balanced_pandas", cfg, 12.0, est, seed=3,
                         scenario="hot_racks",
                         fleet=FleetConfig(use_pallas=p))
            for p in (False, True)]
    assert runs[0] == runs[1]


def test_sweep_passes_the_weights():
    cfg = _cfg(horizon=200, warmup=50)
    lam_grid = np.array([0.6, 0.8], np.float32) * CAP
    out = sim.sweep("balanced_pandas", cfg, lam_grid, np.stack([EST]),
                    np.arange(2), scenario="hot_racks", fleet=True)
    one = sim.simulate("balanced_pandas", cfg, float(lam_grid[1]), EST,
                       seed=1, scenario="hot_racks", fleet=True)
    for key, val in one.items():
        assert float(out[key][1, 0, 1]) == val


def test_fleet_supported_stationary_only():
    cfg = _cfg(horizon=100, warmup=20)
    assert fleet_supported("balanced_pandas", cfg, "hot_racks") is None
    assert fleet_supported("balanced_pandas", cfg, "static") is None
    for name in ("diurnal", "hot_shift", "rack_loss", "flash_crowd"):
        reason = fleet_supported("balanced_pandas", cfg, name)
        assert isinstance(reason, str) and name in reason
    refused = [wl.Scenario("sag", (wl.Segment(start=0.0,
                                              tier_mult=(1.0, 0.5, 0.5)),)),
               wl.Scenario("rate", (wl.Segment(start=0.0, lam_mult=1.2),)),
               wl.Scenario("slow", (wl.Segment(start=0.0,
                                               slow_servers={3: 0.5}),)),
               wl.Scenario("down", (wl.Segment(start=0.0,
                                               down_servers=(3,)),)),
               wl.Scenario("rack2", (wl.Segment(start=0.0, hot_rack=2),))]
    for scn in refused:
        assert scn.name in fleet_supported("balanced_pandas", cfg, scn)
    with pytest.raises(ValueError, match="unsupported"):
        sim.simulate("balanced_pandas", cfg, 5.0, EST, seed=0,
                     scenario="diurnal", fleet=True)
    # fleet=None picks the fleet path for a supported fleet-sized run
    big = sim.SimConfig(topo=loc.Topology(1026, 6), true_rates=RATES,
                        p_hot=0.5, max_arrivals=16, horizon=100, warmup=20)
    assert sim._fleet_engaged(None, "balanced_pandas", big, "hot_racks",
                              None, None, None)


def test_chunk_cache_keys_on_weights(monkeypatch):
    monkeypatch.setattr(fs, "_CHUNK_CACHE", {})
    cfg = _cfg(horizon=256, warmup=64)
    fc = FleetConfig()
    w_a = stationary_traffic(cfg, "hot_racks")[1]
    w_b = stationary_traffic(cfg, wl.make_scenario(
        "hot_racks", weights=(0.0, 1.0)))[1]
    a = fleet_simulate("balanced_pandas", cfg, LAM, EST, seed=2, fleet=fc,
                       rack_weights=w_a)
    prog_a = fs._jitted_chunk("balanced_pandas", cfg, fc, w_a)
    assert len(fs._CHUNK_CACHE) == 1
    again = fleet_simulate("balanced_pandas", cfg, LAM, EST, seed=2,
                           fleet=fc, rack_weights=w_a)
    assert again == a and len(fs._CHUNK_CACHE) == 1
    assert fs._jitted_chunk("balanced_pandas", cfg, fc, w_a) is prog_a
    b = fleet_simulate("balanced_pandas", cfg, LAM, EST, seed=2, fleet=fc,
                       rack_weights=w_b)
    assert len(fs._CHUNK_CACHE) == 2
    assert fs._jitted_chunk("balanced_pandas", cfg, fc, w_b) is not prog_a
    assert b != a


def test_carry_reads_as_five_with_counters_by_name():
    cfg = _cfg(horizon=256, warmup=64)
    init, chunk = fs._jitted_chunk("balanced_pandas", cfg, FleetConfig(),
                                   stationary_traffic(cfg, "hot_racks")[1])
    carry = init()
    for ci in range(2):
        carry = chunk(carry, np.int32(ci * 128), np.float32(LAM), EST,
                      np.uint32(0))
    assert isinstance(carry, FleetCarry) and len(carry) == 5
    q, serving, mean_n, n_meas, compl = carry
    assert float(n_meas) == 256 - 64
    assert 0 < int(carry.pool_placed) < int(carry.hot_arrived)
    # a plain 5-tuple carry runs too, its counters starting at 0
    plain = chunk(tuple(init()), np.int32(0), np.float32(LAM), EST,
                  np.uint32(0))
    assert int(plain.hot_arrived) > 0

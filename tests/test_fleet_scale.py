"""Fleet fast path (sharding.sim): exactness, fidelity bands, gating.

What is pinned here and why:

* the fused route kernel, the segment-min route, and the dense (B, M)
  oracle agree **bitwise** on fuzzed topologies of every depth — the
  three implementations are one semantics contract
  (kernels/ref.fleet_route), including cross-tier score ties, which a
  naive per-level combine gets wrong;
* the dense simulator path is **bitwise-pinned** for all six policies:
  the fleet dispatch seam must not perturb sub-threshold runs at all;
* the fleet path's delay stays inside a band of the dense simulator at
  a mid-size fleet — the fast path is an approximation of the
  sequential in-slot dynamics (snapshot routing + retry passes +
  water-fill pool), and this band is the licensed error;
* chunked/donated execution is an implementation detail: results are
  bitwise-identical across chunk sizes, including ragged tails;
* the compiled chunk's HLO stays under a dispatch budget at M=2400 —
  slots/sec at fleet scale is dispatch-bound, so op-count growth is the
  leading indicator of a throughput regression (see docs/scaling.md).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import sample_path_pins
from repro.core import balanced_pandas as bp
from repro.core import locality as loc, simulator as sim
from repro.core.policy import available_policies
from repro.kernels import ops as kops, ref
from repro.sharding.sim import (
    FLEET_AUTO_THRESHOLD, FleetConfig, _build_fleet_chunk,
    _private_route_segmin, fleet_simulate, fleet_supported, fleet_sweep,
    make_ctx,
)

# fuzz topologies: (topology, rates) covering depth 0 (K=2), 1, and 2
TOPOS = (
    (loc.Topology(24), loc.Rates(0.5, 0.25)),
    (loc.Topology(24, 4), loc.Rates()),
    (loc.Topology(36, (3, 6)), loc.Rates(0.5, 0.45, 0.35, 0.25)),
)


# grids of several blocks in both directions (ops.fleet_route's blocks
# passed smaller): racks of 6 and groups of 12 straddle the 128-server
# block edges, the hot tasks sit on servers across one edge, and few
# queue values make equal scores in different blocks common
MULTI_BLOCK = (
    (loc.Topology(1200, 6), loc.Rates()),
    (loc.Topology(1200, (6, 12)), loc.Rates(0.5, 0.45, 0.35, 0.25)),
)
# (topology, rates, fuzz states, fuzz options, fleet_route block options)
ROUTE_CASES = (
    [(t, r, 10, {}, {}) for t, r in TOPOS]
    + [(t, r, 3, dict(batch=300, qmax=3, hot=np.arange(122, 134)),
        dict(block_tasks=64, block_servers=128)) for t, r in MULTI_BLOCK])
ROUTE_IDS = ["depth0", "depth1", "depth2", "depth1_multiblock",
             "depth2_multiblock"]


def _fuzz_state(rng, m, k, batch=17, qmax=60, hot=np.arange(6)):
    q = jnp.asarray(rng.integers(0, qmax, (m, k)), jnp.int32)
    serving = jnp.asarray(rng.integers(0, 8, (m,)), jnp.int32)
    # half the batch piles onto the hot servers so group minima collide
    hot = np.stack([np.sort(rng.choice(hot, 3, replace=False))
                    for _ in range(batch // 2)])
    cold = np.stack([np.sort(rng.choice(m, 3, replace=False))
                     for _ in range(batch - batch // 2)])
    locs = jnp.asarray(np.concatenate([hot, cold]), jnp.int32)
    return q, serving, locs


@pytest.mark.parametrize("topo,rates,states,fuzz,blocks", ROUTE_CASES,
                         ids=ROUTE_IDS)
def test_fleet_route_kernel_matches_oracle(topo, rates, states, fuzz,
                                           blocks):
    rng = np.random.default_rng(0)
    m = topo.num_servers
    ctx = make_ctx(topo)
    est = loc.per_server_rates(rates.as_array(), m)
    for _ in range(states):
        q, serving, locs = _fuzz_state(rng, m, est.shape[1], **fuzz)
        sk, tk, vk = kops.fleet_route(q, serving, est, ctx.anc, locs,
                                      **blocks)
        sr, tr, vr = ref.fleet_route(q, serving, est, ctx.anc, locs)
        np.testing.assert_array_equal(sk, sr)
        np.testing.assert_array_equal(tk, tr)
        np.testing.assert_array_equal(vk, vr)


@pytest.mark.parametrize("topo,rates,states,fuzz",
                         [c[:4] for c in ROUTE_CASES], ids=ROUTE_IDS)
def test_segmin_route_matches_oracle(topo, rates, states, fuzz):
    rng = np.random.default_rng(1)
    m = topo.num_servers
    ctx = make_ctx(topo)
    est = loc.per_server_rates(rates.as_array(), m)
    for _ in range(states):
        q, serving, locs = _fuzz_state(rng, m, est.shape[1], **fuzz)
        w = bp.workload(bp.PandasState(q=q, serving=serving), est)
        si, ti, vi = _private_route_segmin(w, est, ctx, locs)
        sr, tr, vr = ref.fleet_route(q, serving, est, ctx.anc, locs)
        np.testing.assert_array_equal(si, sr)
        np.testing.assert_array_equal(ti, tr)
        np.testing.assert_array_equal(vi, vr)


@pytest.mark.parametrize("topo,rates", TOPOS,
                         ids=["depth0", "depth1", "depth2"])
def test_kernel_and_segmin_paths_bitwise_in_loop(topo, rates):
    """Full fleet runs with use_pallas on/off are bitwise identical.

    This is strictly stronger than the single-call fuzz: the evolving
    queue state reaches cross-tier score ties (two different servers
    whose f32 scores at different tiers coincide exactly) that random
    states almost never hit; both paths must break them the way the
    dense (B, M) argmin does — lowest server index.
    """
    m = topo.num_servers
    cap = loc.capacity_hot_rack(topo, rates, 0.5)
    lam = 0.75 * cap
    est = loc.per_server_rates(rates.as_array(), m)
    cfg = sim.SimConfig(topo=topo, true_rates=rates, horizon=300,
                        warmup=100, p_hot=0.5,
                        max_arrivals=max(8, int(2.2 * lam)))
    a = fleet_simulate("balanced_pandas", cfg, lam, est, seed=3,
                       fleet=FleetConfig(use_pallas=False))
    b = fleet_simulate("balanced_pandas", cfg, lam, est, seed=3,
                       fleet=FleetConfig(use_pallas=True))
    assert a == b


# ---------------------------------------------------------------------------
# dense path: bitwise pins (fleet dispatch must not perturb it at all)

# recorded from the dense path by tests/sample_path_pins.py; exact f32
# values, not approximations (signal-free slo_pandas IS balanced_pandas,
# bitwise, by construction)
_DENSE_PINS = sample_path_pins.load()["dense"]


@pytest.mark.parametrize("name", sample_path_pins.POLICIES["dense"])
def test_dense_path_bitwise_pinned(name):
    assert set(available_policies()) == set(_DENSE_PINS)
    assert sample_path_pins.run_dense(name) == _DENSE_PINS[name]


# ---------------------------------------------------------------------------
# fidelity: fleet path vs the dense simulator at a mid-size fleet

_BAND_TOPO = loc.Topology(240, 6)
_BAND_RATES = loc.Rates()
_BAND_CAP = loc.capacity_hot_rack(_BAND_TOPO, _BAND_RATES, 0.5)
_BAND_LAM = 0.8 * _BAND_CAP
# the dense arm MUST get max_arrivals ~ 2*lam or arrivals truncate and
# the comparison is void (throughput pins below lam)
_BAND_CFG = sim.SimConfig(topo=_BAND_TOPO, true_rates=_BAND_RATES,
                          horizon=2000, warmup=600, p_hot=0.5,
                          max_arrivals=int(2.05 * _BAND_LAM))
_BAND_EST = loc.per_server_rates(_BAND_RATES.as_array(), 240)


def test_fleet_delay_band_vs_dense_balanced_pandas():
    dense = sim.simulate("balanced_pandas", _BAND_CFG, _BAND_LAM, _BAND_EST,
                         seed=0, fleet=False)
    fleet = fleet_simulate("balanced_pandas", _BAND_CFG, _BAND_LAM,
                           _BAND_EST, seed=0)
    # all offered load is served on both paths
    assert dense["throughput"] == pytest.approx(_BAND_LAM, rel=0.02)
    assert fleet["throughput"] == pytest.approx(dense["throughput"],
                                                rel=0.02)
    # delay band: snapshot routing + 2 retry passes + water-fill pool
    # tracks the sequential dynamics to within 15% at this size
    # (measured -2%; rounds=1 sits at +26% and must stay out of band)
    assert fleet["mean_delay"] == pytest.approx(dense["mean_delay"],
                                                rel=0.15)


def test_fleet_delay_band_vs_dense_pandas_po2():
    dense = sim.simulate("pandas_po2", _BAND_CFG, _BAND_LAM, _BAND_EST,
                         seed=0, fleet=False)
    fleet = fleet_simulate("pandas_po2", _BAND_CFG, _BAND_LAM, _BAND_EST,
                           seed=0)
    assert dense["throughput"] == pytest.approx(_BAND_LAM, rel=0.02)
    assert fleet["throughput"] == pytest.approx(dense["throughput"],
                                                rel=0.02)
    # batch-sampled power-of-d candidates vs sequential draws: same
    # distribution, different stream; measured +6% at this size
    assert fleet["mean_delay"] == pytest.approx(dense["mean_delay"],
                                                rel=0.15)


def test_fleet_rounds_monotone_fidelity():
    """More retry passes must not leave the band (and 1 pass is the
    documented loose end: overflow spills to the remote pool)."""
    f2 = fleet_simulate("balanced_pandas", _BAND_CFG, _BAND_LAM, _BAND_EST,
                        seed=0, fleet=FleetConfig(rounds=3))
    assert f2["throughput"] == pytest.approx(_BAND_LAM, rel=0.02)


# ---------------------------------------------------------------------------
# chunked/donated execution is bitwise-invariant

def test_chunk_size_invariance_bitwise():
    topo, rates = loc.Topology(36, (3, 6)), loc.Rates(0.5, 0.45, 0.35, 0.25)
    cap = loc.capacity_hot_rack(topo, rates, 0.5)
    lam = 0.75 * cap
    est = loc.per_server_rates(rates.as_array(), 36)
    # horizon 300 is a ragged multiple of both chunk sizes
    cfg = sim.SimConfig(topo=topo, true_rates=rates, horizon=300,
                        warmup=100, p_hot=0.5,
                        max_arrivals=max(8, int(2.2 * lam)))
    outs = [fleet_simulate("balanced_pandas", cfg, lam, est, seed=5,
                           fleet=FleetConfig(chunk=c, unroll=u))
            for c, u in ((32, 1), (128, 4), (512, 2))]
    assert outs[0] == outs[1] == outs[2]


def test_fleet_sweep_matches_simulate_bitwise():
    topo, rates = loc.Topology(24, 4), loc.Rates()
    cap = loc.capacity_hot_rack(topo, rates, 0.5)
    est = loc.per_server_rates(rates.as_array(), 24)
    cfg = sim.SimConfig(topo=topo, true_rates=rates, horizon=200, warmup=50,
                        p_hot=0.5, max_arrivals=16)
    lam_grid = np.array([0.6, 0.75], np.float32) * cap
    ests = np.stack([np.asarray(est)] * 2)
    ests[1, :, 1:] *= 0.9  # second error arm
    seeds = np.arange(2)
    out = fleet_sweep("balanced_pandas", cfg, lam_grid, ests, seeds)
    assert out["mean_delay"].shape == (2, 2, 2)
    assert np.isfinite(out["mean_delay"]).all()
    single = fleet_simulate("balanced_pandas", cfg, float(lam_grid[1]),
                            ests[0], seed=1)
    for key, val in single.items():
        assert float(out[key][1, 0, 1]) == val


# ---------------------------------------------------------------------------
# gating: who gets the fast path, and that refusal is loud

def _small_cfg(m=24):
    return sim.SimConfig(topo=loc.Topology(m, 6), true_rates=loc.Rates(),
                         p_hot=0.5, max_arrivals=16, horizon=100, warmup=20)


def test_fleet_supported_reasons():
    cfg = _small_cfg()
    assert fleet_supported("balanced_pandas", cfg, None, None, None,
                           None) is None
    assert fleet_supported("pandas_po2", cfg, None, None, None, None) is None
    for bad, kw in [("fifo", {}),
                    ("balanced_pandas", {"scenario": "server_loss"}),
                    ("balanced_pandas", {"telemetry": True})]:
        reason = fleet_supported(
            bad, cfg, kw.get("scenario"), kw.get("placement"),
            kw.get("replication"), kw.get("telemetry"))
        assert reason is not None and isinstance(reason, str)


def test_auto_gate_threshold():
    # below threshold: auto keeps the dense path even though supported
    assert not sim._fleet_engaged(None, "balanced_pandas", _small_cfg(24),
                                  None, None, None, None)
    assert FLEET_AUTO_THRESHOLD == 1024
    assert sim._fleet_engaged(None, "balanced_pandas", _small_cfg(1026),
                              None, None, None, None)
    # fleet=False pins dense at any size
    assert not sim._fleet_engaged(False, "balanced_pandas",
                                  _small_cfg(1026), None, None, None, None)


def test_forced_fleet_on_unsupported_raises():
    with pytest.raises(ValueError, match="unsupported"):
        sim.simulate("fifo", _small_cfg(), 5.0,
                     sim.make_estimates(_small_cfg(), "network", 0.0, -1),
                     seed=0, fleet=True)


def test_forced_fleet_dispatches_below_threshold():
    cfg = _small_cfg()
    cap = loc.capacity_hot_rack(cfg.topo, cfg.true_rates, 0.5)
    est = loc.per_server_rates(cfg.true_rates.as_array(), 24)
    via_sim = sim.simulate("balanced_pandas", cfg, 0.7 * cap, est, seed=2,
                           fleet=FleetConfig())
    direct = fleet_simulate("balanced_pandas", cfg, 0.7 * cap, est, seed=2)
    assert via_sim == direct


def test_fleet_config_validation():
    with pytest.raises(ValueError):
        FleetConfig(chunk=0)
    with pytest.raises(ValueError):
        FleetConfig(rounds=0)
    with pytest.raises(ValueError):
        FleetConfig(fill_iters=4)


# ---------------------------------------------------------------------------
# dispatch budget: op count of the compiled chunk at M=2400

def test_hlo_dispatch_budget_m2400():
    """The fleet path is dispatch-bound on CPU (and would be on any
    host-driven accelerator): wall clock tracks the number of compiled
    ops per slot, not FLOPs.  Pin a generous ceiling on the chunk
    program's total instruction count so an accidental O(M)-dense
    scatter or an unrolled Python loop shows up as a test failure, not
    as a silent 5x slots/sec regression.  Measured ~18.7k instructions
    (chunk=128, unroll=4) when pinned.
    """
    from repro.utils import hlo

    topo = loc.Topology(2400, 6)
    rates = loc.Rates()
    cap = loc.capacity_hot_rack(topo, rates, 0.5)
    lam = 0.8 * cap
    cfg = sim.SimConfig(topo=topo, true_rates=rates, p_hot=0.5,
                        max_arrivals=int(2.05 * lam), horizon=512,
                        warmup=128)
    est = loc.per_server_rates(rates.as_array(), 2400).astype(np.float32)
    init, chunk = _build_fleet_chunk("balanced_pandas", cfg, FleetConfig())
    args = (init(), np.int32(0), np.float32(lam), est, np.uint32(0))
    text = jax.jit(chunk).lower(*args).compile().as_text()
    comps = hlo.parse_computations(text)
    total = sum(len(instrs) for instrs in comps.values())
    assert 0 < total < 40_000, f"chunk program has {total} HLO instructions"

"""Tier-generic topology core: K-level hierarchies, K-vector rates, the
tier seam, the K-tier fluid capacity vs a brute-force LP, per-rack arrival
weights, and the K=3 sample-path pins.

The K=3 flat-rack default must keep reproducing the sample paths pinned in
tests/sample_path_pins.json (first recorded from the 3-tier code before
the tier-generic refactor, re-recorded per JAX build).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sample_path_pins
from repro import workloads as wl
from repro.core import locality as loc, simulator as sim
from repro.core.cluster import pair_worker_tiers, tier_of, worker_tiers
from repro.core.policy import PolicyConfig


# ----------------------------------------------------------- construction --

def test_topology_levels_and_tiers():
    flat = loc.Topology(16)                      # no grouping: K = 2
    assert flat.depth == 0 and flat.num_tiers == 2
    assert flat.num_racks == 1 and flat.min_rack_size == 16
    assert flat.ancestors.shape == (0, 16)

    rack = loc.Topology(24, 6)                   # the paper's default: K = 3
    assert rack.depth == 1 and rack.num_tiers == 3
    assert rack.num_racks == 4 and rack.servers_per_rack == 6
    np.testing.assert_array_equal(rack.rack_of, np.arange(24) // 6)
    assert rack == loc.Topology(24, (6,))        # legacy int == 1-level spec

    pods = loc.Topology(24, (4, 12))             # racks in pods: K = 4
    assert pods.depth == 2 and pods.num_tiers == 4
    assert pods.num_racks == 6
    np.testing.assert_array_equal(pods.ancestors[0], np.arange(24) // 4)
    np.testing.assert_array_equal(pods.ancestors[1], np.arange(24) // 12)


def test_topology_heterogeneous_groups():
    topo = loc.Topology(24, ((6, 6, 4, 4, 4),))
    assert topo.num_racks == 5 and topo.min_rack_size == 4
    np.testing.assert_array_equal(
        topo.rack_of, np.repeat([0, 1, 2, 3, 4], [6, 6, 4, 4, 4]))
    with pytest.raises(ValueError):
        topo.servers_per_rack  # no single uniform size
    # heterogeneous pods over heterogeneous racks, nesting on boundaries
    deep = loc.Topology(24, ((4, 4, 4, 6, 6), (12, 12)))
    assert deep.num_tiers == 4
    np.testing.assert_array_equal(deep.ancestors[1], np.arange(24) // 12)


def test_topology_validation_tiling_and_nesting():
    with pytest.raises(ValueError):
        loc.Topology(10, 4)                      # does not tile (old
    with pytest.raises(ValueError):              # ClusterSpec gap)
        loc.Topology(24, ((6, 6, 6),))           # sums to 18, not 24
    with pytest.raises(ValueError):
        loc.Topology(24, (4, 10))                # pods don't tile
    with pytest.raises(ValueError):
        loc.Topology(24, ((4, 8, 12), (8, 16)))  # pod cuts a rack in half
    with pytest.raises(ValueError):
        loc.Topology(24, (12, 12))               # level must coarsen
    # legacy host-side aliases survive the retirement of ClusterSpec
    topo = loc.Topology(8, 4)
    assert topo.num_workers == 8
    np.testing.assert_array_equal(topo.pod_of, topo.rack_of)


def test_rates_k_vector():
    r3 = loc.Rates()
    assert r3.values == (0.5, 0.45, 0.25) and r3.num_tiers == 3
    assert (r3.alpha, r3.beta, r3.gamma) == (0.5, 0.45, 0.25)
    r4 = loc.Rates((0.5, 0.45, 0.35, 0.25))
    assert r4.num_tiers == 4 and r4.gamma == 0.25
    assert np.asarray(r4.as_array()).shape == (4,)
    scaled = r4.scaled(0.5)
    assert scaled.values == pytest.approx((0.25, 0.225, 0.175, 0.125))
    with pytest.raises(ValueError):
        loc.Rates((0.5, 0.45, 0.45, 0.25))       # not strictly decreasing
    with pytest.raises(ValueError):
        loc.Rates((0.5,))                        # need >= 2 tiers
    with pytest.raises(ValueError):
        sim.SimConfig(topo=loc.Topology(24, (4, 12)),
                      true_rates=loc.Rates())    # 3 rates on a 4-tier topo


# -------------------------------------------------------------- tier seam --

def brute_tier(topo, task, server):
    if server in task:
        return 0
    anc = topo.ancestors
    for lvl in range(topo.depth):
        if anc[lvl, server] in {int(anc[lvl, s]) for s in task}:
            return lvl + 1
    return topo.num_tiers - 1


@pytest.mark.parametrize("spec", [(), (6,), (4, 12), ((6, 6, 4, 4, 4),)])
def test_server_tiers_matches_bruteforce(spec):
    topo = loc.Topology(24, spec)
    anc = jnp.asarray(topo.ancestors, jnp.int32)
    rng = np.random.default_rng(0)
    for _ in range(8):
        task = sorted(rng.choice(24, 3, replace=False).tolist())
        tiers = np.asarray(loc.server_tiers(jnp.asarray(task, jnp.int32),
                                            anc))
        want = [brute_tier(topo, task, s) for s in range(24)]
        np.testing.assert_array_equal(tiers, want)
        # one-hot masks cover every server exactly once
        masks = np.asarray(loc.tier_masks(jnp.asarray(task, jnp.int32), anc))
        assert masks.shape == (topo.num_tiers, 24)
        np.testing.assert_array_equal(masks.sum(axis=0), 1)
        # host-side helpers agree with the JAX seam
        np.testing.assert_array_equal(worker_tiers(topo, task), want)
        assert all(tier_of(topo, task, s) == want[s] for s in range(24))


def test_pair_tiers_matches_hierarchy():
    topo = loc.Topology(24, (4, 12))
    anc = jnp.asarray(topo.ancestors, jnp.int32)
    sid = jnp.arange(24)
    t = np.asarray(loc.pair_tiers(jnp.int32(0), sid, anc))
    assert t[0] == 0                       # self
    assert (t[1:4] == 1).all()             # same rack of 4
    assert (t[4:12] == 2).all()            # same pod of 12
    assert (t[12:] == 3).all()             # other pod
    np.testing.assert_array_equal(pair_worker_tiers(topo, 0), t)
    # pair rates select the matching tier's rate
    rates = jnp.asarray([0.5, 0.45, 0.35, 0.25])
    np.testing.assert_allclose(
        np.asarray(loc.pair_rate(jnp.int32(0), sid, anc, rates)),
        np.asarray(rates)[t])


# ---------------------------------------------- K-tier fluid capacity LP ---

def _fluid_lp_capacity_k(topo, rates, p_hot):
    """Brute-force fluid LP for the hot-rack pattern, K-generic and
    independent of the water-filling closed form: hot traffic may be served
    by the hot rack (rate r0) or by any tier-l pool (rate r_l); uniform
    traffic is served locally (r0) anywhere."""
    import scipy.optimize as sopt
    r = np.asarray(rates.values, float)
    tier = loc.hot_rack_tiers(topo, 0)
    pools = [(r[0], int((tier <= 1).sum()))]
    pools += [(r[lvl], int((tier == lvl).sum()))
              for lvl in range(2, r.size) if (tier == lvl).sum()]
    p = len(pools)
    nvar = 1 + 2 * p  # [Lam, hot per pool, uniform per pool]
    c = np.zeros(nvar)
    c[0] = -1.0
    a_eq = np.zeros((2, nvar))
    a_eq[0, 0], a_eq[0, 1:1 + p] = -p_hot, 1.0
    a_eq[1, 0], a_eq[1, 1 + p:] = -(1.0 - p_hot), 1.0
    a_ub = np.zeros((p, nvar))
    b_ub = []
    for j, (rj, nj) in enumerate(pools):
        a_ub[j, 1 + j] = 1.0 / rj
        a_ub[j, 1 + p + j] = 1.0 / r[0]
        b_ub.append(float(nj))
    res = sopt.linprog(c, A_ub=a_ub, b_ub=b_ub, A_eq=a_eq, b_eq=[0.0, 0.0],
                       bounds=[(0, None)] * nvar)
    assert res.success, res.message
    return -res.fun


@pytest.mark.parametrize("spec,rates,p_hot", [
    ((), (0.5, 0.25), 0.5),                              # K=2
    ((), (0.5, 0.25), 1.0),
    ((4,), (0.5, 0.45, 0.25), 0.5),                      # K=3 uniform
    ((6,), (0.5, 0.45, 0.25), 0.2),
    ((6,), (0.5, 0.45, 0.25), 0.9),
    (((6, 6, 4, 4, 4),), (0.5, 0.45, 0.25), 0.8),        # K=3 heterogeneous
    ((4, 12), (0.5, 0.45, 0.35, 0.25), 0.5),             # K=4 pods
    ((4, 12), (0.5, 0.45, 0.35, 0.25), 0.95),
    (((4, 4, 4, 6, 6), (12, 12)), (0.5, 0.45, 0.35, 0.25), 0.6),  # K=4 het.
])
def test_capacity_matches_bruteforce_lp_k_tier(spec, rates, p_hot):
    pytest.importorskip("scipy")
    topo = loc.Topology(24, spec)
    r = loc.Rates(rates)
    closed = loc.capacity_hot_rack(topo, r, p_hot)
    lp = _fluid_lp_capacity_k(topo, r, p_hot)
    assert closed == pytest.approx(lp, rel=1e-6)
    # sanity: bounded by the all-local optimum, monotone in p_hot
    assert closed <= topo.num_servers * r.values[0] + 1e-9
    hotter = loc.capacity_hot_rack(topo, r, min(p_hot + 0.05, 1.0))
    assert hotter <= closed + 1e-9


def test_capacity_k3_matches_seed_closed_form():
    """The K-generic water-filling reproduces the seed's 3-tier formula."""
    topo, rates = loc.Topology(24, 6), loc.Rates(0.5, 0.45, 0.25)
    m, mr, a, g = 24, 6, 0.5, 0.25
    for p in (0.1, 0.3, 0.5, 0.8, 1.0):
        want = m * a if p * m * a <= mr * a else \
            (m - mr + mr * a / g) / ((1.0 - p) / a + p / g)
        assert loc.capacity_hot_rack(topo, rates, p) == pytest.approx(want)


# ------------------------------------------------------- bitwise K=3 pins --

# Sample paths of the K=3 flat-rack default, recorded by
# tests/sample_path_pins.py (the recipes, and how to re-record them after
# a JAX upgrade, are there).
PINS = sample_path_pins.load()


@pytest.mark.parametrize("algo", sample_path_pins.POLICIES["k3_12x4"])
def test_k3_default_reproduces_prerefactor_sample_paths(algo):
    out = sample_path_pins.run_12x4(algo)
    for k, v in PINS["k3_12x4"][algo].items():
        assert out[k] == pytest.approx(v, rel=1e-6, abs=1e-9), (algo, k)


@pytest.mark.parametrize("algo", sample_path_pins.POLICIES["k3_24x6"])
def test_k3_paper_scale_pin(algo):
    out = sample_path_pins.run_24x6(algo)
    for k, v in PINS["k3_24x6"][algo].items():
        assert out[k] == pytest.approx(v, rel=1e-6, abs=1e-9), (algo, k)


# ------------------------------------------------------- mean_delay guard --

def test_mean_delay_guard_on_zero_and_negative_load():
    cfg = sim.SimConfig(topo=loc.Topology(12, 4), true_rates=loc.Rates(),
                        max_arrivals=8, horizon=200, warmup=50)
    est = sim.make_estimates(cfg, "network", 0.0, -1)
    out = sim.simulate("balanced_pandas", cfg, 0.0, est, seed=0)
    assert np.isnan(out["mean_delay"])       # used to divide to inf
    assert out["mean_n"] == 0.0
    with pytest.raises(ValueError):
        sim.simulate("balanced_pandas", cfg, -1.0, est, seed=0)
    with pytest.raises(ValueError):
        sim.sweep("balanced_pandas", cfg, np.array([-0.5], np.float32),
                  est[None], np.arange(1))


# ------------------------------------------------ K=4 simulator + kernels --

TOPO4 = loc.Topology(24, (4, 12))
RATES4 = loc.Rates((0.5, 0.45, 0.35, 0.25))
CFG4 = sim.SimConfig(topo=TOPO4, true_rates=RATES4, p_hot=0.5,
                     max_arrivals=16, horizon=800, warmup=200)
CAP4 = loc.capacity_hot_rack(TOPO4, RATES4, 0.5)


@pytest.mark.parametrize("policy", [
    "balanced_pandas", "jsq_maxweight", "priority", "fifo", "pandas_po2",
    PolicyConfig("blind_pandas", {"prior": RATES4.values}),
])
def test_k4_every_policy_simulates_and_sweeps(policy):
    est = sim.make_estimates(CFG4, "network", 0.1, -1)
    assert est.shape == (24, 4)
    out = sim.simulate(policy, CFG4, 0.7 * CAP4, est, seed=0)
    assert np.isfinite(out["mean_delay"])
    assert out["throughput"] == pytest.approx(0.7 * CAP4, rel=0.15)
    swept = sim.sweep(policy, CFG4, np.array([0.5, 0.7], np.float32) * CAP4,
                      est[None], np.arange(2))
    assert swept["mean_delay"].shape == (2, 1, 2)
    assert np.isfinite(swept["mean_delay"]).all()


@pytest.mark.parametrize("spec,rates", [
    ((), (0.5, 0.25)),
    ((4, 12), (0.5, 0.45, 0.35, 0.25)),
    (((6, 6, 4, 4, 4),), (0.5, 0.45, 0.25)),
])
def test_kernels_match_oracle_on_k_tier_ancestors(spec, rates):
    from repro.kernels import ops, ref
    topo = loc.Topology(24, spec)
    anc = jnp.asarray(topo.ancestors, jnp.int32)
    k = topo.num_tiers
    rng = np.random.default_rng(k)
    m, b = 24, 9
    qs = jnp.asarray(rng.integers(0, 20, (m, k)), jnp.int32)
    serving = jnp.asarray(rng.integers(0, k + 1, m), jnp.int32)
    er = jnp.asarray(np.tile(np.asarray(rates, np.float32), (m, 1))
                     * rng.uniform(0.8, 1.2, (m, k)), jnp.float32)
    tl = jnp.sort(jnp.asarray(
        np.stack([rng.choice(m, 3, replace=False) for _ in range(b)]),
        jnp.int32), axis=1)
    s1, t1, sc1 = ops.fleet_route(qs, serving, er, anc, tl)
    s2, t2, sc2 = ref.fleet_route(qs, serving, er, anc, tl)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_allclose(np.asarray(sc1), np.asarray(sc2), rtol=1e-6)

    q = jnp.asarray(rng.integers(0, 5, m), jnp.float32)
    ids = jnp.asarray(rng.choice(m, b, replace=False), jnp.int32)
    er2 = jnp.asarray(np.tile(np.asarray(rates, np.float32), (b, 1)),
                      jnp.float32)
    q1, s1 = ops.maxweight_claim(q, anc, ids, anc[:, ids], er2)
    q2, s2 = ref.maxweight_claim(q, anc, ids, anc[:, ids], er2)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


def test_k4_kernel_tier_derivation_spot_check():
    """The kernel's tier derivation weighs W/rate with the pod level in
    between rack and remote: a rack-mate (W/0.45) must beat a pod-mate
    (W/0.35) and a remote server (W/0.25) at equal queues, so only the tier
    rates discriminate."""
    from repro.kernels import ops
    anc = jnp.asarray(TOPO4.ancestors, jnp.int32)
    # task locals fill rack 0 (servers 0,2,3); server 1 is the rack-mate,
    # 5 sits in the same pod, 13 in the other pod; each of them holds one
    # local task (W = 1/0.5 = 2), every other server ten (W = 20)
    qs = jnp.zeros((24, 4), jnp.int32).at[:, 0].set(10)
    qs = qs.at[jnp.asarray([1, 5, 13]), 0].set(1)
    er = jnp.tile(RATES4.as_array()[None], (24, 1))
    tl = jnp.asarray([[0, 2, 3]], jnp.int32)
    server, tier, score = ops.fleet_route(qs, jnp.zeros((24,), jnp.int32),
                                          er, anc, tl)
    # scores: 1 -> 2/.45 = 4.44; 5 -> 2/.35 = 5.71; 13 -> 2/.25 = 8 (remote)
    assert int(server[0]) == 1 and int(tier[0]) == 1
    assert float(score[0]) == pytest.approx(2 / 0.45)


# ---------------------------------------------- per-rack arrival weights ---

def test_rack_weights_concentrate_arrivals():
    """p_hot=1 + one-hot rack_weights => every replica set lands in that
    rack (the weighted generalization of hot_rack)."""
    topo = loc.Topology(12, 4)
    rack_of = jnp.asarray(topo.rack_of, jnp.int32)
    w = jnp.asarray([0.0, 0.0, 1.0], jnp.float32)
    types = loc.sample_task_types_at(jax.random.PRNGKey(0), rack_of,
                                     p_hot=1.0, hot_rack=0, batch=128,
                                     rack_weights=w)
    t = np.asarray(types)
    assert (t >= 8).all() and (t < 12).all()   # all in rack 2
    # mixed weights spread hot traffic across the weighted racks
    w = jnp.asarray([0.5, 0.0, 0.5], jnp.float32)
    t = np.asarray(loc.sample_task_types_at(jax.random.PRNGKey(1), rack_of,
                                            1.0, 0, 256, rack_weights=w))
    racks = np.asarray(topo.rack_of)[t[:, 0]]
    assert set(racks.tolist()) == {0, 2}


def test_rack_weight_scenario_shifts_load_between_racks():
    scn = wl.Scenario("skew", (
        wl.Segment(start=0.0, rack_weights=(1.0, 0.0, 0.0)),
        wl.Segment(start=0.5, rack_weights=(0.0, 0.0, 1.0)),
    ))
    cfg = sim.SimConfig(topo=loc.Topology(12, 4), true_rates=loc.Rates(),
                        p_hot=0.5, max_arrivals=16, horizon=1000, warmup=200)
    cap = loc.capacity_hot_rack(cfg.topo, cfg.true_rates, cfg.p_hot)
    est = sim.make_estimates(cfg, "network", 0.0, -1)
    out = sim.simulate("balanced_pandas", cfg, 0.6 * cap, est, seed=0,
                       scenario=scn)
    assert np.isfinite(out["mean_delay"])
    assert out["throughput"] == pytest.approx(0.6 * cap, rel=0.2)
    # compiled schedule carries the (S, R) weight track; static has none
    sched = wl.compile_schedule(scn, cfg.topo, horizon=100, base_p_hot=0.5)
    assert sched.rack_weights is not None and sched.rack_weights.shape == (2, 3)
    assert wl.slot_knobs(sched, jnp.int32(75)).rack_weights[2] == 1.0
    static = wl.compile_schedule(wl.make_scenario("static"), cfg.topo, 100,
                                 0.5)
    assert static.rack_weights is None


def test_rack_weights_validation_and_resize():
    with pytest.raises(ValueError):
        wl.Segment(start=0.0, rack_weights=(0.0, 0.0))      # zero sum
    with pytest.raises(ValueError):
        wl.Segment(start=0.0, rack_weights=(-1.0, 2.0))     # negative
    # shorter vectors cycle over the compiled rack count (like hot_rack
    # wrapping mod num_racks)
    scn = wl.Scenario("s", (wl.Segment(start=0.0, rack_weights=(1.0, 0.0)),))
    sched = wl.compile_schedule(scn, loc.Topology(24, 4), 100, 0.5)
    np.testing.assert_allclose(np.asarray(sched.rack_weights[0]),
                               [1, 0, 1, 0, 1, 0])


def test_rack_weight_scenario_plays_back_on_host_consumers():
    """Regression: weights putting zero mass on rack 0 must not break the
    host projection — locality knobs are simulator-only and host_playback
    discards them instead of resizing them to its rack-less view."""
    scn = wl.Scenario("offrack0", (
        wl.Segment(start=0.0, rack_weights=(0.0, 0.0, 1.0)),))
    pb = wl.host_playback(scn, num_workers=4, horizon=100.0)
    assert pb.lam_mult_at(0.0) == 1.0
    from repro.data.pipeline import DataPipeline, PipelineConfig
    pipe = DataPipeline(PipelineConfig(num_hosts=8, hosts_per_pod=4,
                                       num_chunks=8, tokens_per_chunk=2048,
                                       seq_len=64, global_batch=1,
                                       scenario=scn))
    assert next(pipe)["tokens"].shape == (1, 64)


def test_k2_pipeline_counts_nonlocal_as_remote():
    """Regression: on a 2-tier fleet the only non-local tier IS remote —
    the legacy 3-way counters must not file it under 'rack'."""
    from repro.data.pipeline import DataPipeline, PipelineConfig
    pipe = DataPipeline(PipelineConfig(topology=loc.Topology(8),
                                       tier_rates=(1.0, 0.4),
                                       num_chunks=64,
                                       tokens_per_chunk=1024,
                                       seq_len=64, global_batch=2))
    for _ in range(4):
        next(pipe)
    assert pipe.metrics["rack"] == 0
    assert pipe.metrics["remote"] == int(pipe.metrics["tier_reads"][1])


def test_trace_rack_weights_roundtrip_and_compile(tmp_path):
    arr = np.array([10.0, 12.0, 8.0, 10.0])
    rw = np.array([[1.0, 0.0], [1.0, 0.0], [0.25, 0.75], [0.25, 0.75]])
    tr = wl.Trace("skewed", 60.0, arr, rack_weights=rw)
    p = tmp_path / "skewed.jsonl"
    wl.save_trace(tr, p)
    back = wl.load_trace(p)
    assert back == tr
    with pytest.raises(ValueError):
        wl.save_trace(tr, tmp_path / "skewed.csv")  # no CSV representation
    scn = wl.trace_to_scenario(tr, max_segments=8)
    # the weight change at interval 2 is an aux change-point: never merged
    assert len(scn.segments) >= 2
    assert scn.segments[0].rack_weights == (1.0, 0.0)
    assert scn.segments[-1].rack_weights == (0.25, 0.75)


# --------------------------------------------------- K=4 host-side stack ---

def test_k4_pipeline_end_to_end():
    from repro.data.pipeline import DataPipeline, PipelineConfig
    topo = loc.Topology(8, (2, 4))
    cfg = PipelineConfig(topology=topo, tier_rates=(1.0, 0.8, 0.6, 0.4),
                         num_chunks=32, tokens_per_chunk=4096, seq_len=128,
                         global_batch=2,
                         scenario=wl.Scenario("skew", (
                             wl.Segment(start=0.0, slow_servers={3: 0.5}),)))
    pipe = DataPipeline(cfg)
    batch = next(pipe)
    assert batch["tokens"].shape == (2, 128)
    assert pipe.metrics["tier_reads"].shape == (4,)
    assert pipe.metrics["tier_reads"].sum() == pipe.metrics["reads"]
    with pytest.raises(ValueError):
        DataPipeline(PipelineConfig(topology=topo))  # 3 rates on 4 tiers


def test_k4_engine_end_to_end():
    from repro.configs import registry
    from repro.models import params as P
    from repro.serve.engine import EngineConfig, Request, ServingEngine

    cfg = registry.get_smoke_config("chatglm3_6b")
    prm = P.init_params(cfg, jax.random.PRNGKey(0))
    topo = loc.Topology(4, (2, 4))  # racks of 2 in one pod of 4 + ... K=4
    ecfg = EngineConfig(topology=topo,
                        tier_rates=(1.0, 0.7, 0.55, 0.4),
                        slots_per_replica=2, max_len=64,
                        prefill_buckets=(16,))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=2, prefix_id=i % 3) for i in range(6)]
    eng = ServingEngine(cfg, prm, ecfg)
    assert eng.spec.num_tiers == 4
    assert set(eng.assign_tiers) == {0, 1, 2, 3}
    out = eng.run_until_drained(reqs, max_steps=200)
    assert all(r.finish_time > 0 for r in out)
    assert sum(eng.assign_tiers.values()) == len(reqs)
    with pytest.raises(ValueError):
        ServingEngine(cfg, prm, EngineConfig(topology=topo))  # 3-rate prior

"""Replica-placement subsystem: bitwise uniform pins on both substrates,
structural properties of every policy at K=2/3/4 (heterogeneous racks
included), the host placement map, the popularity rebalance step, the
placement-capacity LP, and end-to-end runs through the simulator, the
kernels, the serving engine and the data pipeline.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import locality as loc, robustness as rb, simulator as sim
from repro.placement import (PlacementConfig, available_placements,
                             make_placement, placement_capacity)
from repro.placement.policies import chunk_replicas, hrw_ranking

ALL_PLACEMENTS = ("uniform", "hdfs", "spread", "hot_aware")
TOPOS = {
    "k2": loc.Topology(24, ()),
    "k3": loc.Topology(24, 6),
    "k4": loc.Topology(24, (4, 12)),
    "k3het": loc.Topology(24, ((6, 6, 4, 4, 4),)),
}


def test_registry_surface():
    assert set(ALL_PLACEMENTS) <= set(available_placements())
    from repro.placement import placement_descriptions
    descs = placement_descriptions()
    assert all(descs[p] for p in ALL_PLACEMENTS)
    with pytest.raises(ValueError):
        make_placement("nope")
    p = make_placement(PlacementConfig("hot_aware", {"r_hot": 5}))
    assert p.r_hot == 5
    with pytest.raises(ValueError):
        make_placement(PlacementConfig("hot_aware", {"r_hot": 2}))
    with pytest.raises(ValueError):
        make_placement(PlacementConfig("hot_aware", {"hot_frac": 0.0}))


# -------------------------------------------------- bitwise uniform pins --

ALGOS = ("balanced_pandas", "jsq_maxweight", "priority", "fifo",
         "pandas_po2", "blind_pandas")


@pytest.mark.parametrize("algo", ALGOS)
def test_uniform_placement_is_bitwise_default_sim(algo):
    """placement="uniform" must reproduce the placement-less sample path
    EXACTLY for every policy (the placement-less path itself is pinned to
    the pre-refactor bits by tests/test_topology.py)."""
    from repro.core.policy import PolicyConfig
    cfg = sim.SimConfig(topo=loc.Topology(12, 4), true_rates=loc.Rates(),
                        p_hot=0.5, max_arrivals=16, horizon=800, warmup=200)
    policy = PolicyConfig("blind_pandas", {"prior": loc.Rates().values}) \
        if algo == "blind_pandas" else algo
    cap = loc.capacity_hot_rack(cfg.topo, cfg.true_rates, cfg.p_hot)
    est = sim.make_estimates(cfg, "network", 0.0, -1)
    base = sim.simulate(policy, cfg, 0.8 * cap, est, seed=3)
    unif = sim.simulate(policy, cfg, 0.8 * cap, est, seed=3,
                        placement="uniform")
    assert base == unif


def test_uniform_sampler_is_bitwise_classic_draw():
    topo = loc.Topology(24, 6)
    rack_of = jnp.asarray(topo.rack_of, jnp.int32)
    sampler = make_placement("uniform").build_sampler(topo)
    for seed in range(3):
        key = jax.random.PRNGKey(seed)
        want = loc.sample_task_types_at(key, rack_of, 0.5, jnp.int32(1), 64)
        got = sampler(key, 0.5, jnp.int32(1), 64)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    # the weighted path too
    w = jnp.asarray([0.2, 0.5, 0.3, 0.0], jnp.float32)
    key = jax.random.PRNGKey(7)
    want = loc.sample_task_types_at(key, rack_of, 0.5, jnp.int32(0), 64,
                                    rack_weights=w)
    got = sampler(key, 0.5, jnp.int32(0), 64, w)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_uniform_host_placement_is_bitwise_chunk_replicas():
    from repro.data import pipeline as pl
    topo = loc.Topology(16, 8)
    u = make_placement("uniform")
    for seed in (0, 1):
        for c in range(64):
            want = pl.chunk_replicas(c, 16, 3, seed)
            assert u.replicas(topo, c, 3, seed) == want
            assert chunk_replicas(c, 16, 3, seed) == want
            assert sorted(hrw_ranking(c, 16, seed)[:3]) == want


# --------------------------------------------------- sampler properties --

@pytest.mark.parametrize("topo_name", sorted(TOPOS))
@pytest.mark.parametrize("name", ALL_PLACEMENTS)
def test_sampler_valid_distinct_in_range(topo_name, name):
    topo = TOPOS[topo_name]
    sampler = make_placement(name).build_sampler(topo)
    for p_hot, hot_rack in ((0.0, 0), (0.6, 1), (1.0, topo.num_racks - 1)):
        t = np.asarray(sampler(jax.random.PRNGKey(hash((name, p_hot)) %
                                                  (2**31)),
                               jnp.float32(p_hot), jnp.int32(hot_rack), 256))
        assert t.shape == (256, loc.NUM_REPLICAS) and t.dtype == np.int32
        assert (t >= 0).all() and (t < topo.num_servers).all()
        assert (np.diff(t, axis=1) > 0).all()  # sorted AND distinct


@pytest.mark.parametrize("name", ALL_PLACEMENTS)
def test_sampler_honors_rack_weights(name):
    """With p_hot=1 and one-hot rack weights on rack 2: uniform
    concentrates every replica there, hdfs keeps primary+second there,
    spread keeps the primary there; hot_aware deliberately lets sets
    escape (the rebalanced extras) but must still over-represent it."""
    topo = loc.Topology(12, 4)
    sampler = make_placement(name).build_sampler(topo)
    w = jnp.asarray([0.0, 0.0, 1.0], jnp.float32)
    t = np.asarray(sampler(jax.random.PRNGKey(0), jnp.float32(1.0),
                           jnp.int32(0), 128, w))
    racks = np.asarray(topo.rack_of)[t]
    if name == "hot_aware":
        # uniform draws would put 1/3 of replicas in rack 2; the weighted
        # hot pool puts half its replica mass there
        assert (racks == 2).mean() > 0.45
    else:
        assert (racks == 2).any(axis=1).all()


def test_hdfs_sampler_structure_k3():
    """Hot hdfs types: primary+second in the hot rack, third off-rack —
    exactly two racks covered, one of them the hot one."""
    topo = loc.Topology(24, 6)
    sampler = make_placement("hdfs").build_sampler(topo)
    t = np.asarray(sampler(jax.random.PRNGKey(1), jnp.float32(1.0),
                           jnp.int32(2), 256))
    racks = np.asarray(topo.rack_of)[t]
    assert ((racks == 2).sum(axis=1) == 2).all()
    assert np.array([len(set(r)) for r in racks.tolist()] ==
                    np.full(256, 2)).all()
    # cold tasks: still exactly 2 replicas share the primary's rack
    t = np.asarray(sampler(jax.random.PRNGKey(2), jnp.float32(0.0),
                           jnp.int32(0), 256))
    racks = np.asarray(topo.rack_of)[t]
    assert all(len(set(r)) == 2 for r in racks.tolist())


def test_hdfs_sampler_degrades_to_uniform_when_inexpressible():
    """K=2 (no racks): hdfs falls back to the uniform draw bitwise."""
    topo = loc.Topology(24, ())
    h = make_placement("hdfs").build_sampler(topo)
    u = make_placement("uniform").build_sampler(topo)
    key = jax.random.PRNGKey(0)
    np.testing.assert_array_equal(
        np.asarray(h(key, jnp.float32(0.5), jnp.int32(0), 64)),
        np.asarray(u(key, jnp.float32(0.5), jnp.int32(0), 64)))


def test_spread_sampler_anti_affinity():
    # K=3: three distinct racks
    topo = loc.Topology(24, 6)
    s = make_placement("spread").build_sampler(topo)
    t = np.asarray(s(jax.random.PRNGKey(0), jnp.float32(0.4), jnp.int32(0),
                     256))
    racks = np.asarray(topo.rack_of)[t]
    assert all(len(set(r)) == 3 for r in racks.tolist())
    # K=4 with 2 pods: replicas still land in 3 distinct racks, and the
    # second pick crosses pods whenever it can (max distance first)
    topo4 = loc.Topology(24, (4, 12))
    s4 = make_placement("spread").build_sampler(topo4)
    t = np.asarray(s4(jax.random.PRNGKey(1), jnp.float32(0.4), jnp.int32(0),
                      256))
    racks = np.asarray(topo4.rack_of)[t]
    pods = np.asarray(topo4.ancestors[1])[t]
    assert all(len(set(r)) == 3 for r in racks.tolist())
    assert all(len(set(p)) == 2 for p in pods.tolist())  # both pods covered


def test_hot_aware_sampler_widens_hot_pool():
    """r_hot=3 keeps every hot replica in the hot rack; r_hot=6 leaks some
    replicas off-rack (the rebalanced extras)."""
    topo = loc.Topology(12, 4)
    tight = make_placement(PlacementConfig("hot_aware", {"r_hot": 3}))
    wide = make_placement(PlacementConfig("hot_aware", {"r_hot": 6}))
    kt = jax.random.PRNGKey(3)
    t_tight = np.asarray(tight.build_sampler(topo)(
        kt, jnp.float32(1.0), jnp.int32(1), 256))
    t_wide = np.asarray(wide.build_sampler(topo)(
        kt, jnp.float32(1.0), jnp.int32(1), 256))
    racks_t = np.asarray(topo.rack_of)[t_tight]
    racks_w = np.asarray(topo.rack_of)[t_wide]
    assert (racks_t == 1).all()
    assert (racks_w != 1).any() and (racks_w == 1).any()


# ------------------------------------------------------ host projections --

@pytest.mark.parametrize("topo_name", sorted(TOPOS))
@pytest.mark.parametrize("name", ALL_PLACEMENTS)
def test_host_replicas_valid_and_deterministic(topo_name, name):
    topo = TOPOS[topo_name]
    p = make_placement(name)
    for c in range(32):
        locs = p.replicas(topo, c, 3, seed=5)
        assert locs == sorted(set(locs))
        assert all(0 <= h < topo.num_servers for h in locs)
        assert len(locs) >= 3
        assert locs == make_placement(name).replicas(topo, c, 3, seed=5)


def test_hdfs_host_structure():
    topo = loc.Topology(24, 6)
    rack = np.asarray(topo.rack_of)
    h = make_placement("hdfs")
    for c in range(64):
        locs = h.replicas(topo, c, 3, 0)
        prim = hrw_ranking(c, 24, 0)[0]
        assert prim in locs
        assert len(set(rack[locs].tolist())) == 2  # two fault domains
        assert (rack[locs] == rack[prim]).sum() == 2


def test_spread_host_structure():
    topo = loc.Topology(24, 6)
    rack = np.asarray(topo.rack_of)
    s = make_placement("spread")
    for c in range(64):
        locs = s.replicas(topo, c, 3, 0)
        assert len(set(rack[locs].tolist())) == 3
    # more replicas than racks: fills by rank after racks run out
    locs = s.replicas(loc.Topology(8, 4), 0, 3, 0)
    assert len(locs) == 3 and len(set(locs)) == 3


def test_placement_map_padding_and_mask():
    topo = loc.Topology(24, 6)
    ha = make_placement(PlacementConfig("hot_aware",
                                        {"r_hot": 6, "hot_frac": 0.25}))
    ids, mask = ha.placement_map(topo, 64, 3, seed=0)
    assert ids.shape == (64, 6) and mask.shape == (64, 6)
    assert ids.dtype == np.int32 and mask.dtype == bool
    sizes = mask.sum(axis=1)
    assert set(sizes.tolist()) <= {3, 6} and (sizes > 3).any()
    # mask prefix-true; pad slots replicate a valid host id
    assert (np.diff(mask.astype(int), axis=1) <= 0).all()
    assert (ids >= 0).all() and (ids < 24).all()
    for c in range(64):
        assert ids[c, ~mask[c]].tolist() == [ids[c, 0]] * int((~mask[c]).sum())
    # uniform map is exactly the classic assignment, all-true mask
    ids_u, mask_u = make_placement("uniform").placement_map(topo, 16, 3, 0)
    assert mask_u.all()
    for c in range(16):
        assert ids_u[c].tolist() == chunk_replicas(c, 24, 3, 0)


def test_hot_aware_rebalance_is_deterministic_and_reacts_to_counts():
    topo = loc.Topology(24, 6)
    ha = make_placement(PlacementConfig("hot_aware",
                                        {"r_hot": 6, "hot_frac": 0.25}))
    # chunk 7 becomes the single observed hotspot
    for _ in range(10):
        ha.note_read(7)
    for c in (1, 2, 3):
        ha.note_read(c)
    changed = ha.rebalance()
    assert changed >= 1
    assert len(ha.replicas(topo, 7, 3, 0)) == 6    # hot: widened
    assert len(ha.replicas(topo, 2, 3, 0)) == 3    # cold: base
    # replaying the same history gives the same hot set (determinism)
    hb = make_placement(PlacementConfig("hot_aware",
                                        {"r_hot": 6, "hot_frac": 0.25}))
    for _ in range(10):
        hb.note_read(7)
    for c in (1, 2, 3):
        hb.note_read(c)
    hb.rebalance()
    for c in range(16):
        assert ha.replicas(topo, c, 3, 0) == hb.replicas(topo, c, 3, 0)
    # a hotspot shift moves the wide replica set on the next rebalance
    for _ in range(50):
        ha.note_read(11)
    assert ha.rebalance() >= 1
    assert len(ha.replicas(topo, 11, 3, 0)) == 6


# ------------------------------------------------------------- capacity --

def test_placement_capacity_uniform_matches_water_filling():
    pytest.importorskip("scipy")
    topo, rates = loc.Topology(24, 6), loc.Rates()
    closed = loc.capacity_hot_rack(topo, rates, 0.5)
    mc = placement_capacity(topo, rates, 0.5, "uniform", n_samples=4000)
    assert mc == pytest.approx(closed, rel=0.05)  # Monte-Carlo p_hot noise
    # rack-aware placements un-confine hot traffic: capacity can only grow
    for name in ("hdfs", "spread"):
        assert placement_capacity(topo, rates, 0.5, name,
                                  n_samples=1000) >= closed - 1e-6


# ------------------------------------------------- end-to-end: all layers --

NONDEFAULT = ("hdfs", "spread", "hot_aware")


@pytest.mark.parametrize("topo,rates", [
    (loc.Topology(12, 4), loc.Rates()),
    (loc.Topology(24, (4, 12)), loc.Rates((0.5, 0.45, 0.35, 0.25))),
])
@pytest.mark.parametrize("name", NONDEFAULT)
def test_placement_runs_through_simulate_and_sweep(topo, rates, name):
    cfg = sim.SimConfig(topo=topo, true_rates=rates, p_hot=0.5,
                        max_arrivals=16, horizon=600, warmup=150)
    cap = loc.capacity_hot_rack(topo, rates, cfg.p_hot)
    est = sim.make_estimates(cfg, "network", 0.0, -1)
    out = sim.simulate("balanced_pandas", cfg, 0.6 * cap, est, seed=0,
                       placement=name)
    assert np.isfinite(out["mean_delay"])
    assert out["throughput"] == pytest.approx(0.6 * cap, rel=0.2)
    swept = sim.sweep("jsq_maxweight", cfg,
                      np.array([0.4, 0.6], np.float32) * cap, est[None],
                      np.arange(2), placement=name)
    assert swept["mean_delay"].shape == (2, 1, 2)
    assert np.isfinite(swept["mean_delay"]).all()


@pytest.mark.parametrize("name", NONDEFAULT)
def test_placement_types_feed_both_kernels(name):
    """The sampled task_locals drive fleet_route and maxweight_claim
    unchanged (kernel vs oracle on placement-sampled types)."""
    from repro.kernels import ops, ref
    topo = loc.Topology(24, (4, 12))
    anc = jnp.asarray(topo.ancestors, jnp.int32)
    k = topo.num_tiers
    tl = jnp.asarray(make_placement(name).build_sampler(topo)(
        jax.random.PRNGKey(0), jnp.float32(0.5), jnp.int32(0), 9), jnp.int32)
    rng = np.random.default_rng(3)
    m, b = 24, 9
    qs = jnp.asarray(rng.integers(0, 20, (m, k)), jnp.int32)
    serving = jnp.asarray(rng.integers(0, k + 1, m), jnp.int32)
    er = jnp.asarray(np.tile([0.5, 0.45, 0.35, 0.25], (m, 1)), jnp.float32)
    s1, t1, sc1 = ops.fleet_route(qs, serving, er, anc, tl)
    s2, t2, sc2 = ref.fleet_route(qs, serving, er, anc, tl)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    q = jnp.asarray(rng.integers(0, 5, m), jnp.float32)
    ids = jnp.asarray(rng.choice(m, b, replace=False), jnp.int32)
    er2 = jnp.asarray(np.tile([0.5, 0.45, 0.35, 0.25], (b, 1)), jnp.float32)
    q1, sv1 = ops.maxweight_claim(q, anc, ids, anc[:, ids], er2)
    q2, sv2 = ref.maxweight_claim(q, anc, ids, anc[:, ids], er2)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))


@pytest.mark.parametrize("name", NONDEFAULT)
def test_placement_runs_through_pipeline(name):
    from repro.data.pipeline import DataPipeline, PipelineConfig
    for topo, rates in ((loc.Topology(16, 8), (1.0, 0.8, 0.4)),
                        (loc.Topology(8, (2, 4)), (1.0, 0.8, 0.6, 0.4))):
        pipe = DataPipeline(PipelineConfig(
            topology=topo, tier_rates=rates, num_chunks=32,
            tokens_per_chunk=2048, seq_len=64, global_batch=2,
            placement=name, rebalance_every=4))
        for _ in range(4):
            batch = next(pipe)
        assert batch["tokens"].shape == (2, 64)
        assert pipe.metrics["tier_reads"].sum() == pipe.metrics["reads"]


@pytest.mark.parametrize("name", NONDEFAULT)
def test_placement_runs_through_engine(name):
    from repro.configs import registry
    from repro.models import params as P
    from repro.serve.engine import EngineConfig, Request, ServingEngine

    cfg = registry.get_smoke_config("chatglm3_6b")
    prm = P.init_params(cfg, jax.random.PRNGKey(0))
    for topo, rates in ((loc.Topology(4, 2), (1.0, 0.7, 0.4)),
                        (loc.Topology(4, (2, 4)), (1.0, 0.7, 0.55, 0.4))):
        ecfg = EngineConfig(topology=topo, tier_rates=rates,
                            slots_per_replica=2, max_len=64,
                            prefill_buckets=(16,), placement=name)
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i, prompt=rng.integers(
                    0, cfg.vocab_size, 8).astype(np.int32),
                        max_new_tokens=2, prefix_id=i % 3) for i in range(4)]
        eng = ServingEngine(cfg, prm, ecfg)
        out = eng.run_until_drained(reqs, max_steps=200)
        assert all(r.finish_time > 0 for r in out)
        assert sum(eng.assign_tiers.values()) == len(reqs)


def test_engine_uniform_placement_is_bitwise_old_locs():
    """The engine's default placement reproduces the retired
    chunk_replicas call for every prefix."""
    from repro.data.pipeline import chunk_replicas as old
    from repro.serve.engine import EngineConfig
    topo = loc.Topology(4, 2)
    p = make_placement(EngineConfig().placement)
    for prefix in range(32):
        assert p.replicas(topo, prefix, 3, 0) == old(prefix, 4, 3, 0)


# -------------------------------------------------------- study driver ---

def test_placement_study_shapes_and_stability():
    cfg = rb.StudyConfig(
        sim=sim.SimConfig(topo=loc.Topology(12, 4), true_rates=loc.Rates(),
                          max_arrivals=16, horizon=1000, warmup=250),
        seeds=(0,))
    study = rb.placement_study(cfg, placements=("uniform", "hdfs"),
                               policies=("balanced_pandas",),
                               scenarios=("hot_shift",), load=0.6,
                               capacity_samples=300)
    assert study["placements"] == ("uniform", "hdfs")
    lam = study["load"] * study["capacity_uniform"]
    for plc in study["placements"]:
        d = study["delay"][plc]["hot_shift"]["balanced_pandas"]
        assert d.shape == (1,) and np.isfinite(d).all()
        thr = float(study["throughput"][plc]["hot_shift"]
                    ["balanced_pandas"].mean())
        assert thr > 0.85 * lam
    table = rb.summarize_placement(study)
    assert "hot_shift" in table and "hdfs" in table


# ----------------------------------------------- checkpoint / rebalance ---

def test_pipeline_checkpoint_restores_placement_state():
    """A restored pipeline must place and rebalance exactly like the
    uninterrupted run (the popularity state and the reads counter are part
    of state_dict; regression: they used to be dropped)."""
    from repro.data.pipeline import DataPipeline, PipelineConfig

    def make():
        return DataPipeline(PipelineConfig(
            num_hosts=16, hosts_per_pod=8, num_chunks=24,
            tokens_per_chunk=512, seq_len=32, global_batch=2,
            placement=PlacementConfig("hot_aware", {"hot_frac": 0.25}),
            rebalance_every=4))

    straight = make()
    for _ in range(8):
        next(straight)

    first = make()
    for _ in range(4):
        next(first)
    saved = first.state_dict()
    resumed = make()
    resumed.load_state_dict(saved)
    for _ in range(4):
        next(resumed)

    assert resumed.metrics["reads"] == straight.metrics["reads"]
    assert resumed.placement.state_dict() == straight.placement.state_dict()
    topo = straight.spec
    for c in range(24):
        assert resumed.placement.replicas(topo, c, 3, 0) == \
            straight.placement.replicas(topo, c, 3, 0)
    # stateless placements refuse foreign state, accept their own
    u = make_placement("uniform")
    assert u.state_dict() == {}
    u.load_state_dict({})
    with pytest.raises(ValueError):
        u.load_state_dict({"counts": [1]})


def test_engine_rebalance_cadence():
    from repro.configs import registry
    from repro.models import params as P
    from repro.serve.engine import EngineConfig, Request, ServingEngine

    cfg = registry.get_smoke_config("chatglm3_6b")
    prm = P.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(num_replicas=4, replicas_per_pod=2,
                        slots_per_replica=2, max_len=64,
                        prefill_buckets=(16,),
                        placement=PlacementConfig("hot_aware",
                                                  {"hot_frac": 0.5}),
                        rebalance_every=2)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(
                0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=1, prefix_id=i % 2) for i in range(4)]
    eng = ServingEngine(cfg, prm, ecfg)
    eng.run_until_drained(reqs, max_steps=100)
    assert eng.routed == 4
    assert eng.placement._hot is not None  # rebalance actually ran
    with pytest.raises(ValueError):
        ServingEngine(cfg, prm,
                      EngineConfig(num_replicas=4, replicas_per_pod=2,
                                   rebalance_every=-1))

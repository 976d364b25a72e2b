"""Replication-lifecycle subsystem: registry surface, the scenario failure
track (down_servers / down_racks -> alive masks on both substrates),
bitwise `fixed` pins for every policy, repair / popularity properties,
the migration cost model, the host-side mirror (engine + pipeline),
kernel-vs-oracle on post-migration placements, the study driver, and the
two satellite regressions (scipy-optional placement import, hot_aware
checkpoint round-trip).
"""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import locality as loc, robustness as rb, simulator as sim
from repro.replication import (MigrationModel, ReplicationConfig,
                               available_replications, make_replication,
                               replication_descriptions)
from repro.workloads import (Segment, compile_schedule, host_playback,
                             make_scenario)

ALL_REPLICATIONS = ("fixed", "popularity", "repair")
ALGOS = ("balanced_pandas", "jsq_maxweight", "priority", "fifo",
         "pandas_po2", "blind_pandas")


def small_cfg(**kw):
    base = dict(topo=loc.Topology(12, 4), true_rates=loc.Rates(),
                p_hot=0.5, max_arrivals=16, horizon=800, warmup=200)
    base.update(kw)
    return sim.SimConfig(**base)


def _policy(algo):
    from repro.core.policy import PolicyConfig
    return PolicyConfig("blind_pandas", {"prior": loc.Rates().values}) \
        if algo == "blind_pandas" else algo


# ------------------------------------------------------------- registry --

def test_registry_surface():
    assert set(ALL_REPLICATIONS) <= set(available_replications())
    descs = replication_descriptions()
    assert all(descs[r] for r in ALL_REPLICATIONS)
    with pytest.raises(ValueError):
        make_replication("nope")
    # None -> fixed (the do-nothing controller)
    ctrl = make_replication(None)
    assert ctrl.name == "fixed" and ctrl.is_static
    assert not make_replication("repair").is_static
    c = make_replication(ReplicationConfig("popularity", {"r_hot": 6}))
    assert c.r_hot == 6 and c.max_target(3) == 6
    with pytest.raises(ValueError):
        make_replication(ReplicationConfig("popularity", {"r_hot": 1,
                                                          "r_cold": 3}))
    with pytest.raises(ValueError):
        make_replication(ReplicationConfig("popularity", {"hot_frac": 0.0}))
    with pytest.raises(ValueError):
        make_replication(ReplicationConfig("repair", {"lanes": 0}))
    # passing an instance through is identity; options then make no sense
    assert make_replication(ctrl) is ctrl
    with pytest.raises(ValueError):
        make_replication(ctrl, lanes=2)


def test_migration_model_cost_table():
    m = MigrationModel()  # chunk_size 8.0
    rates = np.asarray(loc.Rates().values)  # (0.5, 0.45, 0.25)
    tab = m.cost_table(rates)
    np.testing.assert_array_equal(tab, [16.0, 18.0, 32.0])
    assert m.cost(rates, 2) == 32.0
    with pytest.raises(ValueError):
        MigrationModel(chunk_size=0.0)
    with pytest.raises(ValueError):
        MigrationModel(contention=0.0)


# ------------------------------------------- scenario failure track ------

def test_segment_failure_fields_validate():
    s = Segment(start=0.0, down_servers=(np.int64(3), 1))
    assert s.down_servers == (3, 1)  # coerced to plain ints
    with pytest.raises(ValueError):
        Segment(start=0.0, down_servers=(-1,))
    with pytest.raises(ValueError):
        Segment(start=0.0, down_racks=(1.5,))


def test_failure_scenarios_registered_and_compile():
    from repro.workloads import available_scenarios
    assert {"server_loss", "rack_loss"} <= set(available_scenarios())
    topo = loc.Topology(12, 4)
    for name in ("server_loss", "rack_loss"):
        sched = compile_schedule(make_scenario(name), topo, 300, 0.5)
        assert sched.alive is not None
        alive = np.asarray(sched.alive)
        assert alive.shape == (3, 12)
        assert alive[0].all() and alive[2].all()  # healthy bookends
        assert not alive[1].all()                 # the loss window
    # static scenario carries no failure track at all
    assert compile_schedule(make_scenario(None), topo, 300, 0.5).alive is None


def test_rack_loss_needs_rack_structure():
    from repro.workloads.scenario import _dense_segments
    from repro.workloads import Scenario
    scn = make_scenario("rack_loss")
    with pytest.raises(ValueError):
        _dense_segments(scn, 12, 4, 0.5, num_tiers=3, rack_of=None)
    # killing every server is a scenario bug, not a simulation outcome
    scn_all = Scenario("suicide",
                       (Segment(start=0.0, down_servers=tuple(range(4))),))
    with pytest.raises(ValueError):
        _dense_segments(scn_all, 4, 4, 0.5, num_tiers=3,
                        rack_of=np.zeros(4, np.int64))


def test_host_playback_alive_mask():
    topo = loc.Topology(8, 4)
    pb = host_playback(make_scenario("server_loss"), 8, 100.0,
                       num_tiers=3, rack_of=np.asarray(topo.rack_of))
    assert pb.alive is not None
    t_mid = 100.0 * 0.5  # inside the default loss window [0.35, 0.65)
    assert not pb.alive_at(t_mid, 0)
    assert pb.alive_at(5.0, 0) and pb.alive_at(95.0, 0)
    mask = pb.alive_mask_at(t_mid)
    assert mask.shape == (8,) and not mask.all() and mask.any()
    # static playback: everything alive, everywhere
    pb0 = host_playback(make_scenario(None), 8, 100.0, num_tiers=3)
    assert pb0.alive is None and pb0.alive_mask_at(50.0).all()


# ------------------------------------------------ bitwise fixed pins -----

@pytest.mark.parametrize("algo", ALGOS)
def test_fixed_replication_is_bitwise_default_sim(algo):
    """replication="fixed" under a static scenario must reproduce the
    replication-less sample path EXACTLY for every policy (that path is
    itself pinned to the pre-refactor bits by tests/test_topology.py)."""
    cfg = small_cfg()
    cap = loc.capacity_hot_rack(cfg.topo, cfg.true_rates, cfg.p_hot)
    est = sim.make_estimates(cfg, "network", 0.0, -1)
    base = sim.simulate(_policy(algo), cfg, 0.8 * cap, est, seed=3)
    pinned = sim.simulate(_policy(algo), cfg, 0.8 * cap, est, seed=3,
                          replication="fixed")
    assert base == pinned
    # and the passthrough adds no metric keys
    assert set(pinned) == set(base)


# ---------------------------------------------- lifecycle properties -----

def test_repair_restores_replication_factor():
    cfg = small_cfg()
    est = sim.make_estimates(cfg, "network", 0.0, -1)
    fixed = sim.simulate("balanced_pandas", cfg, 3.0, est, seed=0,
                         scenario="server_loss", replication="fixed")
    repair = sim.simulate("balanced_pandas", cfg, 3.0, est, seed=0,
                          scenario="server_loss", replication="repair")
    # the loss window wipes replicas; only the repair controller rebuilds
    assert fixed["final_replication"] < 3.0
    assert repair["final_replication"] == pytest.approx(3.0)
    assert repair["repair_moves"] > 0 and fixed["repair_moves"] == 0


def test_repair_respects_lane_cap():
    cfg = small_cfg()
    est = sim.make_estimates(cfg, "network", 0.0, -1)
    out = sim.simulate("balanced_pandas", cfg, 3.0, est, seed=0,
                       scenario="server_loss",
                       replication=ReplicationConfig("repair",
                                                     {"lanes": 2}))
    assert 0 < out["max_concurrent_moves"] <= 2
    wide = sim.simulate("balanced_pandas", cfg, 3.0, est, seed=0,
                        scenario="server_loss",
                        replication=ReplicationConfig("repair",
                                                      {"lanes": 6}))
    assert wide["max_concurrent_moves"] <= 6
    # a tighter repair-bandwidth cap cannot finish repairs sooner
    assert out["repair_moves"] <= wide["repair_moves"] + 1


def test_popularity_widens_hot_chunks():
    cfg = small_cfg()
    est = sim.make_estimates(cfg, "network", 0.0, -1)
    out = sim.simulate("balanced_pandas", cfg, 3.0, est, seed=0,
                       replication=ReplicationConfig(
                           "popularity", {"r_hot": 5, "r_cold": 3}))
    # hot chunks grow toward 5, cold stay at 3: mean strictly above 3
    assert out["final_replication"] > 3.0
    assert out["repair_moves"] > 0


def test_rack_loss_can_lose_data_without_cross_rack_replicas():
    """A whole-rack loss under the `spread` placement (replicas scattered
    across racks) must lose nothing; the availability/data-loss metrics
    separate the two."""
    cfg = small_cfg()
    est = sim.make_estimates(cfg, "network", 0.0, -1)
    out = sim.simulate("balanced_pandas", cfg, 3.0, est, seed=0,
                       scenario="rack_loss", placement="spread",
                       replication="repair")
    assert out["data_loss_frac"] == 0.0
    assert out["availability"] == pytest.approx(1.0)


def test_sweep_carries_replication_metrics():
    cfg = small_cfg(horizon=400, warmup=100)
    est = sim.make_estimates(cfg, "network", 0.0, -1)[None]
    res = sim.sweep("balanced_pandas", cfg, np.asarray([3.0, 4.0]),
                    est, np.asarray([0, 1]), scenario="server_loss",
                    replication="repair")
    for key in ("availability", "data_loss_frac", "mean_replication",
                "final_replication", "repair_moves"):
        assert res[key].shape == (2, 1, 2), key
    assert (res["availability"] <= 1.0).all()
    assert (res["repair_moves"] >= 0).all()


# ------------------------------------------------------- host mirror -----

def _host_ctrl(name="repair", m=8, chunks=16, **opts):
    topo = loc.Topology(m, m // 2)
    from repro.placement import make_placement
    ctrl = make_replication(ReplicationConfig(name, opts) if opts else name)
    host = ctrl.build_host(topo, make_placement(None), chunks, 3, 0,
                           np.asarray(loc.Rates().values))
    return host, topo


def test_host_repair_after_kill():
    host, topo = _host_ctrl()
    assert host.mean_replication() == pytest.approx(3.0)
    alive = np.ones(topo.num_servers, bool)
    alive[:3] = False
    host.observe(0.0, alive)
    wiped = host.mean_replication()
    assert wiped < 3.0
    # chunks whose whole replica set died are gone for good — repair can
    # only restore the rest to the target factor
    lost = sum(not host.replicas_for(c) for c in range(host.num_chunks))
    for t in range(1, 400):
        host.observe(float(t), alive)
    want = 3.0 * (host.num_chunks - lost) / host.num_chunks
    assert host.mean_replication() == pytest.approx(want)
    assert host.moves > 0
    assert host.data_loss_frac() == pytest.approx(lost / host.num_chunks)
    # repaired copies live only on surviving hosts
    for c in range(host.num_chunks):
        assert all(alive[h] for h in host.replicas_for(c))


def test_host_replicas_for_and_lost_reads():
    host, topo = _host_ctrl(name="fixed", m=4, chunks=4)
    locs = host.replicas_for(1)
    assert locs == sorted(locs) and len(locs) == 3
    host.observe(0.0, np.zeros(topo.num_servers, bool) | False)
    # all hosts dead -> no live replica, read is lost
    assert host.replicas_for(1) == []
    assert host.lost_reads == 1


def test_host_state_round_trip_is_json_safe():
    host, topo = _host_ctrl()
    alive = np.ones(topo.num_servers, bool)
    alive[0] = False
    host.observe(0.0, alive)
    host.note_read(3)
    state = json.loads(json.dumps(host.state_dict()))  # the manifest path
    host2, _ = _host_ctrl()
    host2.load_state_dict(state)
    assert host2.state_dict() == host.state_dict()
    # lanes survive: advancing both produces identical placements
    for t in range(1, 200):
        host.observe(float(t), alive)
        host2.observe(float(t), alive)
    np.testing.assert_array_equal(host.mask, host2.mask)


def test_post_migration_placements_feed_both_kernels():
    """Post-repair replica rows drive fleet_route / maxweight_claim
    unchanged (kernel vs oracle on lifecycle-produced task_locals)."""
    from repro.kernels import ops, ref
    from repro.placement import make_placement
    topo = loc.Topology(24, (4, 12))
    ctrl = make_replication("repair")
    host = ctrl.build_host(topo, make_placement(None), 16, 3, 0,
                           np.asarray([0.5, 0.45, 0.35, 0.25]))
    alive = np.ones(24, bool)
    alive[[0, 5, 7]] = False
    for t in range(200):
        host.observe(float(t), alive)
    rows = [host.replicas_for(c) for c in range(9)]
    assert all(len(r) == 3 for r in rows)
    tl = jnp.asarray(rows, jnp.int32)
    anc = jnp.asarray(topo.ancestors, jnp.int32)
    rng = np.random.default_rng(3)
    m, b = 24, 9
    qs = jnp.asarray(rng.integers(0, 20, (m, 4)), jnp.int32)
    serving = jnp.asarray(rng.integers(0, 5, m), jnp.int32)
    er = jnp.asarray(np.tile([0.5, 0.45, 0.35, 0.25], (m, 1)), jnp.float32)
    s1, t1, _ = ops.fleet_route(qs, serving, er, anc, tl)
    s2, t2, _ = ref.fleet_route(qs, serving, er, anc, tl)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    q = jnp.asarray(rng.integers(0, 5, m), jnp.float32)
    ids = jnp.asarray(rng.choice(m, b, replace=False), jnp.int32)
    er2 = jnp.asarray(np.tile([0.5, 0.45, 0.35, 0.25], (b, 1)), jnp.float32)
    q1, sv1 = ops.maxweight_claim(q, anc, ids, anc[:, ids], er2)
    q2, sv2 = ref.maxweight_claim(q, anc, ids, anc[:, ids], er2)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))


# --------------------------------------------------- pipeline / engine ---

def test_pipeline_replication_gate_and_failure_run():
    from repro.data.pipeline import DataPipeline, PipelineConfig
    small = dict(num_hosts=8, hosts_per_pod=4, num_chunks=32, seq_len=64,
                 global_batch=2, seed=0)
    # static + fixed: machinery compiled out entirely (the bitwise gate)
    assert DataPipeline(PipelineConfig(**small)).replication_ctl is None
    assert DataPipeline(PipelineConfig(
        **small, replication_policy="fixed")).replication_ctl is None
    # a failure scenario engages the machinery even for fixed
    p_fix = DataPipeline(PipelineConfig(**small, scenario="server_loss"))
    assert p_fix.replication_ctl is not None
    p = DataPipeline(PipelineConfig(**small, scenario="server_loss",
                                    replication_policy="repair"))
    for _ in range(12):
        next(p)
    assert p.metrics["reads"] > 0
    assert p.replication_ctl.mean_replication() > 0


def test_pipeline_checkpoint_restores_replication_state():
    from repro.data.pipeline import DataPipeline, PipelineConfig
    from repro.train.trainer import _np_to_list
    kw = dict(num_hosts=8, hosts_per_pod=4, num_chunks=32, seq_len=64,
              global_batch=2, seed=0, scenario="server_loss",
              replication_policy="repair")
    p1 = DataPipeline(PipelineConfig(**kw))
    for _ in range(6):
        next(p1)
    # exactly what the trainer writes into the checkpoint manifest
    state = json.loads(json.dumps(_np_to_list(p1.state_dict())))
    p2 = DataPipeline(PipelineConfig(**kw))
    p2.load_state_dict(state)
    assert (p2.replication_ctl.state_dict()
            == p1.replication_ctl.state_dict())
    b1, b2 = next(p1), next(p2)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # a manifest carrying lifecycle state needs a configured controller
    p3 = DataPipeline(PipelineConfig(num_hosts=8, hosts_per_pod=4,
                                     num_chunks=32, seq_len=64,
                                     global_batch=2, seed=0))
    with pytest.raises(ValueError):
        p3.load_state_dict(state)


def test_engine_replication_gate_and_repair():
    from repro.configs import registry
    from repro.models import params as P
    from repro.serve.engine import EngineConfig, Request, ServingEngine
    CFG = registry.get_smoke_config("chatglm3_6b")
    PARAMS = P.init_params(CFG, jax.random.PRNGKey(0))
    base = dict(num_replicas=4, replicas_per_pod=2, slots_per_replica=2,
                max_len=64, prefill_buckets=(16,))
    # static + fixed: no lifecycle object at all (bitwise by construction)
    assert ServingEngine(CFG, PARAMS, EngineConfig(
        **base, replication="fixed")).replication is None
    eng = ServingEngine(CFG, PARAMS, EngineConfig(
        **base, scenario="server_loss", replication="repair",
        scenario_horizon=12))
    assert eng.replication is not None
    rng = np.random.default_rng(3)
    outstanding = []
    for t in range(30):  # drip-feed through the loss window
        for _ in range(2):
            rid = len(outstanding)
            req = Request(rid=rid, max_new_tokens=2, prefix_id=rid % 6,
                          prompt=rng.integers(0, CFG.vocab_size,
                                              8).astype(np.int32))
            eng.submit(req)
            outstanding.append(req)
        eng.step()
    while any(r.finish_time == 0.0 for r in outstanding) and eng.steps < 200:
        eng.step()
    assert all(r.finish_time > 0 for r in outstanding)
    assert eng.replication.moves > 0  # the window forced repairs
    assert eng.replication.availability() == pytest.approx(1.0)


# ------------------------------------------------------- study driver ----

def test_replication_study_shapes_and_gates():
    cfg = rb.StudyConfig(sim=small_cfg(horizon=600, warmup=150), seeds=(0,))
    study = rb.replication_study(cfg, replications=("fixed", "repair"),
                                 scenarios=("server_loss",),
                                 policies=("balanced_pandas",),
                                 loads=(0.7,))
    a = study["availability"]["server_loss"]["repair"]["balanced_pandas"]
    assert a.shape == (1, 1)
    mv = study["repair_moves"]["server_loss"]
    assert float(mv["repair"]["balanced_pandas"].mean()) > 0
    assert float(mv["fixed"]["balanced_pandas"].mean()) == 0
    text = rb.summarize_replication(study)
    assert "server_loss" in text and "repair" in text


# ------------------------------------------------- satellite regressions --

def test_placement_package_imports_without_scipy():
    """repro.placement must import (and everything but the LP must run)
    when scipy is absent; placement_capacity raises a descriptive
    ImportError under strict=True and returns None under strict=False."""
    code = """
import sys
sys.modules["scipy"] = None
sys.modules["scipy.optimize"] = None
sys.modules["scipy.sparse"] = None
import repro.placement as P
from repro.core import locality as loc
try:
    P.placement_capacity(loc.Topology(8, 4), loc.Rates(), 0.5, "uniform",
                         n_samples=50, strict=True)
except ImportError as e:
    assert "scipy" in str(e) and "optional" in str(e), e
else:
    raise AssertionError("strict=True should raise without scipy")
out = P.placement_capacity(loc.Topology(8, 4), loc.Rates(), 0.5, "uniform",
                           n_samples=50, strict=False)
assert out is None, out
print("OK")
"""
    proc = subprocess.run([sys.executable, "-c", code], text=True,
                          capture_output=True)
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout


def test_hot_aware_state_json_safe_with_numpy_ids():
    """np.int64 chunk ids (what the pipeline/engine actually pass) must not
    poison the checkpoint manifest: json.dumps of state_dict() works and
    the round trip preserves the counts."""
    from repro.placement import make_placement
    p = make_placement("hot_aware")
    for c in (np.int64(3), np.int32(5), 3):
        p.note_read(c)
    s = json.loads(json.dumps(p.state_dict()))
    assert s["count_ids"] == [3, 5] and s["counts"] == [2, 1]


def test_hot_aware_mid_run_save_load_same_rebalance():
    """Satellite regression: save/load mid-run must leave the *subsequent*
    rebalance() decisions identical (the popularity state round-trips
    through the trainer's JSON manifest path)."""
    from repro.placement import make_placement
    from repro.train.trainer import _np_to_list
    rng = np.random.default_rng(0)
    p1 = make_placement("hot_aware")
    for c in rng.integers(0, 32, 200):
        p1.note_read(c)  # numpy ints, like the real callers
    p1.rebalance()
    for c in rng.integers(0, 32, 100):
        p1.note_read(c)
    state = json.loads(json.dumps(_np_to_list(p1.state_dict())))
    p2 = make_placement("hot_aware")
    p2.load_state_dict(state)
    assert p1.rebalance() == p2.rebalance()
    assert p1.state_dict() == p2.state_dict()
    topo = loc.Topology(12, 4)
    for c in range(32):
        assert (p1.replicas(topo, c, 3, 0) == p2.replicas(topo, c, 3, 0))

"""Shape/dtype sweeps for the scheduler kernels vs. their pure-jnp oracles."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RATES = np.array([0.5, 0.45, 0.25], np.float32)


def _fleet(rng, m, rack):
    q = jnp.asarray(rng.integers(0, 20, (m, 3)), jnp.int32)
    serving = jnp.asarray(rng.integers(0, 4, m), jnp.int32)
    er = jnp.asarray(np.tile(RATES, (m, 1)) * rng.uniform(0.8, 1.2, (m, 3)),
                     jnp.float32)
    sr = jnp.asarray(np.arange(m) // rack, jnp.int32)
    return q, serving, er, sr


@pytest.mark.parametrize("m,b,rack", [
    (64, 8, 8),          # single tile
    (300, 50, 25),       # padding on both axes
    (1024, 256, 32),     # multi-tile servers
    (4096, 512, 64),     # fleet scale-ish
])
def test_fleet_route_default_blocks_match_oracle(m, b, rack):
    """The route kernel at its default blocks (a legacy rack map) equals
    its oracle."""
    rng = np.random.default_rng(m + b)
    q, serving, er, sr = _fleet(rng, m, rack)
    tl = jnp.sort(jnp.asarray(
        np.stack([rng.choice(m, 3, replace=False) for _ in range(b)]),
        jnp.int32), axis=1)
    s1, t1, sc1 = ops.fleet_route(q, serving, er, sr, tl)
    s2, t2, sc2 = ref.fleet_route(q, serving, er, sr, tl)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_allclose(np.asarray(sc1), np.asarray(sc2), rtol=1e-6)


def test_fleet_route_prefers_idle_local():
    """Semantics spot-check: an idle local server must win."""
    m = 256
    q = jnp.zeros((m, 3), jnp.int32).at[:, 0].set(5).at[7].set(0)
    serving = jnp.ones((m,), jnp.int32).at[7].set(0)
    er = jnp.tile(jnp.asarray(RATES)[None], (m, 1))
    sr = jnp.asarray(np.arange(m) // 16, jnp.int32)
    tl = jnp.asarray([[7, 20, 40]], jnp.int32)
    server, tier, _ = ops.fleet_route(q, serving, er, sr, tl)
    assert int(server[0]) == 7 and int(tier[0]) == 0


@pytest.mark.parametrize("n,b,rack", [(64, 8, 8), (300, 37, 25), (2048, 200, 64)])
def test_maxweight_claim_matches_oracle(n, b, rack):
    rng = np.random.default_rng(n * 7 + b)
    q = jnp.asarray(rng.integers(0, 5, n), jnp.float32)
    qr = jnp.asarray(np.arange(n) // rack, jnp.int32)
    ids = jnp.asarray(rng.choice(n, b, replace=False), jnp.int32)
    ir = qr[ids]
    er = jnp.asarray(np.tile(RATES, (b, 1)) * rng.uniform(0.8, 1.2, (b, 3)),
                     jnp.float32)
    q1, s1 = ops.maxweight_claim(q, qr, ids, ir, er)
    q2, s2 = ref.maxweight_claim(q, qr, ids, ir, er)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


def test_maxweight_all_empty_scores_neginf():
    n = 128
    q = jnp.zeros((n,), jnp.float32)
    qr = jnp.zeros((n,), jnp.int32)
    ids = jnp.asarray([3, 5], jnp.int32)
    er = jnp.tile(jnp.asarray(RATES)[None], (2, 1))
    _, score = ops.maxweight_claim(q, qr, ids, qr[ids], er)
    assert (np.asarray(score) < -1e30).all()


def test_kernel_router_consistent_with_core_router():
    """The production kernel and the numpy cluster router agree on routing
    decisions over the private tiers (snapshot semantics)."""
    from repro.core import ClusterSpec, BalancedPandasRouter
    rng = np.random.default_rng(0)
    spec = ClusterSpec(num_workers=32, workers_per_pod=8)
    router = BalancedPandasRouter(spec, RATES, seed=1)
    router.q = rng.integers(0, 6, (32, 3)).astype(np.int64)

    er = jnp.asarray(router._est(), jnp.float32)
    sr = jnp.asarray(spec.pod_of, jnp.int32)
    tasks = np.sort(np.stack([rng.choice(32, 3, replace=False)
                              for _ in range(16)]), axis=1)
    # the numpy router's workload has no in-service residual
    servers, tiers, _ = ops.fleet_route(
        jnp.asarray(router.q, jnp.int32), jnp.zeros((32,), jnp.int32), er,
        sr, jnp.asarray(tasks, jnp.int32))
    remote = router.num_tiers - 1
    for i, task in enumerate(tasks):
        tier = router.tiers(task)
        rate = np.take_along_axis(router._est(), tier[:, None], 1)[:, 0]
        score = np.where(tier < remote, router.workload() / rate, np.inf)
        mins = np.flatnonzero(np.isclose(score, score.min(), rtol=1e-6))
        assert int(servers[i]) in mins
        assert int(tiers[i]) == tier[int(servers[i])]

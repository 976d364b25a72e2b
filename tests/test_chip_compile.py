"""Compile the simulator's main path for a TPU v5e chip, without the chip.

The TPU compiler is installed alongside jax; it compiles for a chip that
is described rather than attached.  These tests compile, at fleet size,
what the chip runs: the fused route kernel (`kernels/slot_step.py`), the
fleet chunk program with that kernel inside it, and the vmapped dense
sweep at paper scale.  Nothing runs, so they check only that the chip's
compiler accepts the programs (layouts, lowering rules, VMEM) — the
interpret-mode tests of tests/test_fleet_scale.py check the results.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under pytest-xdist
every worker imports this file.  Keep every chip-compile test in this one
file, so that one worker holds the library.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import locality as loc, simulator as sim
from repro.kernels import ops as kops
from repro.sharding.sim import (FleetConfig, _build_fleet_chunk, make_ctx,
                                stationary_traffic)

FLEET_M = 10_008


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _shape(x, sharding):
    x = np.asarray(x)
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _fleet_cfg(topo, rates, horizon=512):
    """λ = 0.8·capacity, batch 2.05·λ: the fleet cells' arrival rule."""
    lam = 0.8 * loc.capacity_hot_rack(topo, rates, 0.5)
    cfg = sim.SimConfig(topo=topo, true_rates=rates, p_hot=0.5,
                        max_arrivals=int(2.05 * lam), horizon=horizon,
                        warmup=horizon // 4)
    return cfg, lam


@pytest.mark.parametrize("topo_,rates", [
    (loc.Topology(FLEET_M, 6), loc.Rates()),
    (loc.Topology(FLEET_M, (6, 12)), loc.Rates(0.5, 0.45, 0.35, 0.25)),
], ids=["k3", "k4"])
def test_fleet_route_kernel_compiles(one_chip, topo_, rates):
    cfg, _ = _fleet_cfg(topo_, rates)
    m, k, b = FLEET_M, topo_.num_tiers, cfg.max_arrivals
    anc = np.asarray(make_ctx(topo_).anc)
    f = jax.jit(functools.partial(kops.fleet_route, interpret=False))
    text = f.lower(_shape(np.zeros((m, k), np.float32), one_chip),
                   _shape(np.zeros((m,), np.int32), one_chip),
                   _shape(np.zeros((m, k), np.float32), one_chip),
                   _shape(anc, one_chip),
                   _shape(np.zeros((b, 3), np.int32), one_chip)
                   ).compile().as_text()
    assert "tpu_custom_call" in text


def test_fleet_chunk_compiles_with_kernel(one_chip, monkeypatch):
    # the chunk picks the kernel when it sees a TPU backend; here the
    # backend is the CPU, so the test tells it otherwise
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    rates = loc.Rates()
    cfg, lam = _fleet_cfg(loc.Topology(FLEET_M, 6), rates)
    est = loc.per_server_rates(rates.as_array(), FLEET_M).astype(np.float32)
    init, chunk = _build_fleet_chunk("balanced_pandas", cfg, FleetConfig())
    args = (tuple(_shape(a, one_chip) for a in init()),
            _shape(np.int32(0), one_chip), _shape(np.float32(lam), one_chip),
            _shape(est, one_chip), _shape(np.uint32(0), one_chip))
    text = jax.jit(chunk).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_fleet_chunk_compiles_with_hot_racks(one_chip, monkeypatch):
    """The chunk of the hot-rack fleet cell: a quarter of the racks hot,
    half the tasks hot, 0.9 of the fluid capacity (417 x the paper's
    10.0 tasks/slot), lanes 2.05 x that, the weighted sampler inside."""
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    rates = loc.Rates()
    lam = 0.9 * 417 * loc.capacity_hot_rack(loc.Topology(24, 6), rates, 0.5)
    cfg = sim.SimConfig(topo=loc.Topology(FLEET_M, 6), true_rates=rates,
                        p_hot=0.5, max_arrivals=int(2.05 * lam),
                        horizon=2048, warmup=512)
    cfg, weights = stationary_traffic(cfg, "hot_racks")
    est = loc.per_server_rates(rates.as_array(), FLEET_M).astype(np.float32)
    init, chunk = _build_fleet_chunk("balanced_pandas", cfg, FleetConfig(),
                                     weights)
    args = (jax.tree.map(lambda a: _shape(a, one_chip), init()),
            _shape(np.int32(0), one_chip), _shape(np.float32(lam), one_chip),
            _shape(est, one_chip), _shape(np.uint32(0), one_chip))
    text = jax.jit(chunk).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("policy", ["balanced_pandas", "jsq_maxweight"])
def test_dense_sweep_compiles(one_chip, policy):
    cfg = sim.SimConfig(topo=loc.Topology(24, 6), true_rates=loc.Rates(),
                        p_hot=0.5, horizon=2000, warmup=500)
    run = sim._build_run(policy, cfg)
    f = jax.vmap(jax.vmap(jax.vmap(run, (None, None, 0)), (None, 0, None)),
                 (0, None, None))
    est = np.stack([sim.make_estimates(cfg, "network", 0.0, -1)] * 2)
    text = jax.jit(f).lower(
        _shape(np.zeros((3,), np.float32), one_chip),
        _shape(est.astype(np.float32), one_chip),
        _shape(np.zeros((2,), np.uint32), one_chip)).compile().as_text()
    assert "while" in text

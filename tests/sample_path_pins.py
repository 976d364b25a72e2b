"""Sample-path pins of the dense simulator, and the script that records them.

Three tests hold the dense `simulate` path to exact recorded metrics:
tests/test_topology.py (the K=3 pins at Topology(12, 4) and (24, 6)) and
tests/test_fleet_scale.py (one pin per registered policy).  The values are
f32 results of one JAX build; a JAX upgrade that changes f32 rounding or
an RNG sampler moves them although the code did not change.  After such
an upgrade, first check that the in-repo references still agree (the
fleet kernel and segment-min loops stay bitwise equal, the fleet-vs-dense
bands hold, `slo_pandas` still equals `balanced_pandas`), then re-record:

    PYTHONPATH=src JAX_PLATFORMS=cpu python tests/sample_path_pins.py

which rewrites tests/sample_path_pins.json from the recipes below.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax

from repro.core import locality as loc, simulator as sim
from repro.core.policy import PolicyConfig

PINS_FILE = Path(__file__).with_name("sample_path_pins.json")


def run_12x4(algo: str):
    """Topology(12, 4), Rates(0.5, 0.45, 0.25), p_hot=0.5, max_arrivals=16,
    horizon=2000, warmup=500, lam = 0.8 * capacity, seed 3."""
    cfg = sim.SimConfig(topo=loc.Topology(12, 4), true_rates=loc.Rates(),
                        p_hot=0.5, max_arrivals=16, horizon=2000, warmup=500)
    cap = loc.capacity_hot_rack(cfg.topo, cfg.true_rates, cfg.p_hot)
    est = sim.make_estimates(cfg, "network", 0.0, -1)
    return sim.simulate(algo, cfg, 0.8 * cap, est, seed=3)


def run_24x6(algo: str):
    """Topology(24, 6), max_arrivals=24, horizon=1500, warmup=300,
    lam = 0.9 * capacity (= 9.0), seed 7."""
    cfg = sim.SimConfig(topo=loc.Topology(24, 6), true_rates=loc.Rates(),
                        p_hot=0.5, max_arrivals=24, horizon=1500, warmup=300)
    cap = loc.capacity_hot_rack(cfg.topo, cfg.true_rates, cfg.p_hot)
    assert abs(cap - 10.0) < 1e-9
    est = sim.make_estimates(cfg, "network", 0.0, -1)
    return sim.simulate(algo, cfg, 0.9 * cap, est, seed=7)


def run_dense(name: str):
    """Topology(24, 6), max_arrivals=24, horizon=1200, warmup=300,
    lam = 0.8 * capacity, seed 0; blind_pandas starts from the true rates."""
    cfg = sim.SimConfig(topo=loc.Topology(24, 6), true_rates=loc.Rates(),
                        p_hot=0.5, max_arrivals=24, horizon=1200, warmup=300)
    cap = loc.capacity_hot_rack(cfg.topo, cfg.true_rates, 0.5)
    est = sim.make_estimates(cfg, "network", 0.0, -1)
    pol = PolicyConfig(name, {"prior": cfg.true_rates.values}) \
        if name == "blind_pandas" else name
    return sim.simulate(pol, cfg, 0.8 * cap, est, seed=0)


RECIPES = {"k3_12x4": run_12x4, "k3_24x6": run_24x6, "dense": run_dense}
POLICIES = {
    "k3_12x4": ("balanced_pandas", "blind_pandas", "fifo", "jsq_maxweight",
                "pandas_po2", "priority"),
    "k3_24x6": ("balanced_pandas", "jsq_maxweight"),
    "dense": ("balanced_pandas", "blind_pandas", "fifo", "jsq_maxweight",
              "pandas_po2", "priority", "slo_pandas"),
}


def load() -> dict:
    """{group: {policy: {metric: value}}} as last recorded."""
    return json.loads(PINS_FILE.read_text())


def record() -> dict:
    pins = {group: {algo: RECIPES[group](algo) for algo in algos}
            for group, algos in POLICIES.items()}
    pins["recorded_with"] = f"jax {jax.__version__}"
    PINS_FILE.write_text(json.dumps(pins, indent=1, sort_keys=True) + "\n")
    return pins


if __name__ == "__main__":
    old = load() if PINS_FILE.exists() else {}
    new = record()
    for group in POLICIES:
        for algo, metrics in new[group].items():
            was = old.get(group, {}).get(algo)
            print(f"{group:8s} {algo:16s} "
                  f"{'unchanged' if was == metrics else 'changed'}: "
                  f"{metrics}", file=sys.stderr)

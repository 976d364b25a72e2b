"""The hot-rack fleet cell (`borg10k.bp_hot`) at a small size on the CPU:
240 servers in 40 racks of 6, every 4th rack hot, so 10 hot racks.

* the sound run reads `state_gap` and `replay_gap` 0 against the plain
  reference bench/reference/fleet_racks.py;
* the bfloat16 control reads above the limit;
* a program whose hot tasks take their replicas from rack 0 alone, or
  from racks drawn uniformly, comes out not correct.

    python -m pytest -q bench/tests/test_hot_racks.py
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import small
import control

CELL = "borg10k.bp_hot"
SEEDS = (4294967311, 2147483659, 3000000019)


def shrink(config: dict, workload: dict):
    config, workload = dict(config), json.loads(json.dumps(workload))
    config.update(num_servers=240, horizon=384, warmup=128)
    return config, workload


def run_cell(capsys) -> dict:
    rc = small.run.main(["--workload", CELL, "--seed", str(SEEDS[0]),
                         "--seconds", "0.01", "--trace", "0"],
                        find_chip=False, overrides=shrink)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture(autouse=True)
def fresh_programs(monkeypatch):
    """No compiled program outlives a test: a planted fault must reach the
    trace, and a sound run must not see one."""
    from repro.sharding import sim as fs
    monkeypatch.setattr(fs, "_CHUNK_CACHE", {})
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_run_reads_zero(capsys):
    result = run_cell(capsys)
    assert result["correct"] is True
    assert result["checks"]["state_gap"]["value"] == 0.0
    assert result["checks"]["replay_gap"]["value"] == 0.0


def test_control_fails_and_program_passes():
    limits = json.loads((small.BENCH / "workloads" / f"{CELL}.json"
                         ).read_text())["check"]["limits"]
    for r in control.readings(CELL, SEEDS, find_chip=False,
                              overrides=shrink):
        assert all(v <= limits[k] for k, v in r["program"].items()), r
        (ctl,) = r["control"].values()
        assert ctl["state_gap"] > limits["state_gap"], r


def _racks_replaced(monkeypatch, cum_of):
    """Plant a fault in the program's sampler: hot racks drawn from the
    cumulative shares `cum_of(R)` in place of the configured ones."""
    from repro.sharding import sim as fs
    orig = fs._sample_arrivals

    def sample(key, ctx, lam, p_hot, batch):
        if ctx.rack_cum is not None:
            racks = ctx.rack_cum.shape[0]
            ctx = dataclasses.replace(ctx, rack_cum=jnp.asarray(
                cum_of(racks), jnp.float32))
        return orig(key, ctx, lam, p_hot, batch)
    monkeypatch.setattr(fs, "_sample_arrivals", sample)


@pytest.mark.parametrize("fault", ["rack_0_only", "uniform_racks"])
def test_fault_is_not_correct(fault, monkeypatch, capsys):
    cum_of = {"rack_0_only": lambda r: np.ones(r),
              "uniform_racks": lambda r: np.arange(1, r + 1) / r}[fault]
    _racks_replaced(monkeypatch, cum_of)
    result = run_cell(capsys)
    assert result["correct"] is False, result["checks"]

"""A run whose timed path is broken underneath has to come out not correct.

Each test skips the harness's look for a chip (`find_chip=False`) and
drives the rest of a run at the small sizes of `small.py`, on the CPU, with
one fault planted in the program before set-up, so that the window and the
check both see it:

* a step that returns its state unchanged;
* half of the arrival batch left out (lanes past the middle never carry
  a task);
* an answer altered where it is produced: routing decisions sent to the
  next server (every one on the dense path; the first task's private
  choice in each slot on the fleet path);
* on the fleet path, the accumulators behind mean_delay and throughput
  (the running mean of tasks in the system and the count of measured
  slots) left as they were at the start of each chunk.  On the dense path
  the check compares those metrics with the reference's, whole.

The exchange between chips has no fault to plant: every cell runs on one
chip and no simulator state is sharded.  The sound run of each cell, with
nothing planted, has to come out correct.

    python -m pytest -q bench/tests
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import small  # noqa: F401  (puts bench/ and src/ on the path)
from small import run_cell

DENSE = ("paper24.bp_fig3", "paper24.mw_fig3")
FLEET = ("borg10k.bp_uniform",)


def _unchanged_dense(monkeypatch):
    from repro.core import balanced_pandas as bp, jsq_maxweight as mw
    monkeypatch.setattr(bp, "slot_step",
                        lambda s, *a, **k: (s, jnp.int32(0)))
    monkeypatch.setattr(mw, "slot_step",
                        lambda s, *a, **k: (s, jnp.int32(0)))


def _half_dense(monkeypatch):
    from repro.core import locality as loc
    orig = loc.sample_arrivals_at

    def half(key, rack_of, lam, p_hot, hot_rack, max_arrivals, *a, **k):
        types, active = orig(key, rack_of, lam, p_hot, hot_rack,
                             max_arrivals, *a, **k)
        return types, active & (jnp.arange(max_arrivals) < max_arrivals // 2)
    monkeypatch.setattr(loc, "sample_arrivals_at", half)


def _altered_dense(monkeypatch):
    from repro.core import locality as loc
    orig = loc.random_argmin
    monkeypatch.setattr(loc, "random_argmin", lambda key, score: (
        orig(key, score) + 1) % score.shape[0])


def _unchanged_fleet(monkeypatch):
    from repro.sharding import sim as fs
    monkeypatch.setattr(fs, "_route_batch_pandas", lambda s, *a, **k: s)


def _half_fleet(monkeypatch):
    from repro.sharding import sim as fs
    orig = fs._sample_arrivals

    def half(key, ctx, lam, p_hot, batch):
        types, active = orig(key, ctx, lam, p_hot, batch)
        return types, active & (jnp.arange(batch) < batch // 2)
    monkeypatch.setattr(fs, "_sample_arrivals", half)


def _altered_fleet(monkeypatch):
    from repro.sharding import sim as fs
    orig = fs._private_route_segmin

    def altered(w, est, ctx, locs):
        best_i, best_t, best_v = orig(w, est, ctx, locs)
        first = jnp.arange(best_i.shape[0]) == 0
        return (jnp.where(first, (best_i + 1) % ctx.num_servers, best_i),
                best_t, best_v)
    monkeypatch.setattr(fs, "_private_route_segmin", altered)


def _accumulators_fleet(monkeypatch):
    from repro.sharding import sim as fs
    orig = fs._build_fleet_chunk

    def build(*a, **k):
        init, chunk = orig(*a, **k)

        def skipped(carry, *args):
            out = chunk(carry, *args)
            return out[:2] + carry[2:4] + out[4:]
        return init, skipped
    monkeypatch.setattr(fs, "_build_fleet_chunk", build)


FAULTS = {"unchanged": {"dense": _unchanged_dense, "fleet": _unchanged_fleet},
          "half_batch": {"dense": _half_dense, "fleet": _half_fleet},
          "altered": {"dense": _altered_dense, "fleet": _altered_fleet},
          "accumulators": {"fleet": _accumulators_fleet}}
CASES = [(cell, fault) for fault in sorted(FAULTS) for cell in DENSE + FLEET
         if ("dense" if cell in DENSE else "fleet") in FAULTS[fault]]


@pytest.fixture(autouse=True)
def fresh_programs(monkeypatch):
    """No compiled program outlives a test: a planted fault must reach the
    trace, and a sound run must not see one."""
    from repro.sharding import sim as fs
    monkeypatch.setattr(fs, "_CHUNK_CACHE", {})
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell", DENSE + FLEET)
def test_sound_run_is_correct(cell, capsys):
    assert run_cell(cell, capsys=capsys)["correct"] is True


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_not_correct(cell, fault, monkeypatch, capsys):
    plant = FAULTS[fault]["dense" if cell in DENSE else "fleet"]
    plant(monkeypatch)
    result = run_cell(cell, capsys=capsys)
    assert result["correct"] is False, result["checks"]

"""One-chip smoke run of the scheduler simulator and the serving engine.

    python3 chip_smoke.py

Needs one TPU; run anywhere else it exits non-zero and prints no result.
It drives the main path once through the entry points the examples and
benches call, on `jax.devices()[0]`, in this one process:

  (a) dense   `simulator.sweep` of balanced_pandas and jsq_maxweight at
              paper scale (Topology(24, 6), p_hot 0.5): 3 loads x 2
              network-error settings x 2 seeds, horizon 2000;
  (b) fleet   `simulator.simulate` and `sweep(fleet=True)` of
              balanced_pandas at M=10008 with the fleet cells' arrival
              batch (2.05 x 0.8 x capacity), horizon 512 — the fleet
              backend with the compiled Pallas route kernel in its chunk;
  (c) serving `ServingEngine` drains of 8 requests behind the
              balanced_pandas router, on the `repro.launch.serve` build of
              granite_moe_1b — a smoke-width stand-in model, random
              weights from seed 0.

Checks (any failure exits non-zero): the route kernel's server and tier
equal `kernels/ref.fleet_route` on seeded fleet-size snapshots; fleet
metrics with the kernel equal those of the XLA segment-min route; the
compiled fleet chunk contains the kernel (`tpu_custom_call`); throughput
matches offered load (every load here is below capacity); every metric is
finite; dense and fleet results agree with the same calls on the host CPU
device within `DENSE_BAND` and `FLEET_BAND` (see there); the fleet
sweep's cell equals `simulate` at the same load and seed.  Each phase
prints its wall time (compilation included; `bench/run.py` measures speed
and set-up).  The last line of standard output is one JSON object naming
the device.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FLEET_M = 10_008
POLICIES = ("balanced_pandas", "jsq_maxweight")
LOADS = (0.6, 0.8, 0.95)            # fractions of the hot-rack capacity
ERRORS = ((0.0, -1), (0.3, -1))     # ("network", eps, sign) estimates
SEEDS = (0, 1)
# Chip-vs-host bands: the largest relative difference allowed per metric.
# Both backends draw the same random stream (threefry is bit-exact), but
# their f32 arithmetic (division among it) can differ in the last bit.  On
# the dense path that moved only the rounding of the running means (at most
# 1.2e-7 on a TPU v5e), so its band is f32 rounding with headroom.  On the
# fleet path such a difference re-routes a task and the sample path parts;
# a parted path is another draw of the same queue and agrees only
# statistically (0.6% in mean delay on the v5e, where seeds 0 and 1 differ
# by 0.1-0.4%).
DENSE_BAND = {"throughput": 1e-5, "mean_delay": 1e-5, "mean_n": 1e-5}
FLEET_BAND = {"throughput": 0.005, "mean_delay": 0.03, "mean_n": 0.03}
THROUGHPUT_BAND = 0.03              # |throughput / λ - 1| below capacity


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


def compare_to_cpu(label, chip, cpu, band):
    """Print chip vs host per metric; hold each to its band."""
    import numpy as np
    for key, limit in band.items():
        a = np.asarray(chip[key], np.float64)
        b = np.asarray(cpu[key], np.float64)
        bitwise = np.array_equal(np.asarray(chip[key]), np.asarray(cpu[key]))
        rel = float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))
        print(f"  {label} {key}: chip {a.ravel().tolist()} | cpu "
              f"{b.ravel().tolist()} | bitwise {bitwise} | max rel diff "
              f"{rel:.3g}", flush=True)
        check(rel <= limit, f"{label} {key} chip vs cpu within {limit:g}")


def check_metrics(label, out, lam) -> None:
    import numpy as np
    for key, val in out.items():
        check(bool(np.all(np.isfinite(np.asarray(val)))),
              f"{label} {key} finite")
    thr = np.asarray(out["throughput"], np.float64)
    lam = np.broadcast_to(np.asarray(lam, np.float64), thr.shape)
    rel = float(np.max(np.abs(thr / lam - 1.0)))
    check(rel <= THROUGHPUT_BAND,
          f"{label} throughput matches offered load (max rel diff "
          f"{rel:.3g} <= {THROUGHPUT_BAND})")


def phase_dense(cpu, m: int = 24, horizon: int = 2000):
    import jax
    import numpy as np
    from repro.core import locality as loc, simulator as sim

    cfg = sim.SimConfig(topo=loc.Topology(m, 6), true_rates=loc.Rates(),
                        p_hot=0.5, horizon=horizon, warmup=horizon // 4)
    cap = loc.capacity_hot_rack(cfg.topo, cfg.true_rates, 0.5)
    lam = np.asarray(LOADS, np.float32) * cap
    ests = np.stack([sim.make_estimates(cfg, "network", eps, sign)
                     for eps, sign in ERRORS])
    seeds = np.asarray(SEEDS)
    for pol in POLICIES:
        out = jax.block_until_ready(sim.sweep(pol, cfg, lam, ests, seeds))
        check_metrics(f"dense {pol}", out, lam[:, None, None])
        with jax.default_device(cpu):
            ref = jax.block_until_ready(
                sim.sweep(pol, cfg, lam, ests, seeds))
        compare_to_cpu(f"dense {pol}", out, ref, DENSE_BAND)


def fleet_snapshots(rng, m, k, batch, hot_rack):
    """A seeded policy state and arrival batch at fleet size: half the
    tasks sit in rack 0, so group minima collide as they do in the loop."""
    import numpy as np
    q = rng.integers(0, 60, (m, k)).astype(np.int32)
    serving = rng.integers(0, k + 1, (m,)).astype(np.int32)
    pools = np.where(np.arange(batch) < batch // 2, hot_rack, m)
    locs = np.stack([np.sort(rng.choice(int(p), 3, replace=False))
                     for p in pools]).astype(np.int32)
    return q, serving, locs


def phase_fleet(cpu, m: int = FLEET_M, horizon: int = 512):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import locality as loc, simulator as sim
    from repro.kernels import ops as kops, ref
    from repro.sharding import sim as fleet_sim

    check(kops._on_tpu(), "kernels run compiled (interpret=False)")
    topo, rates = loc.Topology(m, 6), loc.Rates()
    cap = loc.capacity_hot_rack(topo, rates, 0.5)
    lam = 0.8 * cap
    cfg = sim.SimConfig(topo=topo, true_rates=rates, p_hot=0.5,
                        max_arrivals=int(2.05 * lam), horizon=horizon,
                        warmup=horizon // 4)
    est = sim.make_estimates(cfg, "network", 0.0, -1)
    ctx = fleet_sim.make_ctx(topo)

    route = jax.jit(kops.fleet_route)
    oracle = jax.jit(ref.fleet_route)
    rng = np.random.default_rng(0)
    for i in range(3):
        q, serving, locs = fleet_snapshots(rng, m, topo.num_tiers,
                                           cfg.max_arrivals, ctx.hot_rack_size)
        sk, tk, vk = jax.block_until_ready(
            route(q, serving, est, ctx.anc, locs))
        sr, tr, vr = jax.block_until_ready(
            oracle(q, serving, est, ctx.anc, locs))
        check(np.array_equal(sk, sr) and np.array_equal(tk, tr),
              f"kernel server and tier equal ref.fleet_route (snapshot {i}, "
              f"B={len(locs)}, M={m})")
        print(f"  score equal bitwise: {np.array_equal(vk, vr)}", flush=True)

    fc = fleet_sim.FleetConfig()
    init, chunk = fleet_sim._jitted_chunk("balanced_pandas", cfg, fc)
    compiled = chunk.lower(
        init(), jnp.int32(0), jnp.float32(lam),
        jnp.asarray(est, jnp.float32), jnp.uint32(0)).compile()
    text = compiled.as_text()
    check("tpu_custom_call" in text,
          "compiled fleet chunk contains the route kernel")

    out = jax.block_until_ready(
        sim.simulate("balanced_pandas", cfg, lam, est, seed=0))
    check_metrics("fleet simulate", out, lam)
    segmin = jax.block_until_ready(sim.simulate(
        "balanced_pandas", cfg, lam, est, seed=0,
        fleet=fleet_sim.FleetConfig(use_pallas=False)))
    check(out == segmin, "fleet metrics with the kernel equal the "
          "segment-min route's (bitwise)")

    lam_grid = np.asarray((0.6 * cap, lam), np.float32)
    ests, seeds = est[None], np.asarray(SEEDS)
    grid = jax.block_until_ready(sim.sweep(
        "balanced_pandas", cfg, lam_grid, ests, seeds, fleet=True))
    check_metrics("fleet sweep", grid, lam_grid[:, None, None])
    check(all(float(grid[k][1, 0, 0]) == v for k, v in out.items()),
          "fleet sweep at (0.8 capacity, seed 0) equals simulate (bitwise)")

    host = fleet_sim.FleetConfig(use_pallas=False)
    with jax.default_device(cpu):
        out_cpu = jax.block_until_ready(sim.simulate(
            "balanced_pandas", cfg, lam, est, seed=0, fleet=host))
        grid_cpu = jax.block_until_ready(sim.sweep(
            "balanced_pandas", cfg, lam_grid, ests, seeds, fleet=host))
    compare_to_cpu("fleet simulate", out, out_cpu, FLEET_BAND)
    compare_to_cpu("fleet sweep", grid, grid_cpu, FLEET_BAND)


def phase_serving(arch: str = "granite_moe_1b", n: int = 8):
    import jax
    import numpy as np
    from repro.launch.serve import build_engine, make_requests

    print(f"  model: {arch} smoke config — the engine's stand-in model, "
          f"not its published widths", flush=True)
    cfg, eng = jax.block_until_ready(build_engine(arch, "balanced_pandas"))
    reqs = make_requests(cfg, n)
    out = jax.block_until_ready(eng.run_until_drained(reqs))
    check(len(out) == n, f"all {n} requests drained")
    for r in out:
        toks = np.asarray(r.generated)
        check(len(toks) == r.max_new_tokens + 1
              and bool(np.all((toks >= 0) & (toks < cfg.vocab_size))),
              f"request {r.rid}: {len(toks)} tokens in vocabulary")
    lat = np.asarray([r.finish_time - r.arrival for r in out])
    check(bool(np.all(np.isfinite(lat)) and np.all(lat >= 0)),
          "latencies finite")
    check(sum(eng.assign_tiers.values()) == n,
          f"router placed every request (tier mix {eng.assign_tiers})")
    print(f"  {eng.steps} engine steps, mean latency "
          f"{lat.mean() * 1e3:.1f} ms", flush=True)


def main() -> int:
    try:
        from repro.utils.cache import enable_persistent_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e}); run it from a checkout", file=sys.stderr)
        return 2
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    cpu = jax.devices("cpu")[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"jax {jax.__version__}; compile cache "
          f"{enable_persistent_cache()}", flush=True)
    for name, phase in (("a dense", lambda: phase_dense(cpu)),
                        ("b fleet", lambda: phase_fleet(cpu)),
                        ("c serving", phase_serving)):
        print(f"phase ({name})", flush=True)
        t0 = time.perf_counter()
        phase()
        print(f"phase ({name}) done: wall {time.perf_counter() - t0:.3f} s",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

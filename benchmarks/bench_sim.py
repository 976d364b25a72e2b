"""Simulator throughput bench: slots/sec per policy, 3-tier vs 4-tier.

The tier-generic refactor makes the tier count a parameter of every hot
path (policy state shapes, kernel tier derivation, schedule compilation),
so this bench tracks what that generality costs: for each registered
policy, the wall-clock rate (simulated slots per second, compile time
excluded) of one jit-compiled run on the classic flat-rack topology and
on a 4-tier pod topology of the same fleet size.

Rows come back in the orchestrator's ``(name, value, derived)`` format;
``benchmarks/run.py --json`` additionally serializes them into the
machine-readable perf record CI uploads (the bench trajectory's seed).
Every arm reports BOTH a steady-state rate row (``sim_slots_per_sec_*``,
min-of-3 on the already-compiled executable) and a compile-time row
(``sim_compile_sec_*``, the XLA lowering+compile step timed separately
via AOT compilation) — so a compile-time regression can't hide inside a
throughput number or vice versa.
"""

from __future__ import annotations

import time

import numpy as np


def _timed(run, args) -> float:
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(run(*args))
    return time.perf_counter() - t0


def _compile_split(run, args):
    """(compile_sec, steady_sec): AOT-split timings of a jitted callable.

    Compile time is the real XLA compile (``.lower().compile()``), not a
    first-call-minus-steady estimate; steady time is min-of-3 on the
    compiled executable after one warm call (a single sample is dominated
    by run-to-run noise, which would drown any real regression in the CI
    trajectory).
    """
    import jax

    lowered = run.lower(*args)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    jax.block_until_ready(compiled(*args))  # warm: allocs, autotuning
    dt = min(_timed(compiled, args) for _ in range(3))
    return t_compile, dt


def bench(fast: bool = True):
    import jax
    from repro.core import locality as loc, simulator as sim
    from repro.core.policy import PolicyConfig, available_policies

    horizon = 2_000 if fast else 20_000
    grids = (
        ("3tier", loc.Topology(24, 6), loc.Rates()),
        ("4tier", loc.Topology(24, (6, 12)), loc.Rates((0.5, 0.45, 0.35,
                                                        0.25))),
    )
    rows = []
    for label, topo, rates in grids:
        cfg = sim.SimConfig(topo=topo, true_rates=rates, p_hot=0.5,
                            max_arrivals=24, horizon=horizon,
                            warmup=horizon // 4)
        cap = loc.capacity_hot_rack(topo, rates, cfg.p_hot)
        est = sim.make_estimates(cfg, "network", 0.0, -1)
        for name in available_policies():
            policy = PolicyConfig(name, {"prior": rates.values}) \
                if name == "blind_pandas" else name
            run = jax.jit(sim._build_run(policy, cfg))
            args = (np.float32(0.8 * cap), est.astype(np.float32),
                    np.uint32(0))
            t_compile, dt = _compile_split(run, args)
            derived = (f"policy={name},topology={label},K={topo.num_tiers},"
                       f"M={topo.num_servers},horizon={horizon}")
            rows.append((f"sim_slots_per_sec_{name}_{label}",
                         horizon / dt, derived))
            rows.append((f"sim_compile_sec_{name}_{label}", t_compile,
                         derived))
    return rows


def bench_scaling(fast: bool = True):
    """Fleet-scale throughput: simulated slots/sec of the fleet fast path
    (sharding.sim) at M=2400 and M=10008 servers, plus the dense
    reference arm at M=2400.

    These are the headline rows of the fast-fleet-path work: the dense
    `lax.scan` body is dispatch-bound (a sequential `fori_loop` of
    O(M) argmins per arrival), while the fleet path routes the whole
    arrival batch against a workload snapshot in O(M*depth + B) — see
    docs/scaling.md for the performance model.  The dense M=2400 row is
    the "before" curve; `sim_slots_per_sec_scaling_kernel_M10008` is the
    acceptance metric for 10k-server studies.
    """
    import jax
    from repro.core import locality as loc, simulator as sim
    from repro.sharding.sim import FleetConfig, _build_fleet_chunk

    rows = []
    horizon = 512 if fast else 2_048
    fleet_ms = (2_400, 10_008) if fast else (2_400, 10_008, 24_000)
    rates = loc.Rates()

    def fleet_arm(m):
        topo = loc.Topology(m, 6)
        cap = loc.capacity_hot_rack(topo, rates, 0.5)
        lam = 0.8 * cap
        batch = int(2.05 * lam)
        fc = FleetConfig()
        cfg = sim.SimConfig(topo=topo, true_rates=rates, p_hot=0.5,
                            max_arrivals=batch, horizon=horizon,
                            warmup=horizon // 4)
        est = loc.per_server_rates(rates.as_array(), m).astype(np.float32)
        init, chunk = _build_fleet_chunk("balanced_pandas", cfg, fc)
        run = jax.jit(chunk)  # no donation: _compile_split reuses args
        args = (init(), np.int32(0), np.float32(lam), est, np.uint32(0))
        t_compile, dt = _compile_split(run, args)
        derived = (f"path=fleet,policy=balanced_pandas,M={m},"
                   f"chunk={fc.chunk},rounds={fc.rounds},"
                   f"batch={batch},horizon={horizon}")
        rows.append((f"sim_slots_per_sec_scaling_kernel_M{m}",
                     fc.chunk / dt, derived))
        rows.append((f"sim_compile_sec_scaling_kernel_M{m}", t_compile,
                     derived))

    def dense_arm(m, dense_horizon):
        topo = loc.Topology(m, 6)
        cap = loc.capacity_hot_rack(topo, rates, 0.5)
        lam = 0.8 * cap
        cfg = sim.SimConfig(topo=topo, true_rates=rates, p_hot=0.5,
                            max_arrivals=int(2.05 * lam),
                            horizon=dense_horizon,
                            warmup=dense_horizon // 4)
        est = loc.per_server_rates(rates.as_array(), m).astype(np.float32)
        run = jax.jit(sim._build_run("balanced_pandas", cfg))
        args = (np.float32(lam), est, np.uint32(0))
        t_compile, dt = _compile_split(run, args)
        derived = (f"path=dense,policy=balanced_pandas,M={m},"
                   f"horizon={dense_horizon}")
        rows.append((f"sim_slots_per_sec_scaling_dense_M{m}",
                     dense_horizon / dt, derived))
        rows.append((f"sim_compile_sec_scaling_dense_M{m}", t_compile,
                     derived))

    for m in fleet_ms:
        fleet_arm(m)
    dense_arm(2_400, 64 if fast else 256)
    return rows


def bench_placement(fast: bool = True):
    """Placement-sampler throughput: simulator slots/sec of the default
    policy under every registered replica placement, 3-tier and 4-tier.

    The placement seam swaps the arrival-type sampler inside the
    `lax.scan`; this bench tracks what each compiled sampler costs
    relative to the bitwise-pinned uniform draw (the §Placement
    throughput record of the CI bench artifact).
    """
    import jax
    from repro.core import locality as loc, simulator as sim
    from repro.placement import available_placements

    horizon = 2_000 if fast else 20_000
    grids = (
        ("3tier", loc.Topology(24, 6), loc.Rates()),
        ("4tier", loc.Topology(24, (6, 12)), loc.Rates((0.5, 0.45, 0.35,
                                                        0.25))),
    )
    rows = []
    for label, topo, rates in grids:
        cfg = sim.SimConfig(topo=topo, true_rates=rates, p_hot=0.5,
                            max_arrivals=24, horizon=horizon,
                            warmup=horizon // 4)
        cap = loc.capacity_hot_rack(topo, rates, cfg.p_hot)
        est = sim.make_estimates(cfg, "network", 0.0, -1)
        args = (np.float32(0.7 * cap), est.astype(np.float32), np.uint32(0))
        for plc in available_placements():
            run = jax.jit(sim._build_run("balanced_pandas", cfg,
                                         placement=plc))
            t_compile, dt = _compile_split(run, args)
            derived = (f"placement={plc},policy=balanced_pandas,"
                       f"topology={label},K={topo.num_tiers},"
                       f"M={topo.num_servers},horizon={horizon}")
            rows.append((f"sim_slots_per_sec_placement_{plc}_{label}",
                         horizon / dt, derived))
            rows.append((f"sim_compile_sec_placement_{plc}_{label}",
                         t_compile, derived))
    return rows


def bench_control(fast: bool = True):
    """Control-plane throughput: simulator slots/sec of the default policy
    with each control arm compiled into the scan — no control (the
    bitwise-pinned reference), token-bucket admission, closed-loop load
    generation, proactive autoscaling, and the full stack with the
    SLO-conditioned scheduler + telemetry (the §SLO control study
    configuration).  Tracks what each per-slot hook costs relative to the
    zero-cost ``control=None`` baseline.
    """
    import jax
    from repro.core import locality as loc, simulator as sim

    horizon = 2_000 if fast else 20_000
    topo, rates = loc.Topology(24, 6), loc.Rates()
    cfg = sim.SimConfig(topo=topo, true_rates=rates, p_hot=0.5,
                        max_arrivals=24, horizon=horizon,
                        warmup=horizon // 4)
    cap = loc.capacity_hot_rack(topo, rates, cfg.p_hot)
    est = sim.make_estimates(cfg, "network", 0.0, -1)
    args = (np.float32(0.9 * cap), est.astype(np.float32), np.uint32(0))
    bucket = {"name": "token_bucket",
              "options": {"rate": 0.93 * cap, "burst": 8.0 * cap}}
    arms = [
        ("none", "balanced_pandas", None, None),
        ("admission", "balanced_pandas", bucket, None),
        ("closed_loop", "balanced_pandas",
         {"name": "closed_loop", "options": {"users": 64}}, None),
        ("autoscale", "balanced_pandas", "autoscale", None),
        ("full_slo", "slo_pandas", (bucket, "autoscale"), True),
    ]
    rows = []
    for label, pol, control, telemetry in arms:
        run = jax.jit(sim._build_run(pol, cfg, control=control,
                                     telemetry=telemetry))
        t_compile, dt = _compile_split(run, args)
        derived = (f"control={label},policy={pol},K={topo.num_tiers},"
                   f"M={topo.num_servers},horizon={horizon},"
                   f"telemetry={bool(telemetry)}")
        rows.append((f"sim_slots_per_sec_control_{label}", horizon / dt,
                     derived))
        rows.append((f"sim_compile_sec_control_{label}", t_compile,
                     derived))
    return rows


def bench_replication(fast: bool = True):
    """Replication-lifecycle throughput: simulator slots/sec of the default
    policy under every registered replication controller, with the
    server_loss scenario engaged so the lifecycle machinery (chunk
    catalogue, migration lanes, repair scans) is actually in the scan body.

    The `fixed`+static row is the bitwise-pinned passthrough (no lifecycle
    state in the carry at all), included as the zero-cost reference.
    """
    import jax
    from repro.core import locality as loc, simulator as sim
    from repro.replication import available_replications

    horizon = 2_000 if fast else 20_000
    topo, rates = loc.Topology(24, 6), loc.Rates()
    cfg = sim.SimConfig(topo=topo, true_rates=rates, p_hot=0.5,
                        max_arrivals=24, horizon=horizon,
                        warmup=horizon // 4)
    cap = loc.capacity_hot_rack(topo, rates, cfg.p_hot)
    est = sim.make_estimates(cfg, "network", 0.0, -1)
    args = (np.float32(0.7 * cap), est.astype(np.float32), np.uint32(0))
    arms = [("fixed", "static")]
    arms += [(ctrl, "server_loss") for ctrl in available_replications()]
    rows = []
    for ctrl, scen in arms:
        run = jax.jit(sim._build_run("balanced_pandas", cfg, scenario=scen,
                                     replication=ctrl))
        t_compile, dt = _compile_split(run, args)
        derived = (f"replication={ctrl},scenario={scen},"
                   f"policy=balanced_pandas,K={topo.num_tiers},"
                   f"M={topo.num_servers},horizon={horizon}")
        rows.append((f"sim_slots_per_sec_replication_{ctrl}_{scen}",
                     horizon / dt, derived))
        rows.append((f"sim_compile_sec_replication_{ctrl}_{scen}",
                     t_compile, derived))
    return rows

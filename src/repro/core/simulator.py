"""Vectorized discrete-time simulator (paper §4 experimental engine).

One `jax.lax.scan` over time slots per configuration; `jax.vmap` over the
sweep grid (load x error x seed).  All state is fixed-shape, so the whole
robustness study compiles to a single XLA program.

Scenarios (`repro.workloads`): every run plays back a declarative
piecewise schedule of workload knobs — arrival-rate multiplier, hot
fraction, hot rack, per-server/per-tier true-rate multipliers — gathered
per slot from compiled fixed-shape arrays (`slot_knobs`).  The simulator
itself contains zero per-scenario branching: the default ``"static"``
scenario multiplies every knob by 1.0 and reproduces the pre-scenario
sample paths bitwise (common random numbers preserved across scenarios and
policies alike).

The simulator is algorithm-agnostic: it drives any registered `SlotPolicy`
(see `core/policy.py`) and accepts a policy name, a `PolicyConfig` carrying
per-policy options (e.g. ``PolicyConfig("fifo", {"cap": 4096})``,
``PolicyConfig("pandas_po2", {"d": 4})``), or a policy instance.  Per-policy
metrics (FIFO's drop counter) are merged into the output via
`SlotPolicy.extra_metrics`.

Mean task completion time is measured via Little's law:
``W = mean(N_in_system over measurement window) / lambda_total`` (slots),
exact for stationary ergodic systems.  Divergence (instability / outside the
capacity region) is visible as ``final_n`` growing with the horizon and as
throughput < arrival rate.

Error models for the estimated rates (see balanced_pandas.py docstring for
the scale-invariance finding that motivates them):
  - "uniform":    est = true * (1 +/- eps) for all three tiers — provably a
                  no-op for PANDAS/MW decisions; kept as the control arm.
  - "network":    alpha known exactly; beta, gamma scaled by (1 +/- eps) —
                  mis-estimated network depreciation (the realistic reading
                  of the paper's experiment; used for the figure benches).
  - "per_server": each server's three estimates carry iid multipliers in
                  [1-eps, 1] (sign<0) or [1, 1+eps] (sign>0).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.control import ControlLike, resolve_control
from repro.core import locality as loc
from repro.core.policy import PolicyLike, make_policy
from repro import workloads as wl
from repro.placement import PlacementLike, make_placement
from repro.replication import ReplicationLike, make_replication
from repro.telemetry import (SimTelemetry, TelemetryLike,
                             as_telemetry_config, maybe_span)


@dataclasses.dataclass(frozen=True)
class SimConfig:
    topo: loc.Topology
    true_rates: loc.Rates
    p_hot: float = 0.5
    max_arrivals: int = 24
    horizon: int = 40_000
    warmup: int = 10_000

    def __post_init__(self):
        # Same guard as loc.Traffic: p_hot feeds bernoulli via the compiled
        # scenario schedule, and a negative value would flow silently.
        if not 0.0 <= self.p_hot <= 1.0:
            raise ValueError(f"p_hot must be in [0, 1], got {self.p_hot}")
        if self.max_arrivals < 1:
            raise ValueError(
                f"max_arrivals must be >= 1, got {self.max_arrivals}")
        if not 0 <= self.warmup < self.horizon:
            raise ValueError(f"need 0 <= warmup < horizon, got "
                             f"warmup={self.warmup} horizon={self.horizon}")
        # Rate vector and hierarchy must agree on the tier count, and every
        # rack must be able to hold a hot task's replica set (the sampler
        # draws NUM_REPLICAS distinct servers from one rack).
        if self.true_rates.num_tiers != self.topo.num_tiers:
            raise ValueError(
                f"true_rates have {self.true_rates.num_tiers} tiers but the "
                f"topology has {self.topo.num_tiers}")
        if self.topo.min_rack_size < loc.NUM_REPLICAS:
            raise ValueError(
                f"every rack needs >= {loc.NUM_REPLICAS} servers for "
                f"hot-rack types; smallest rack has "
                f"{self.topo.min_rack_size}")


def default_config(**kw) -> SimConfig:
    """Paper-scale default: 24 servers in 4 racks, hot-rack traffic."""
    return SimConfig(topo=loc.Topology(24, 6), true_rates=loc.Rates(), **kw)


def make_estimates(cfg: SimConfig, mode: str, eps: float, sign: int,
                   seed: int = 0) -> np.ndarray:
    """(M, K) estimated rates for one error setting.  sign: -1 lower, +1 higher.

    "network" scales every non-local tier (the rack/pod/DCN rates) and
    leaves the local rate exact, generalizing the 3-tier beta/gamma error.
    """
    m = cfg.topo.num_servers
    k = cfg.true_rates.num_tiers
    true_k = np.asarray(cfg.true_rates.values, np.float32)
    if mode == "uniform":
        mult = np.full((m, k), 1.0 + sign * eps, np.float32)
    elif mode == "network":
        mult = np.ones((m, k), np.float32)
        mult[:, 1:] = 1.0 + sign * eps
    elif mode == "per_server":
        rng = np.random.default_rng(seed)
        u = rng.uniform(0.0, eps, size=(m, k)).astype(np.float32)
        mult = 1.0 + sign * u
    else:
        raise ValueError(f"unknown error mode {mode!r}")
    est = true_k[None, :] * mult
    return np.clip(est, 1e-3, 1.0)


def _merge_metrics(out: Dict[str, Any], extra: Dict[str, Any],
                   source: str) -> None:
    """Merge `extra` into the metrics dict, refusing to silently overwrite
    a key another layer already produced (policy extra_metrics vs
    replication vs telemetry vs the core Little's-law scalars)."""
    for k in extra:
        if k in out:
            raise ValueError(
                f"{source} metric key {k!r} collides with an existing "
                f"metrics key; rename it (existing keys: {sorted(out)})")
    out.update(extra)


def _build_run(policy_like: PolicyLike, cfg: SimConfig,
               scenario: wl.ScenarioLike = None,
               placement: PlacementLike = None,
               replication: ReplicationLike = None,
               telemetry: TelemetryLike = None,
               control: ControlLike = None):
    """Returns jit-able run(lam_total, est(M,3), seed) -> metrics dict.

    `scenario` (name / ScenarioConfig / Scenario; None -> "static") compiles
    to fixed-shape per-segment arrays gathered once per slot — the only
    scenario seam in the simulator, shared by every policy.

    `placement` (name / PlacementConfig / PlacementPolicy; None ->
    "uniform") compiles to the per-task replica sampling distribution
    (`repro.placement`) the arrival stream draws task types from; the
    default reproduces the classic i.i.d.-uniform draws bitwise.

    `replication` (name / ReplicationConfig / ReplicationController; None
    -> "fixed") selects the replication-lifecycle controller
    (`repro.replication`).  The machinery only engages when the
    controller is dynamic or the scenario carries a failure track
    (``server_loss`` / ``rack_loss``) — a compile-time Python fact, so
    ``"fixed"`` with no failures runs the exact pre-replication step and
    stays bitwise-identical (same keys, same metrics keys; pinned by
    tests/test_replication.py).  In machinery mode the lifecycle rides
    the scan carry: dead servers serve at rate 0 and lose their
    replicas, migration endpoints serve at the contention multiplier,
    and availability / data-loss metrics join the output dict.

    `telemetry` (None / True / TelemetryConfig; `repro.telemetry`)
    compiles the in-scan recorders into the step: a FIFO-coupled sojourn
    histogram (-> ``delay_p50/p95/p99``), a queue-length histogram, and
    downsampled time series.  ``None`` compiles nothing (the pre-telemetry
    step, bitwise); when on, the recorder consumes no random bits, so the
    sample path is still bitwise-identical — only new metrics keys appear
    (both facts pinned in tests/test_telemetry.py).

    `control` (None / name / ControlConfig / Controller / sequence;
    `repro.control`) engages the control plane: load generation reshapes
    the offered rate, admission trims the fixed-shape arrival lane mask
    BEFORE routing (shed tasks never touch a queue or the telemetry
    sojourn pairing), and autoscaling hands mask-aware policies a
    per-slot (M,) routable-server mask (descaled servers drain — distinct
    from the replication ``alive`` track, where dead servers stop serving
    and lose replicas).  ``None`` compiles nothing: the exact pre-control
    step, bitwise for every policy (pinned in tests/test_control.py).
    When engaged, ``ctl_*`` metrics join the output and ``mean_delay``'s
    Little's-law denominator switches from the configured rate to the
    MEASURED admitted rate (the configured lam no longer equals what
    entered the system).  SLO-conditioned policies (``uses_signals``)
    additionally receive the recorder's live p99 each slot when
    ``telemetry=`` is on.
    """
    policy = make_policy(policy_like)
    topo, true_rates = cfg.topo, cfg.true_rates
    rack_of = jnp.asarray(topo.rack_of, jnp.int32)
    ancestors = jnp.asarray(topo.ancestors, jnp.int32)  # (depth, M)
    true_k = true_rates.as_array()
    plc = make_placement(placement)
    sample_types = plc.build_sampler(topo)
    sched = wl.compile_schedule(wl.make_scenario(scenario), topo,
                                cfg.horizon, cfg.p_hot)
    ctrl = make_replication(replication)
    rep_sim = None
    if not (ctrl.is_static and sched.alive is None):
        rep_sim = ctrl.build_sim(topo, np.asarray(true_rates.values), plc)
    # Telemetry (repro.telemetry): in-scan recorders for delay/queue-length
    # distributions and downsampled time series.  `None` compiles nothing
    # (the pre-telemetry step, bitwise); when configured, the recorder is
    # pure observation — it consumes no random bits, so the sample path is
    # STILL bitwise-identical and only new metrics keys appear.
    tel = None
    if telemetry is not None and telemetry is not False:
        tel_tracks = []
        if rep_sim is not None:
            tel_tracks += ["alive_servers", "open_lanes"]
        tel_tracks += sorted(policy.telemetry_gauges(
            policy.init_state(topo)))
        tel = SimTelemetry(as_telemetry_config(telemetry), cfg.horizon,
                           cfg.warmup, topo.num_servers, cfg.max_arrivals,
                           tuple(tel_tracks))
    # Control plane (repro.control): None compiles nothing — the exact
    # pre-control step (bitwise).  Engaged, its state rides the scan carry
    # between the replication and telemetry slices.
    plane = resolve_control(control)
    ctl = None
    if plane is not None:
        ctl = plane.build_sim(topo, cfg, sched,
                              float(np.asarray(true_rates.values)[0]))
        if ctl.has_mask and not policy.supports_server_mask:
            raise ValueError(
                f"control plane {plane.describe()!r} autoscales, but policy "
                f"{policy.name!r} does not accept a server mask "
                f"(supports_server_mask=False); drop the autoscale "
                f"controller or pick a mask-aware policy")
    uses_signals = bool(getattr(policy, "uses_signals", False)) \
        and tel is not None
    # Carry layout: (state, mean_n, n_meas, completions)[+rep][+ctl][+tel].
    i_rep = 4 if rep_sim is not None else None
    i_ctl = 4 + (rep_sim is not None) if ctl is not None else None
    i_tel = 4 + (rep_sim is not None) + (ctl is not None) \
        if tel is not None else None
    # Little's-law denominator: the offered rate over the measurement
    # window is lam_total x the window's mean arrival multiplier (exactly
    # 1.0 for the static scenario and any unit-mean modulation).
    lam_scale = wl.mean_lam_mult_over(sched, cfg.warmup, cfg.horizon)
    init = functools.partial(policy.init_state, topo)

    def run_body(lam_total, est, seed):
        base = jax.random.PRNGKey(seed)

        def step(carry, t):
            state, mean_n, n_meas, completions = carry[:4]
            knobs = wl.slot_knobs(sched, t)
            key_t = jax.random.fold_in(base, t)
            k_arr, k_algo = jax.random.split(key_t)
            if tel is not None or ctl is not None:
                # observed BEFORE this slot's arrivals/service touch state
                n_prev = policy.num_in_system(state).astype(jnp.int32)
            if ctl is not None:
                # loadgen shapes the offered rate (closed loop gates on the
                # POLICY's in-system count, exact even under policy drops)
                lam_t, arr_cap = ctl.offered_lam(n_prev, lam_total, knobs)
            else:
                lam_t = lam_total * knobs.lam_mult
            # Arrival stream depends only on (seed, t) and the scenario:
            # identical across policies -> paired comparisons (common
            # random numbers).  The control plane consumes no random bits,
            # so CRN coupling survives engagement too.
            with jax.named_scope("sim.arrivals"):
                types, active = loc.sample_arrivals_at(
                    k_arr, rack_of, lam_t, knobs.p_hot,
                    knobs.hot_rack, cfg.max_arrivals, knobs.rack_weights,
                    type_sampler=sample_types)
            server_mask = None
            if ctl is not None:
                # admission trims the lane mask BEFORE routing; autoscale
                # computes this slot's routable-server mask
                ctl_state, active, server_mask = ctl.pre(
                    carry[i_ctl], active, arr_cap, n_prev, lam_t,
                    t >= cfg.warmup)
            true_mk = true_k[None, :] * knobs.rate_mult
            if rep_sim is not None:
                alive = knobs.alive if knobs.alive is not None \
                    else jnp.ones(topo.num_servers, jnp.float32)
                rep_state, fg_mult = rep_sim.step(
                    carry[i_rep], alive, key_t, active, t >= cfg.warmup)
                true_mk = true_mk * fg_mult[:, None]
            step_kw = {}
            if server_mask is not None:
                step_kw["server_mask"] = server_mask
            if uses_signals:
                step_kw["signals"] = {
                    "delay_p99": tel.live_quantile(carry[i_tel], 0.99)}
            state, compl = policy.slot_step(state, k_algo, types, active,
                                            est, true_mk, ancestors,
                                            **step_kw)
            n = policy.num_in_system(state).astype(jnp.float32)
            in_window = (t >= cfg.warmup).astype(jnp.float32)
            n_meas = n_meas + in_window
            mean_n = mean_n + in_window * (n - mean_n) / jnp.maximum(n_meas, 1.0)
            completions = completions + compl * (t >= cfg.warmup)
            out_carry = (state, mean_n, n_meas, completions)
            if rep_sim is not None:
                out_carry += (rep_state,)
            if ctl is not None:
                out_carry += (ctl_state,)
            if tel is not None:
                # admissions inferred from the state delta, so arrivals the
                # policy rejected (FIFO's drops) never enter the sojourn
                # pairing; pure observation of the post-step state
                n_now = policy.num_in_system(state).astype(jnp.int32)
                extras = dict(policy.telemetry_gauges(state))
                if rep_sim is not None:
                    extras["alive_servers"] = jnp.sum(
                        alive > 0.5).astype(jnp.float32)
                    extras["open_lanes"] = jnp.sum(
                        rep_state.lane_left > 0.0).astype(jnp.float32)
                out_carry += (tel.record(carry[i_tel], t,
                                         n_now - n_prev + compl,
                                         compl, n_now, extras),)
            return out_carry, ()

        carry0 = (init(), jnp.float32(0.0), jnp.float32(0.0), jnp.int32(0))
        if rep_sim is not None:
            carry0 += (rep_sim.init(),)
        if ctl is not None:
            carry0 += (ctl.init(),)
        if tel is not None:
            carry0 += (tel.init(),)
        carry, _ = jax.lax.scan(step, carry0, jnp.arange(cfg.horizon))
        state, mean_n, n_meas, completions = carry[:4]
        # Little's law needs a positive offered rate; lam_total == 0 used
        # to divide straight to inf — flag it as NaN instead (the host-side
        # drivers additionally reject negative loads outright).
        denom = lam_total * lam_scale
        out = {
            "mean_n": mean_n,
            "mean_delay": jnp.where(denom > 0, mean_n / denom, jnp.nan),
            "throughput": completions / jnp.maximum(n_meas, 1.0),
            "final_n": policy.num_in_system(state).astype(jnp.float32),
        }
        if ctl is not None:
            # Control reshapes the arrival stream (closed loop, shedding),
            # so Little's law must divide by what actually ENTERED the
            # system: the measured in-window admitted rate.
            adm_rate = ctl.measured_rate(carry[i_ctl], n_meas)
            out["mean_delay"] = jnp.where(adm_rate > 0, mean_n / adm_rate,
                                          jnp.nan)
        _merge_metrics(out, policy.extra_metrics(state),
                       "SlotPolicy.extra_metrics")
        if rep_sim is not None:
            _merge_metrics(out, rep_sim.metrics(carry[i_rep]),
                           "replication lifecycle")
        if ctl is not None:
            _merge_metrics(out, ctl.metrics(carry[i_ctl]), "control plane")
        if tel is not None:
            _merge_metrics(out, tel.metrics(carry[i_tel]), "telemetry")
        return out

    def run(lam_total, est, seed):
        # runs only while JAX traces the program: the trace's count of
        # `sim.trace` spans is the count of program traces
        with maybe_span(None, "sim.trace"):
            return run_body(lam_total, est, seed)

    return run


# Jitted dense study programs, kept per configuration and dropped least
# recently used first.  A repeated `sweep` or `simulate` of one
# configuration at the same input shapes (a study cut into calls, as the
# benchmark window does; fig5/6 after fig3/4 in `benchmarks/figures.py`)
# reuses its `jax.jit` object, so JAX neither traces, lowers nor loads the
# program again; only the traced arguments (loads, estimates, seeds)
# change.  The studies in `core/robustness.py` call each configuration
# once, so within one of them every call builds its program.
# Programs, not compiled executables: `jax.clear_caches()` still sends the
# next call through a fresh trace.
_PROGRAMS_MAX = 32
_PROGRAMS: "collections.OrderedDict[tuple, Any]" = collections.OrderedDict()
_PROGRAMS_LOCK = threading.Lock()


def _value_key(x):
    """`x` as a hashable key of its value, with the type of every part, or
    None when it has no such form: a dict, an array, a mutable object or
    an instance that hashes by identity.  The types keep ``6000`` and
    ``6000.0`` (or ``True`` and ``1``) apart, which would build different
    programs though they compare equal."""
    if x is None or isinstance(x, (str, bool, int, float)):
        return (type(x), x)
    if isinstance(x, tuple):
        parts = tuple(_value_key(v) for v in x)
    elif isinstance(x, loc.Rates):
        parts = x.values
    elif (dataclasses.is_dataclass(x) and not isinstance(x, type)
          and x.__dataclass_params__.frozen and x.__dataclass_params__.eq):
        parts = tuple(_value_key(getattr(x, f.name))
                      for f in dataclasses.fields(x))
    else:
        return None
    return None if None in parts else (type(x), parts)


def _program(kind: str, policy_like: PolicyLike, cfg: SimConfig,
             scenario: wl.ScenarioLike, placement: PlacementLike,
             replication: ReplicationLike, telemetry: TelemetryLike,
             control: ControlLike):
    """The jitted program of a dense study: `run` for ``"simulate"``,
    `run` vmapped over (loads, estimates, seeds) for ``"sweep"``.

    Kept in `_PROGRAMS` under the value of every argument `_build_run`
    reads; when one of them has no value key (`_value_key`), the program
    is built and jitted fresh and not kept.  Input shapes are not part of
    the key: the `jax.jit` object keys its own compilations on them."""
    args = (policy_like, cfg, scenario, placement, replication, telemetry,
            control)
    key = _value_key((kind,) + args)
    if key is not None:
        with _PROGRAMS_LOCK:
            if key in _PROGRAMS:
                _PROGRAMS.move_to_end(key)
                return _PROGRAMS[key]
    run = _build_run(*args)
    if kind == "sweep":
        run = jax.vmap(jax.vmap(jax.vmap(run, (None, None, 0)),
                                (None, 0, None)), (0, None, None))
    program = jax.jit(run)
    if key is not None:
        with _PROGRAMS_LOCK:
            _PROGRAMS[key] = program
            if len(_PROGRAMS) > _PROGRAMS_MAX:
                _PROGRAMS.popitem(last=False)
    return program


def clear_program_cache() -> None:
    """Drop every kept dense program (for tests that count traces)."""
    with _PROGRAMS_LOCK:
        _PROGRAMS.clear()


def _fleet_engaged(fleet, policy, cfg, scenario, placement, replication,
                   telemetry, control=None) -> bool:
    """Resolve the ``fleet=`` seam shared by simulate/sweep.

    ``False`` -> dense, always.  ``True`` / a FleetConfig -> fleet path,
    raising if the configuration has no fleet step.  ``None`` (default)
    -> auto: fleet only when supported AND the topology is at least
    ``sharding.sim.FLEET_AUTO_THRESHOLD`` servers, so every paper-scale
    run keeps the faithful (bitwise-pinned) dense path.  A control plane
    always pins the dense path (the fleet step has no control seam yet).
    """
    if control is not None:
        if fleet is True or (fleet is not None and fleet is not False):
            raise ValueError("fleet=True is not supported with control=; "
                             "the fleet step has no control-plane seam yet")
        return False
    if fleet is False:
        return False
    from repro.sharding import sim as fleet_sim  # lazy: avoids a cycle
    reason = fleet_sim.fleet_supported(policy, cfg, scenario, placement,
                                       replication, telemetry)
    if fleet is None:
        return (reason is None and cfg.topo.num_servers
                >= fleet_sim.FLEET_AUTO_THRESHOLD)
    if reason is not None:
        raise ValueError(f"fleet=True requested but unsupported: {reason}")
    return True


def simulate(policy: PolicyLike, cfg: SimConfig, lam_total: float,
             est: np.ndarray, seed: int = 0,
             scenario: wl.ScenarioLike = None,
             placement: PlacementLike = None,
             replication: ReplicationLike = None,
             telemetry: TelemetryLike = None,
             control: ControlLike = None,
             fleet=None) -> Dict[str, Any]:
    """Single-configuration run (jit-compiled).  ``lam_total == 0`` yields
    ``mean_delay = NaN`` (Little's law is undefined); negative loads are
    rejected here.  Scalar metrics come back as floats; array-valued
    telemetry metrics (histograms, the series) as numpy arrays.

    ``control`` engages the control plane (`repro.control`: load
    generation, admission, autoscaling); ``None`` compiles the exact
    pre-control program.  ``fleet`` selects the fleet-scale backend
    (`repro.sharding.sim`): ``None`` auto-engages it for supported
    configurations at >= 1024 servers, ``True``/`FleetConfig` forces it
    (raising when the configuration has no fleet step), ``False`` pins
    the dense path.

    The dense program is kept per configuration under the same key as
    `sweep`'s, so a repeated call with a new ``lam_total``, ``est`` or
    ``seed`` is not traced again; an argument with no value key gets a
    program built fresh.
    """
    if lam_total < 0:
        raise ValueError(f"lam_total must be >= 0, got {lam_total}")
    if _fleet_engaged(fleet, policy, cfg, scenario, placement, replication,
                      telemetry, control):
        from repro.sharding import sim as fleet_sim
        fleet_cfg, weights = fleet_sim.stationary_traffic(cfg, scenario)
        return fleet_sim.fleet_simulate(policy, fleet_cfg, lam_total, est,
                                        seed, fleet, rack_weights=weights)
    run = _program("simulate", policy, cfg, scenario, placement,
                   replication, telemetry, control)
    out = run(jnp.float32(lam_total), jnp.asarray(est, jnp.float32),
              jnp.asarray(seed, jnp.uint32))
    res: Dict[str, Any] = {}
    for k, v in out.items():
        arr = np.asarray(v)
        res[k] = float(arr) if arr.ndim == 0 else arr
    return res


def sweep(policy: PolicyLike, cfg: SimConfig, lam_grid: np.ndarray,
          est_stack: np.ndarray, seeds: np.ndarray,
          scenario: wl.ScenarioLike = None,
          placement: PlacementLike = None,
          replication: ReplicationLike = None,
          telemetry: TelemetryLike = None,
          control: ControlLike = None,
          fleet=None) -> Dict[str, np.ndarray]:
    """Full cartesian sweep, vmapped: results have shape (L, E, S).

    lam_grid: (L,) loads; est_stack: (E, M, K); seeds: (S,).  The scenario
    schedule, the compiled placement sampler, the replication machinery,
    and the telemetry recorder are closure constants — their shapes carry
    no batch dimension, so the whole grid still compiles to one vmapped
    XLA program (lifecycle and recorder state vmap through the scan
    carry).  Telemetry metrics batch like everything else: scalars
    (delay_p50/p95/p99) come back (L, E, S), histograms (L, E, S, bins+1),
    the series (L, E, S, T_s, n_tracks).

    The jitted program is kept per configuration (the 32 most recently
    used): the key is the value of ``policy``, ``cfg``, ``scenario``,
    ``placement``, ``replication``, ``telemetry`` and ``control``, each
    part with its type.  A repeated call with new loads, estimates or
    seeds of the same shapes neither traces, lowers nor loads the
    program again.  An argument with no value key, such as a
    `PolicyConfig` whose options are a dict, an array or a policy
    instance, gets a program built and jitted fresh on every call.
    """
    if np.any(np.asarray(lam_grid) < 0):
        raise ValueError(f"lam_grid must be >= 0, got {lam_grid}")
    if _fleet_engaged(fleet, policy, cfg, scenario, placement, replication,
                      telemetry, control):
        from repro.sharding import sim as fleet_sim
        fleet_cfg, weights = fleet_sim.stationary_traffic(cfg, scenario)
        return fleet_sim.fleet_sweep(policy, fleet_cfg, lam_grid, est_stack,
                                     seeds, fleet, rack_weights=weights)
    # sim.prepare: look up the kept program (on a miss: build, trace, lower,
    # compile or read the cache), enqueue; sim.fetch: wait for the device
    # and copy the metrics to the host
    with maybe_span(None, "sim.prepare"):
        f = _program("sweep", policy, cfg, scenario, placement, replication,
                     telemetry, control)
        out = f(jnp.asarray(lam_grid, jnp.float32),
                jnp.asarray(est_stack, jnp.float32),
                jnp.asarray(seeds, jnp.uint32))
    with maybe_span(None, "sim.fetch"):
        return {k: np.asarray(v) for k, v in out.items()}

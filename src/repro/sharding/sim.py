"""Fleet-scale fast path for the discrete-time simulator (10k+ servers).

The faithful simulator (`core/simulator.py`) routes each slot's arrivals
*sequentially* — a `fori_loop` of B ≈ 2·lam O(M) argmins — and samples
task types with (B, M) Gumbel top-k.  At M = 10^4 that is ~50k tiny XLA
ops and ~5·10^7 Gumbels dispatched per slot on CPU: the path *runs* but
at under 1 slot/s.  This module is the fleet-engaged backend: same
discrete-time model, same metrics keys, O(B + M·depth) work per slot and
a few hundred fused ops per slot, so 10k-server studies run at hundreds
of slots/s (see docs/scaling.md for the before/after curve and the
dispatch-bound performance model).

What changes (and what is pinned to hold still):

* **Arrivals** — O(B) distinct-3 sampler (uniform-offset trick) instead
  of (B, M) Gumbel top-k.  Statistically identical task-type law; the
  sample path is NOT bitwise the dense path's (different RNG layout), so
  the fleet path is held to the *delay bands* of tests/test_fleet_scale.py
  rather than bitwise pins.  The dense sub-threshold path is untouched
  and stays bitwise (pinned per policy).
* **Routing** — one workload snapshot per round instead of per-arrival
  updates.  The private phase (every tier better than remote) is an
  exact per-level `segment_min`: a server whose true tier is deeper than
  the level scanned always scores strictly lower at its true tier (rates
  decrease in the tier and the -rate*1e-6 term breaks toward the faster
  tier), so per-group minima at each level combine into the exact
  private argmin — no exclusion machinery.  (Assumes per-server estimated
  rates decrease in the tier, which every shipped error model preserves.)
  On TPU the fused Pallas kernel (`kernels/slot_step.py`) computes the
  same surface in one launch; on CPU the segment-min form wins (it is
  O(M·depth), not O(B·M)).
* **The remote pool** — a snapshot argmin would pile every pool-bound
  task of a slot onto one server.  Instead the slot's pool assignment is
  solved as a *water-filling fixed point*: server m enters the pool at
  score p_m = W_m/r_m - r_m*1e-6 and each absorbed task raises it by
  d_m = 1/r_m^2, so at water level y it absorbs
  c_m(y) = max(0, ceil((y - p_m)/d_m)) tasks; tasks prefer their private
  option iff s_priv <= y.  Bisecting y to the smallest level with
  sum_m c_m(y) >= #{active: s_priv > y} reproduces the sequential
  greedy's fluid limit.  Private fill-up is modeled the same way: the
  r-th task (0-based) claiming private server m stays private only while
  s_priv + r/rate^2 <= y — the rank clamp that stops a hot rack from
  absorbing a whole slot's hot batch in one snapshot.
* **The scan hot loop** — the horizon is cut into fixed-size chunks run
  by one jitted function with a *donated* carry (`donate_argnums=0`), so
  per-chunk buffers are reused instead of reallocated; inside each chunk
  `lax.scan(..., unroll=)` amortizes dispatch.  Slots past the horizon
  are frozen (the carry is re-selected), so ragged horizons compile
  exactly one chunk program.  Arrival scatters touch B rows
  (`q.at[srv, tier].add`), never an (M, K)-dense one-hot — the
  event-driven update shape.
* **Sweeps** — `fleet_sweep` vmaps the chunk function over the flattened
  (load x error x seed) grid: one compile for the whole study.

Service/scheduling dynamics reuse `core.balanced_pandas.serve_and_schedule`
verbatim (vectorized already).  Supported configurations: policies
`balanced_pandas` / `pandas_po2`, a stationary scenario (one segment that
may set the hot fraction and per-rack arrival weights — ``static``,
``hot_racks`` — see `stationary_traffic`), uniform placement, static
replication, no telemetry — `fleet_supported` reports why anything else
must take the dense path, and `core.simulator.simulate/sweep` fall back
(or raise, when ``fleet=True`` was explicit).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import balanced_pandas as bp
from repro.core import locality as loc
from repro.core.policy import PolicyLike, make_policy
from repro.kernels import ops as kops
from repro.telemetry import maybe_span

# Auto-engagement floor for core.simulator's ``fleet=None``: every
# paper-scale configuration (M <= a few hundred) stays on the faithful
# dense path; only genuinely fleet-sized topologies switch.
FLEET_AUTO_THRESHOLD = 1024

_SUPPORTED_POLICIES = ("balanced_pandas", "pandas_po2")


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Knobs of the fleet fast path.

    chunk      -- slots per donated-carry jit call (the horizon is cut
                  into ceil(horizon/chunk) identical chunk programs)
    unroll     -- lax.scan unroll factor inside a chunk
    rounds     -- private-routing retry passes per slot (Balanced-PANDAS
                  only): each pass commits the clamp winners and the
                  losers re-route against the updated workload, so
                  collision overflow lands on its next-best private
                  option instead of spilling to the remote pool.  2 is
                  enough to hold the delay bands pinned in
                  tests/test_fleet_scale.py; 1 is the cheapest/loosest.
    fill_iters -- bisection iterations for the pool water level
    use_pallas -- force the fused Pallas route kernel on/off
                  (None = auto: on only on TPU; the CPU hot loop uses
                  the O(M·depth) segment-min form)
    """

    chunk: int = 128
    unroll: int = 4
    rounds: int = 2
    fill_iters: int = 32
    use_pallas: Optional[bool] = None

    def __post_init__(self):
        if self.chunk < 1 or self.unroll < 1 or self.rounds < 1:
            raise ValueError(f"chunk/unroll/rounds must be >= 1, got "
                             f"{self.chunk}/{self.unroll}/{self.rounds}")
        if self.fill_iters < 8:
            raise ValueError(f"fill_iters must be >= 8 for a usable water "
                             f"level, got {self.fill_iters}")


FleetLike = Union[None, bool, FleetConfig]


def as_fleet_config(spec: FleetLike) -> FleetConfig:
    """None/True -> defaults; a FleetConfig passes through."""
    if isinstance(spec, FleetConfig):
        return spec
    return FleetConfig()


@dataclasses.dataclass(frozen=True)
class FleetCtx:
    """Static per-topology constants the hot loop closes over."""

    num_servers: int
    num_tiers: int
    depth: int
    group_counts: Tuple[int, ...]   # groups per level
    hot_rack_size: int              # rack 0 size (M for a depth-0 fleet)
    anc: Any                        # (depth, M) int32 device array
    gids: Tuple[Any, ...]           # per-level (M,) group-id rows
    # per-rack arrival weights (None: hot tasks live in rack 0)
    rack_cum: Any = None            # (R,) f32 cumulative weights, see
    #                                 `cumulative_weights`
    rack_start: Any = None          # (R,) int32 first server of each rack
    rack_size: Any = None           # (R,) int32 servers in each rack


def cumulative_weights(rack_weights) -> np.ndarray:
    """(R,) float32 cumulative shares of the per-rack weights: the
    running sums in float64 over the last of them, rounded once to
    float32, and 1.0 from the last rack of positive weight on.  A hot
    task's rack is the number of entries at or below a uniform draw in
    [0, 1), so a rack of weight 0 is never drawn."""
    w = np.asarray(rack_weights, np.float64)
    cum = np.cumsum(w)
    cum = cum / cum[-1]
    cum[np.flatnonzero(w > 0)[-1]:] = 1.0
    return cum.astype(np.float32)


def make_ctx(topo: loc.Topology, rack_weights=None) -> FleetCtx:
    anc = jnp.asarray(topo.ancestors, jnp.int32)
    racks = {}
    if rack_weights is not None:
        sizes = np.asarray(topo.group_sizes[0] if topo.depth
                           else (topo.num_servers,), np.int32)
        racks = dict(rack_cum=jnp.asarray(cumulative_weights(rack_weights)),
                     rack_start=jnp.asarray(np.cumsum(sizes) - sizes),
                     rack_size=jnp.asarray(sizes))
    return FleetCtx(
        num_servers=topo.num_servers,
        num_tiers=topo.num_tiers,
        depth=topo.depth,
        group_counts=tuple(len(topo.group_sizes[l])
                           for l in range(topo.depth)),
        hot_rack_size=(topo.group_sizes[0][0] if topo.depth
                       else topo.num_servers),
        anc=anc,
        gids=tuple(anc[l] for l in range(topo.depth)),
        **racks,
    )


def stationary_traffic(cfg, scenario=None):
    """The arrival law the fleet path runs for `scenario`:
    ``(cfg, rack_weights)``, with the scenario's hot fraction in ``cfg``
    and its per-rack weights resized to the rack count (a tuple of R
    floats), or None where hot tasks live in rack 0.

    Only a stationary scenario has one: a single segment with no
    arrival-rate, tier or per-server rate multiplier and no failures.
    Raises ValueError naming what else the scenario asks for."""
    from repro import workloads as wl
    scn = wl.make_scenario(scenario)
    if len(scn.segments) != 1:
        raise ValueError(f"scenario {scn.name!r} has {len(scn.segments)} "
                         f"segments; only a stationary scenario (one "
                         f"segment) is fleet-compiled")
    sched = wl.compile_schedule(scn, cfg.topo, cfg.horizon, cfg.p_hot)
    if np.asarray(sched.lam_mult)[0] != 1.0:
        raise ValueError(f"scenario {scn.name!r} scales the arrival rate; "
                         f"the fleet path runs the configured rate")
    if not np.all(np.asarray(sched.rate_mult) == 1.0):
        raise ValueError(f"scenario {scn.name!r} changes true rates "
                         f"(tier_mult / slow_servers); the fleet step "
                         f"serves at the configured rates")
    if sched.alive is not None:
        raise ValueError(f"scenario {scn.name!r} has a failure track, which "
                         f"rides the dense replication machinery")
    seg = scn.segments[0]
    if seg.p_hot is not None:
        cfg = dataclasses.replace(cfg, p_hot=seg.p_hot)
    if sched.rack_weights is None:
        if seg.hot_rack % cfg.topo.num_racks:
            raise ValueError(f"scenario {scn.name!r} puts hot traffic on rack "
                             f"{seg.hot_rack}; a hot rack other than 0 "
                             f"reaches the fleet path only as rack_weights")
        return cfg, None
    weights = tuple(float(x) for x in np.asarray(sched.rack_weights)[0])
    sizes = (cfg.topo.group_sizes[0] if cfg.topo.depth
             else (cfg.topo.num_servers,))
    small = [r for r, (w, n) in enumerate(zip(weights, sizes))
             if w > 0 and n < loc.NUM_REPLICAS]
    if small:
        raise ValueError(f"racks {small[:5]} carry hot weight but hold fewer "
                         f"than {loc.NUM_REPLICAS} servers")
    return cfg, weights


def fleet_supported(policy_like: PolicyLike, cfg, scenario=None,
                    placement=None, replication=None,
                    telemetry=None) -> Optional[str]:
    """None when the fleet path can run this configuration, else the
    reason it cannot (the dense path must be used)."""
    policy = make_policy(policy_like)
    if policy.name not in _SUPPORTED_POLICIES:
        return (f"policy {policy.name!r} has no fleet step "
                f"(supported: {_SUPPORTED_POLICIES})")
    if telemetry is not None and telemetry is not False:
        return "telemetry recorders require the dense in-scan step"
    try:
        stationary_traffic(cfg, scenario)
    except ValueError as e:
        return str(e)
    from repro.placement import make_placement
    if make_placement(placement).name != "uniform":
        return "only uniform placement has a fleet sampler"
    from repro.replication import make_replication
    if not make_replication(replication).is_static:
        return "dynamic replication rides the dense scan carry"
    if cfg.topo.num_servers < loc.NUM_REPLICAS:
        return "need at least NUM_REPLICAS servers"
    return None


# ---------------------------------------------------------------------------
# O(B) arrival sampling (distinct-3 via the uniform-offset trick)
# ---------------------------------------------------------------------------


def _arrival_keys(key: jax.Array, ctx: FleetCtx):
    """(k_n, k_hot, k_rack, k_u) of one slot's arrivals: ``key`` splits
    into the count's key k_n and k_t; k_t splits into k_hot and k_u, or,
    with per-rack weights, into k_hot, k_rack and k_u (k_rack None
    without weights)."""
    k_n, k_t = jax.random.split(key)
    if ctx.rack_cum is None:
        k_hot, k_u = jax.random.split(k_t)
        return k_n, k_hot, None, k_u
    k_hot, k_rack, k_u = jax.random.split(k_t, 3)
    return k_n, k_hot, k_rack, k_u


def _hot_lanes(key: jax.Array, ctx: FleetCtx, p_hot: float, batch: int):
    """(B,) bool: the lanes whose task is hot, drawn from k_hot."""
    return jax.random.bernoulli(_arrival_keys(key, ctx)[1], p_hot, (batch,))


def _sample_arrivals(key: jax.Array, ctx: FleetCtx, lam, p_hot: float,
                     batch: int):
    """(types (B,3) i32 sorted, active (B,) bool) — same arrival law as
    `locality.sample_arrivals_at` under a stationary scenario (truncated
    Poisson count; hot tasks replica-set inside one rack, the rest
    uniform) in O(B) work instead of (B, M) Gumbels.

    Key discipline (`_arrival_keys`): n = min(poisson(k_n, lam), B) and
    lanes below n are active; hot = bernoulli(k_hot, p_hot, (B,)).
    Without rack weights a hot task's pool is rack 0.  With them, its
    rack is the number of `ctx.rack_cum` entries at or below
    uniform(k_rack, (B,)), and its pool is that rack's servers, counted
    from the rack's first server.  A cold task's pool is the fleet.
    Within a pool of S servers, u = uniform(k_u, (B, 3)) picks three
    distinct offsets: x0 = floor(u0 S), x1 = floor(u1 (S-1)) skipping
    x0, x2 = floor(u2 (S-2)) skipping both, each clipped below its
    range's end.
    """
    k_n, _, k_rack, k_u = _arrival_keys(key, ctx)
    n = jnp.minimum(jax.random.poisson(k_n, lam), batch)
    active = jnp.arange(batch) < n
    hot = _hot_lanes(key, ctx, p_hot, batch)
    if ctx.rack_cum is None:
        size = jnp.where(hot, ctx.hot_rack_size, ctx.num_servers
                         ).astype(jnp.float32)
    else:
        rack = jnp.searchsorted(ctx.rack_cum,
                                jax.random.uniform(k_rack, (batch,)),
                                side="right")
        size = jnp.where(hot, ctx.rack_size[rack], ctx.num_servers
                         ).astype(jnp.float32)
    r = jax.random.uniform(k_u, (batch, 3))
    x0 = jnp.minimum(jnp.floor(r[:, 0] * size), size - 1)
    x1 = jnp.minimum(jnp.floor(r[:, 1] * (size - 1)), size - 2)
    x1 = x1 + (x1 >= x0)
    lo, hi = jnp.minimum(x0, x1), jnp.maximum(x0, x1)
    x2 = jnp.minimum(jnp.floor(r[:, 2] * (size - 2)), size - 3)
    x2 = x2 + (x2 >= lo)
    x2 = x2 + (x2 >= hi)
    types = jnp.stack([x0, x1, x2], axis=1).astype(jnp.int32)
    if ctx.rack_cum is not None:
        types = types + jnp.where(hot, ctx.rack_start[rack], 0)[:, None]
    return jnp.sort(types, axis=1), active


# ---------------------------------------------------------------------------
# Private-phase routing: exact per-level segment-min (CPU) / fused kernel
# ---------------------------------------------------------------------------


def _segment_argmin(score, gid, ngroups: int, m: int):
    """Per-group (min, lowest index achieving it); gid rows are the
    contiguous `Topology.ancestors` levels, so indices are sorted."""
    gmin = jax.ops.segment_min(score, gid, num_segments=ngroups,
                               indices_are_sorted=True)
    hit = score == gmin[gid]
    sid = jnp.arange(score.shape[0], dtype=jnp.int32)
    gidx = jax.ops.segment_min(jnp.where(hit, sid, m), gid,
                               num_segments=ngroups, indices_are_sorted=True)
    return gmin, gidx


def _private_route_segmin(w, est, ctx: FleetCtx, locs):
    """Exact private argmin per task from per-level group minima.

    Level l's candidate scores every member of a local's level-l group at
    the tier-(l+1) rate.  A member whose true tier is shallower scores
    strictly lower at its true tier — rates decrease in the tier, and the
    -rate*1e-6 term also favors the faster tier — and that true-tier
    score is itself a candidate at the shallower level, so any candidate
    achieving the overall minimum is at its true tier.  Combining levels
    (locals first) by lexicographic (score, server index) therefore
    reproduces the full (B, M) surface's lowest-index argmin exactly,
    including cross-tier score ties.  Semantics contract:
    kernels/ref.fleet_route.
    """
    m = ctx.num_servers
    e0 = est[:, 0]
    sc_loc = w[locs] / e0[locs] - e0[locs] * 1e-6          # (B, 3)
    best_v = jnp.min(sc_loc, axis=1)
    hit = sc_loc == best_v[:, None]
    best_i = jnp.min(jnp.where(hit, locs, m), axis=1)
    best_t = jnp.zeros_like(best_i)
    for lvl in range(ctx.depth):
        rate = est[:, lvl + 1]
        sc = w / rate - rate * 1e-6                        # (M,)
        gmin, gidx = _segment_argmin(sc, ctx.gids[lvl],
                                     ctx.group_counts[lvl], m)
        tg = ctx.gids[lvl][locs]                           # (B, 3)
        cand_v = gmin[tg]
        cand_i = gidx[tg]
        cv = jnp.min(cand_v, axis=1)
        chit = cand_v == cv[:, None]
        ci = jnp.min(jnp.where(chit, cand_i, m), axis=1)
        better = (cv < best_v) | ((cv == best_v) & (ci < best_i))
        best_v = jnp.where(better, cv, best_v)
        best_i = jnp.where(better, ci, best_i)
        best_t = jnp.where(better, lvl + 1, best_t)
    return (best_i.astype(jnp.int32), best_t.astype(jnp.int32), best_v)


def _water_level(p, d, demand_fn, hi0, batch: int, iters: int):
    """Smallest y with sum_m c_m(y) >= demand(y), by bisection.

    c_m(y) = clip(ceil((y - p_m)/d_m), 0, B).  demand_fn must be
    non-increasing in y; returns the upper end (capacity >= demand
    guaranteed there)."""
    lo = jnp.min(p)
    hi = jnp.maximum(jnp.max(p), hi0) + batch * jnp.max(d)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cap = jnp.sum(jnp.clip(jnp.ceil((mid - p) / d), 0.0, float(batch)))
        ok = cap >= demand_fn(mid)
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return hi


def _route_batch_pandas(s: bp.PandasState, est, ctx: FleetCtx, locs, active,
                        fc: FleetConfig, use_pallas: bool):
    """One slot of Balanced-PANDAS fleet routing: `fc.rounds` retry passes
    of (private argmin + rank clamp) with the workload recomputed between
    passes, then one pool water-fill for whatever is left.

    Each pass commits the tasks whose filled private score stays under
    the water level; the losers retry against the *updated* workload, so
    a collision's overflow lands on its second-best private option —
    the sequential simulator's fallback behavior — instead of spilling
    straight to the (slower) remote pool.
    """
    m, k = ctx.num_servers, ctx.num_tiers
    batch = locs.shape[0]
    pending = active
    for r in range(fc.rounds):
        w = bp.workload(s, est)
        with jax.named_scope("sim.private"):
            if use_pallas:
                best_i, best_t, best_v = kops.fleet_route(
                    s.q, s.serving, est, ctx.anc, locs)
            else:
                best_i, best_t, best_v = _private_route_segmin(w, est, ctx,
                                                               locs)

        with jax.named_scope("sim.fill"):
            # pool (remote tier) water-fill parameters from the same
            # snapshot
            pr = est[:, k - 1]
            p = w / pr - pr * 1e-6
            d = 1.0 / (pr * pr)
            s_priv = jnp.where(pending, best_v, jnp.float32(-3e38))

            def demand(y):
                return jnp.sum((pending & (best_v > y)).astype(jnp.float32))

            y1 = _water_level(p, d, demand, jnp.max(s_priv), batch,
                              fc.fill_iters)

        # private rank clamp: the r-th claimant of a server stays private
        # only while its filled score is still under the water level
        go_raw = pending & (best_v <= y1)
        key_m = jnp.where(go_raw, best_i, m)
        order = jnp.argsort(key_m, stable=True)
        sk = key_m[order]
        first = jnp.searchsorted(sk, sk, side="left")
        rank = jnp.zeros((batch,), jnp.int32).at[order].set(
            (jnp.arange(batch) - first).astype(jnp.int32))
        e_at = est[best_i, best_t]
        stay = go_raw & (best_v + rank / (e_at * e_at) <= y1)

        if r < fc.rounds - 1:
            # commit this pass's winners; losers retry against updated W
            s = bp.PandasState(
                q=s.q.at[best_i, best_t].add(stay.astype(jnp.int32)),
                serving=s.serving)
            pending = pending & ~stay

    # final pass: pool assignment at the re-raised level
    pool = pending & ~stay
    with jax.named_scope("sim.fill"):
        n_pool = jnp.sum(pool.astype(jnp.float32))
        y2 = _water_level(p, d, lambda y: n_pool, jnp.max(s_priv), batch,
                          fc.fill_iters)
        caps = jnp.clip(jnp.ceil((y2 - p) / d), 0.0, float(batch)
                        ).astype(jnp.int32)
        cum = jnp.cumsum(caps)
        pool_rank = jnp.cumsum(pool.astype(jnp.int32)) - 1
        pool_srv = jnp.clip(jnp.searchsorted(cum, pool_rank, side="right"),
                            0, m - 1).astype(jnp.int32)

    srv = jnp.where(stay, best_i, pool_srv)
    tier = jnp.where(stay, best_t, k - 1)
    inc = pending.astype(jnp.int32)
    return bp.PandasState(q=s.q.at[srv, tier].add(inc), serving=s.serving)


def _route_batch_po2(s: bp.PandasState, est, ctx: FleetCtx, locs, active,
                     key: jax.Array, d_choices: int):
    """One snapshot round of power-of-d fleet routing: each task argmins
    over {3 locals} ∪ {d uniform candidates} directly (remote candidates
    allowed — no pool is needed, the d samples spread load by
    construction)."""
    m, k = ctx.num_servers, ctx.num_tiers
    batch = locs.shape[0]
    w = bp.workload(s, est)
    cand = jnp.floor(jax.random.uniform(key, (batch, d_choices)) * m
                     ).astype(jnp.int32)
    cand = jnp.minimum(cand, m - 1)
    cset = jnp.concatenate([locs, cand], axis=1)           # (B, 3+d)
    tier = jnp.full(cset.shape, k - 1, jnp.int32)
    for lvl in range(ctx.depth - 1, -1, -1):
        row = ctx.gids[lvl]
        share = jnp.any(row[cset][:, :, None] == row[locs][:, None, :],
                        axis=-1)
        tier = jnp.where(share, lvl + 1, tier)
    tier = jnp.where(jnp.any(cset[:, :, None] == locs[:, None, :], axis=-1),
                     0, tier)
    rate = est[cset, tier]                                 # (B, 3+d)
    score = w[cset] / rate - rate * 1e-6
    j = jnp.argmin(score, axis=1)
    rows = jnp.arange(batch)
    srv = cset[rows, j]
    inc = active.astype(jnp.int32)
    return bp.PandasState(q=s.q.at[srv, tier[rows, j]].add(inc),
                          serving=s.serving)


# ---------------------------------------------------------------------------
# Chunked donated-carry runner
# ---------------------------------------------------------------------------


class FleetCarry(tuple):
    """The chunk program's carry: (q (M,K) i32, serving (M,) i32, mean_n
    f32, n_meas f32, completions i32), which it unpacks, indexes and
    iterates as, plus two counters over the measured slots by name:
    `hot_arrived` (hot tasks that arrived) and `pool_placed` (tasks
    routed to the remote tier: for Balanced-PANDAS, those the pool's
    water-fill placed), both i32."""

    def __new__(cls, core, hot_arrived, pool_placed):
        self = super().__new__(cls, core)
        self.hot_arrived, self.pool_placed = hot_arrived, pool_placed
        return self

    @classmethod
    def of(cls, carry) -> "FleetCarry":
        """A FleetCarry as it is; a plain 5-tuple with counters at 0; a
        7-tuple with its last two as the counters."""
        if isinstance(carry, cls):
            return carry
        carry = tuple(carry)
        if len(carry) == 5:
            return cls(carry, jnp.int32(0), jnp.int32(0))
        return cls(carry[:5], *carry[5:])


jax.tree_util.register_pytree_node(
    FleetCarry, lambda c: (tuple(c) + (c.hot_arrived, c.pool_placed), None),
    lambda _, leaves: FleetCarry(leaves[:5], *leaves[5:]))


def _build_fleet_chunk(policy_like: PolicyLike, cfg, fc: FleetConfig,
                       rack_weights=None):
    """Returns (init() -> carry, chunk(carry, t0, lam, est, seed) -> carry).

    The carry is a `FleetCarry`; `chunk` also takes a plain 5-tuple (its
    counters start at 0) or 7-tuple.  `chunk` advances `fc.chunk` slots
    starting at slot t0; slots at t >= horizon are frozen (the carry
    re-selected), so the tail chunk reuses the same compiled program.
    `rack_weights` (R floats, `stationary_traffic`) spread hot tasks
    over racks; None keeps them in rack 0.  Jit it with
    ``donate_argnums=0`` and drive the horizon from a Python loop.
    """
    policy = make_policy(policy_like)
    if policy.name not in _SUPPORTED_POLICIES:
        raise ValueError(f"policy {policy.name!r} has no fleet step "
                         f"(supported: {_SUPPORTED_POLICIES})")
    d_choices = int(getattr(policy, "d", 0))
    ctx = make_ctx(cfg.topo, rack_weights)
    m, k = ctx.num_servers, ctx.num_tiers
    batch = cfg.max_arrivals
    true_k = cfg.true_rates.as_array()
    p_hot = float(cfg.p_hot)
    horizon, warmup = cfg.horizon, cfg.warmup
    use_pallas = kops._on_tpu() if fc.use_pallas is None else fc.use_pallas

    def init():
        return FleetCarry((jnp.zeros((m, k), jnp.int32),
                           jnp.zeros((m,), jnp.int32), jnp.float32(0.0),
                           jnp.float32(0.0), jnp.int32(0)),
                          jnp.int32(0), jnp.int32(0))

    def chunk(carry, t0, lam, est, seed):
        # runs only while JAX traces the program (see core/simulator)
        with maybe_span(None, "sim.trace"):
            return chunk_body(FleetCarry.of(carry), t0, lam, est, seed)

    def chunk_body(carry, t0, lam, est, seed):
        base_key = jax.random.PRNGKey(seed)

        def step(c, t):
            q, serving, mean_n, n_meas, compl = c
            s = bp.PandasState(q, serving)
            key_t = jax.random.fold_in(base_key, t)
            k_arr, k_algo = jax.random.split(key_t)
            with jax.named_scope("sim.arrivals"):
                types, active = _sample_arrivals(k_arr, ctx, lam, p_hot,
                                                 batch)
                hot_n = jnp.sum(_hot_lanes(k_arr, ctx, p_hot, batch)
                                & active, dtype=jnp.int32)
            remote_before = jnp.sum(s.q[:, k - 1])
            k_route, k_serve = jax.random.split(k_algo)
            with jax.named_scope("sim.route"):
                if policy.name == "pandas_po2":
                    s = _route_batch_po2(s, est, ctx, types, active, k_route,
                                         d_choices)
                else:
                    s = _route_batch_pandas(s, est, ctx, types, active, fc,
                                            use_pallas)
            pool_n = jnp.sum(s.q[:, k - 1]) - remote_before
            with jax.named_scope("sim.serve"):
                s, compl_t = bp.serve_and_schedule(s, k_serve, true_k)
            n = (jnp.sum(s.q) + jnp.sum(s.serving > 0)).astype(jnp.float32)
            in_w = (t >= warmup).astype(jnp.float32)
            n_meas2 = n_meas + in_w
            mean_n2 = mean_n + in_w * (n - mean_n) / jnp.maximum(n_meas2, 1.0)
            compl2 = compl + compl_t * (t >= warmup)
            new = FleetCarry((s.q, s.serving, mean_n2, n_meas2, compl2),
                             c.hot_arrived + hot_n * (t >= warmup),
                             c.pool_placed + pool_n * (t >= warmup))
            live = t < horizon
            return jax.tree.map(lambda a, b: jnp.where(live, a, b), new,
                                c), ()

        carry, _ = jax.lax.scan(step, carry, t0 + jnp.arange(fc.chunk),
                                unroll=fc.unroll)
        return carry

    return init, chunk


def _finalize(carry_np: FleetCarry, lam_total) -> Dict[str, Any]:
    """Metrics dict (the dense path's keys, plus `hot_share` and
    `pool_share`: hot arrivals and pool placements per task offered over
    the measured slots) from a final carry on the host."""
    q, serving, mean_n, n_meas, compl = carry_np
    denom = np.float32(lam_total)  # stationary scenario: lam_scale == 1
    mean_delay = np.where(denom > 0, mean_n / denom, np.nan)
    offered = denom * n_meas
    per_offered = np.where(offered > 0, offered, 1.0)
    return {
        "mean_n": mean_n,
        "mean_delay": mean_delay,
        "throughput": compl / np.maximum(n_meas, 1.0),
        "final_n": (q.sum(axis=(-2, -1))
                    + (serving > 0).sum(axis=-1)).astype(np.float32),
        "hot_share": np.where(offered > 0,
                              carry_np.hot_arrived / per_offered, np.nan),
        "pool_share": np.where(offered > 0,
                               carry_np.pool_placed / per_offered, np.nan),
    }


# Keyed cache of jitted chunk closures: repeated fleet_simulate calls
# with the same (policy, cfg, fleet, rack weights) settings — a seed
# study, the test suite's band runs — would otherwise retrace AND
# recompile every call, and the fleet chunk compile is ~8 s at M=10008 on
# one core.  The key is the dataclass reprs (all three are frozen value
# types; cfg holds p_hot) and the weights' values, so a config change can
# never alias a stale program.
_CHUNK_CACHE: Dict[Tuple[Any, ...], Any] = {}


def _jitted_chunk(policy: PolicyLike, cfg, fc: FleetConfig,
                  rack_weights=None):
    key = (repr(policy), repr(cfg), repr(fc),
           None if rack_weights is None else tuple(rack_weights))
    hit = _CHUNK_CACHE.get(key)
    if hit is None:
        init, chunk = _build_fleet_chunk(policy, cfg, fc, rack_weights)
        hit = (init, jax.jit(chunk, donate_argnums=0))
        _CHUNK_CACHE[key] = hit
    return hit


def fleet_simulate(policy: PolicyLike, cfg, lam_total: float, est,
                   seed: int = 0, fleet: FleetLike = None,
                   rack_weights=None) -> Dict[str, Any]:
    """Fleet-path analogue of `core.simulator.simulate` (stationary
    scenario, uniform placement): `rack_weights` as `stationary_traffic`
    gives them.  Same metrics keys plus `hot_share` and `pool_share`;
    scalars come back as floats."""
    if lam_total < 0:
        raise ValueError(f"lam_total must be >= 0, got {lam_total}")
    fc = as_fleet_config(fleet)
    init, fn = _jitted_chunk(policy, cfg, fc, rack_weights)
    carry = init()
    lam = jnp.float32(lam_total)
    est = jnp.asarray(est, jnp.float32)
    seed = jnp.asarray(seed, jnp.uint32)
    for ci in range(-(-cfg.horizon // fc.chunk)):
        carry = fn(carry, jnp.int32(ci * fc.chunk), lam, est, seed)
    out = _finalize(jax.tree.map(np.asarray, FleetCarry.of(carry)),
                    lam_total)
    return {k: float(v) for k, v in out.items()}


def fleet_sweep(policy: PolicyLike, cfg, lam_grid, est_stack, seeds,
                fleet: FleetLike = None,
                rack_weights=None) -> Dict[str, np.ndarray]:
    """Fleet-path analogue of `core.simulator.sweep`: (L, E, S) metrics.

    The (load x error x seed) grid is flattened and vmapped through the
    chunk function — one compile amortizes across the whole study."""
    lam_grid = np.asarray(lam_grid, np.float32)
    est_stack = np.asarray(est_stack, np.float32)
    seeds = np.asarray(seeds, np.uint32)
    if np.any(lam_grid < 0):
        raise ValueError(f"lam_grid must be >= 0, got {lam_grid}")
    fc = as_fleet_config(fleet)
    init, chunk = _build_fleet_chunk(policy, cfg, fc, rack_weights)
    nl, ne, ns = len(lam_grid), len(est_stack), len(seeds)
    n = nl * ne * ns
    lam_b = jnp.asarray(np.repeat(lam_grid, ne * ns))
    est_b = jnp.asarray(np.tile(np.repeat(est_stack, ns, axis=0), (nl, 1, 1)))
    seed_b = jnp.asarray(np.tile(seeds, nl * ne))
    fn = jax.jit(jax.vmap(chunk, in_axes=(0, None, 0, 0, 0)),
                 donate_argnums=0)
    carry = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n,) + a.shape), init())
    for ci in range(-(-cfg.horizon // fc.chunk)):
        carry = fn(carry, jnp.int32(ci * fc.chunk), lam_b, est_b, seed_b)
    out = _finalize(jax.tree.map(np.asarray, FleetCarry.of(carry)),
                    np.asarray(lam_b))
    return {k: np.asarray(v).reshape(nl, ne, ns) for k, v in out.items()}

"""Outer module: optimal grouping (OG) of users by deadline similarity [10].

Users sorted by deadline are partitioned into contiguous groups; groups are
served in deadline order, each occupying the edge GPU from the previous
group's ``t_free`` (Eq. 22 threads through).  A dynamic program over prefix
boundaries picks the grouping that minimizes total energy.

Two implementations:

* :func:`optimal_grouping` — the production path.  All O(M²) contiguous
  segments of the deadline-sorted fleet are enumerated up front, then
  solved by the **batched** J-DOB core level-synchronously: the DP is
  lower-triangular in the prefix end j, so once dp[0..j-1] are final the
  threaded ``t_free`` of every segment ending at j is known, and all of
  level j's (segment, t_free) solves go through a few padded batched
  dispatches — versus the seed's O(M²) dispatches and one XLA recompile
  per distinct segment size.  Shape policy, planner construction and
  compile caching live in :class:`repro.core.planner_service.\
PlannerService` (see ARCHITECTURE.md): small fleets plan against one
  compiled shape, large fleets split each level into 2-3 per-length
  buckets (restoring the large-M speedup), and every shape the fleet can
  need is background-prefetched up front.  The level solver consumes
  exactly the (segment, t_free) pairs the sequential DP consumes, with
  the same memo keys and tie-breaks, and the batched core is bitwise
  padding-invariant, so the result matches
  :func:`optimal_grouping_reference` bit for bit.
* :func:`optimal_grouping_reference` — the seed's sequential DP (one
  ``inner`` call per (segment, t_free) with per-prefix threading), kept as
  the benchmark baseline, the test oracle, and the fallback for arbitrary
  ``inner`` callables the batched core cannot mirror.

Note (documented deviation): the exact DP state would carry the continuous
``t_free``; like [10] we keep the scalar DP over prefixes — optimal when
inner costs are monotone in ``t_free`` (they are: a later GPU start can
only shrink the feasible set), and empirically tight in the paper's regime.

That single-state prefix DP is NOT exact under occupancy coupling,
however: segment energy depends on the threaded cursor, and a
cheaper-but-later prefix can poison its suffix (a coarser cohort chain
measured 5.25% BELOW "exact" at M=96 — the ROADMAP's blind spot).  Both
entry points therefore take ``dp="pareto"``: :func:`_run_dp_pareto` keeps
a **Pareto frontier** of (energy, t_free) states per prefix — a state
survives only if no other state is at least as cheap AND at least as
early — so a costlier-but-earlier prefix stays available to rescue the
suffix.  ``frontier_eps`` (relative epsilon-dominance) and ``beam_width``
bound the frontier when exactness can be traded for speed; the defaults
(0, unbounded) match :func:`bruteforce_grouping` on every fleet small
enough to enumerate (hypothesis-tested), and are never above the prefix
DP by construction (the prefix DP's chain is always in the frontier).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import numpy as np

from .cost_models import DeviceFleet
from .jdob import (BatchedPlanner, Schedule, fused_scan_viable,
                   jdob_schedule, og_plan_fused)
from .planner_service import PlannerService
from .telemetry import span
from .timeline import GpuTimeline, TimelineCursor

#: grouping-DP execution backends: "dispatch" folds the DP host-side with
#: one batched device launch per level (dynamic per-level prefetch hooks,
#: arbitrary beam widths); "fused" folds the whole level loop in one
#: jitted device scan (:func:`repro.core.jdob.og_plan_fused`) and
#: materializes once — bit-identical decisions, O(1) dispatches per plan
DP_BACKENDS = ("dispatch", "fused")


@dataclasses.dataclass
class GroupedSchedule:
    energy: float
    groups: list[np.ndarray]        # member indices (into the original fleet)
    schedules: list[Schedule]
    t_free_end: float

    @property
    def per_user_energy(self) -> np.ndarray:
        M = sum(len(g) for g in self.groups)
        out = np.zeros(M)
        for g, s in zip(self.groups, self.schedules):
            out[g] = s.per_user_energy
        return out


def _run_dp(M: int, cursor: TimelineCursor, solve, level_prefetch=None,
            dp: list | None = None) -> list[tuple[int, int]]:
    """The shared prefix DP: ``dp[j] = (energy, timeline cursor, split i)``
    for users [0, j), folding ``solve(i, j, cursor_i.t_free)`` with
    ascending-``i`` tie-breaks.  Occupancy threads through a
    :class:`~repro.core.timeline.TimelineCursor` — the serialized scalar
    view of the GPU timeline, which ``advance`` folds exactly as Eq. 22
    did, so the DP consumes the same occupancy abstraction the online and
    tenancy layers book against.  ``level_prefetch(j, dp)``, when given,
    runs before level j folds so a batched backend can warm every
    (i, j, tf_i) solve at once.  Returns the chain of contiguous segments
    covering [0, M).  Both grouping implementations run THIS function —
    their bit-for-bit parity is structural, not coincidental.

    ``dp``, when given, is a partial prefix list from a previous run whose
    entries are already final (levels 0..len(dp)-1); folding resumes at
    level ``len(dp)`` and the list is extended IN PLACE — this is the
    incremental path's suffix re-solve (:class:`IncrementalOgState`).  A
    level's fold reads only dp[0..j-1] and ``solve``, so re-folding the
    suffix over a trusted prefix is exactly the from-scratch recurrence.
    """
    INF = np.inf
    if dp is None:
        dp = [(0.0, cursor, -1)]
    start = len(dp)
    for j in range(start, M + 1):
        with span("repro.og.level", level=j):
            if level_prefetch is not None:
                level_prefetch(j, dp)
            with span("repro.og.fold"):
                best = (INF, cursor, 0)
                for i in range(j):
                    e_i, cur_i, _ = dp[i]
                    if not np.isfinite(e_i):
                        continue
                    s = solve(i, j, cur_i.t_free)
                    cand = e_i + s.energy
                    if cand < best[0]:
                        best = (cand, cur_i.advance(s), i)
                dp.append(best)
    chain: list[tuple[int, int]] = []
    j = M
    while j > 0:
        i = dp[j][2]
        chain.append((i, j))
        j = i
    chain.reverse()
    return chain


class AdaptiveBeam:
    """Self-sizing beam for the Pareto-frontier DP (``beam_width="auto"``).

    A static beam pays for its width at EVERY level, but most levels'
    frontiers never fork — the occupancy trade-off concentrates where
    deadlines cluster.  This policy starts at width 1 (the prefix-DP
    view) and doubles only at levels whose dominance survivors overflow
    the current beam (the frontier actually forked there), saturating at
    ``cap``; once widened it stays widened, so a late fork never thrashes.
    The energy invariant does NOT come from the width policy — ANY width
    schedule is sound because :func:`_run_dp_pareto` force-retains the
    prefix-DP anchor state at every level (see there), so the adaptive
    result can never exceed the prefix DP's energy."""

    def __init__(self, start: int = 1, growth: int = 2, cap: int = 12):
        assert start >= 1 and growth >= 2 and cap >= start
        self.width = start
        self.growth = growth
        self.cap = cap
        #: levels whose fork actually widened the beam (observability)
        self.widenings = 0

    def fit(self, survivors: int) -> int:
        """The beam width to cap a level with ``survivors`` dominance
        survivors at — widening state updates as a side effect."""
        while survivors > self.width and self.width < self.cap:
            self.width = min(self.width * self.growth, self.cap)
            self.widenings += 1
        return self.width


def _pareto_sweep(cands: list, frontier_eps: float = 0.0,
                  beam_width=None, stats=None) -> list:
    """Deterministic Pareto reduction of DP candidate states.

    ``cands`` entries are ``(energy, cursor, split, state_idx)``.  Sorted
    ascending by (energy, t_free, split, state_idx), a candidate survives
    only if its ``t_free`` is strictly below every kept state's — i.e. no
    kept (cheaper-or-equal) state is also as early (weak dominance, with
    the lowest-(energy, t_free) representative kept on exact ties, so the
    sweep is order-independent).  ``frontier_eps`` > 0 additionally drops
    candidates whose t_free improvement over the best kept state is below
    a relative epsilon (bounded frontiers at bounded suboptimality);
    ``beam_width`` hard-caps the frontier at the N cheapest survivors
    (``beam_width=1`` collapses to the single min-energy state — the
    prefix DP's view); an :class:`AdaptiveBeam` instance self-sizes the
    cap from the survivor count, widening only at levels that actually
    fork.  ``stats``, when given, accumulates ``frontier_states`` /
    ``frontier_max`` / ``dominance_pruned`` / ``frontier_levels`` onto a
    :class:`~repro.core.jdob.PlannerStats`."""
    cands = [c for c in cands if np.isfinite(c[0])]
    n_in = len(cands)
    cands.sort(key=lambda c: (c[0], c[1].t_free, c[2], c[3]))
    front: list = []
    best_tf = np.inf
    for c in cands:
        tf = c[1].t_free
        if tf < best_tf * (1.0 - frontier_eps):
            front.append(c)
            best_tf = tf
    if isinstance(beam_width, AdaptiveBeam):
        w0 = beam_width.widenings
        bw = beam_width.fit(len(front))
        if stats is not None:
            stats.beam_widenings += beam_width.widenings - w0
    else:
        bw = beam_width
    if bw is not None and len(front) > bw:
        front = front[:bw]
    if stats is not None:
        stats.frontier_states += len(front)
        stats.frontier_max = max(stats.frontier_max, len(front))
        stats.dominance_pruned += n_in - len(front)
        if len(stats.frontier_levels) < 4096:
            stats.frontier_levels.append(len(front))
    return front


def _run_dp_pareto(M: int, cursor: TimelineCursor, solve,
                   level_prefetch=None, dp: list | None = None,
                   frontier_eps: float = 0.0, beam_width=None,
                   stats=None, anchor: list | None = None,
                   beam_hist: list | None = None
                   ) -> list[tuple[int, int]]:
    """The Pareto-frontier prefix DP: ``dp[j]`` is a LIST of frontier
    states ``(energy, cursor, split i, state index into dp[i])``, sorted
    ascending by energy, one list per prefix [0, j).  Where
    :func:`_run_dp` keeps only the min-energy state — provably wrong
    under occupancy coupling (a cheaper-but-later prefix poisons the
    suffix) — this keeps every state no other state dominates in BOTH
    energy and threaded ``t_free``, so the winning chain is extracted
    from the true trade-off surface.  Same ``solve`` memo keys, same
    ``level_prefetch`` contract (a batched backend warms one level's
    (i, state, j) solves in one dispatch), same in-place ``dp`` resume
    protocol as :func:`_run_dp` (the incremental path truncates past the
    churn point and re-folds the suffix).  With every segment's
    (energy, end) monotone in its start the frontier contains the exact
    optimum; ``frontier_eps``/``beam_width`` trade that for bounded
    state counts.  Returns the chain of the min-energy final state.

    With an :class:`AdaptiveBeam`, ``anchor[j]`` tracks the index into
    ``dp[j]`` of the PREFIX-DP ANCHOR: the state :func:`_run_dp` would
    have kept at level j, re-folded here over anchor states only with
    the identical ``e_i + s.energy`` / strict-``<`` / ascending-``i``
    fold.  The anchor is force-retained — re-inserted if the beam cap or
    dominance dropped it — so every level's frontier contains the entire
    prefix-DP chain and the adaptive min-energy result is ≤ the prefix
    DP's, whatever width schedule the beam picks.  Its solves are a
    subset of the frontier's own (the anchor state lives in ``dp[i]``),
    so the guarantee costs no extra solver dispatches.  On resume, pass
    back the same ``anchor`` list truncated in lockstep with ``dp``;
    ``beam_hist`` likewise records the beam's (width, widenings) per
    level so a truncated resume rewinds the widening state to exactly
    what a from-scratch fold would have at the churn point — without it
    a wider leftover beam would keep extra suffix states and break
    incremental-vs-scratch parity."""
    adaptive = isinstance(beam_width, AdaptiveBeam)
    if dp is None:
        dp = [[(0.0, cursor, -1, 0)]]
    if adaptive and anchor is None:
        anchor = [0]
    if adaptive and beam_hist is not None:
        if beam_hist:
            beam_width.width, beam_width.widenings = beam_hist[-1]
        else:
            beam_hist.append((beam_width.width, beam_width.widenings))
    start = len(dp)
    for j in range(start, M + 1):
        with span("repro.og.level", level=j):
            if level_prefetch is not None:
                level_prefetch(j, dp)
            with span("repro.og.fold"):
                cands = []
                for i in range(j):
                    for si, st in enumerate(dp[i]):
                        e_i, cur_i = st[0], st[1]
                        if not np.isfinite(e_i):
                            continue
                        s = solve(i, j, cur_i.t_free)
                        cands.append((e_i + s.energy, cur_i.advance(s), i,
                                      si))
                a_best = None
                if adaptive:
                    # re-fold _run_dp over the anchor chain (solves
                    # already memoized)
                    for i in range(j):
                        e_i, cur_i = dp[i][anchor[i]][0], dp[i][anchor[i]][1]
                        if not np.isfinite(e_i):
                            continue
                        s = solve(i, j, cur_i.t_free)
                        cand = e_i + s.energy
                        if a_best is None or cand < a_best[0]:
                            a_best = (cand, cur_i.advance(s), i, anchor[i])
                front = _pareto_sweep(cands, frontier_eps, beam_width, stats)
                if not front:
                    front = [(np.inf, cursor, 0, 0)]
                    if adaptive:
                        anchor.append(0)
                elif adaptive:
                    if a_best is None:
                        anchor.append(0)
                    else:
                        ai = next((k for k, c in enumerate(front)
                                   if c[2] == a_best[2]
                                   and c[3] == a_best[3]), None)
                        if ai is None:
                            front.append(a_best)
                            front.sort(key=lambda c: (c[0], c[1].t_free,
                                                      c[2], c[3]))
                            ai = next(k for k, c in enumerate(front)
                                      if c[2] == a_best[2]
                                      and c[3] == a_best[3])
                            if stats is not None:
                                stats.frontier_states += 1
                                stats.frontier_max = max(stats.frontier_max,
                                                         len(front))
                        anchor.append(ai)
                dp.append(front)
                if adaptive and beam_hist is not None:
                    beam_hist.append((beam_width.width,
                                      beam_width.widenings))
    chain: list[tuple[int, int]] = []
    j, si = M, 0
    while j > 0:
        st = dp[j][si]
        chain.append((st[2], j))
        j, si = st[2], st[3]
    chain.reverse()
    return chain


def _fused_chain(rows: list, M: int) -> list[tuple[int, int]]:
    """Backtrack the winning split chain from numeric DP rows (level
    0..M, each a list of ``(energy, t_free, split, state_idx)`` — the
    fused scan's host view), exactly as the host DPs backtrack theirs."""
    chain: list[tuple[int, int]] = []
    j, si = M, 0
    while j > 0:
        st = rows[j][si]
        chain.append((st[2], j))
        j, si = st[2], st[3]
    chain.reverse()
    return chain


def _resolve_beam(beam_width):
    """Normalize a ``beam_width`` knob: the string ``"auto"`` becomes a
    fresh per-run :class:`AdaptiveBeam` (widening state must never leak
    across independent DP runs); ints, ``None`` and prebuilt beam objects
    pass through."""
    return AdaptiveBeam() if beam_width == "auto" else beam_width


def _entry_states(entry):
    """A DP level's states: the prefix DP keeps one tuple per level, the
    Pareto DP a list of them — iterate either uniformly."""
    return entry if isinstance(entry, list) else (entry,)


def _collect_chain(chain, order, solve, cursor: TimelineCursor,
                   timeline: GpuTimeline | None = None) -> GroupedSchedule:
    """Walk the DP-selected chain threading the timeline cursor exactly
    (Eq. 22 as the serialized special case).  When a ``timeline`` is
    given, each offloading group's occupancy is committed as a
    reservation (tenant −1, flush-less), so ``t_free_end`` is derived
    from the reservations rather than a free-floating scalar."""
    groups, schedules = [], []
    total = 0.0
    for (i, j) in chain:
        s = solve(i, j, cursor.t_free)
        groups.append(order[i:j])
        schedules.append(s)
        total += s.energy
        if timeline is not None and s.offload.any():
            timeline.reserve(-1, cursor.t_free, s.t_free_end,
                             gpu_start=s.gpu_start, f_edge=s.f_edge)
        cursor = cursor.advance(s)
    t_free_end = (timeline.horizon if timeline is not None
                  and timeline.reservations else cursor.t_free)
    return GroupedSchedule(total, groups, schedules, t_free_end)


def optimal_grouping(profile, fleet: DeviceFleet, edge,
                     inner: Callable = jdob_schedule,
                     t_free: float = 0.0, rho: float = 0.03e9,
                     max_groups: int | None = None,
                     planner: BatchedPlanner | None = None,
                     service: PlannerService | None = None,
                     timeline: GpuTimeline | None = None,
                     dp: str = "prefix", frontier_eps: float = 0.0,
                     beam_width: int | str | None = None,
                     dp_backend: str = "dispatch",
                     _count_plan: bool = True) -> GroupedSchedule:
    """OG over the deadline-sorted fleet.  ``inner`` picks the per-group
    solver; the J-DOB family routes through the planner service (pass a
    prebuilt ``service`` to reuse its planners/compiled shapes across
    calls), other callables fall back to
    :func:`optimal_grouping_reference`.  ``max_groups`` is accepted for API
    compatibility and, as in the seed implementation, not enforced (the DP
    picks the group count freely).  ``timeline`` plugs the DP into a GPU
    timeline: the starting occupancy is read from it and the winning
    chain's group occupancies are committed as reservations (serialized
    semantics — the DP's threading IS Eq. 22's special case).
    ``dp="pareto"`` switches the recurrence to the Pareto-frontier DP
    (:func:`_run_dp_pareto` — sound under occupancy coupling, never above
    the prefix DP), with ``frontier_eps``/``beam_width`` bounding the
    per-prefix frontier; ``beam_width="auto"`` self-sizes the beam
    (:class:`AdaptiveBeam`) with the anchor guarantee that the result
    never exceeds the prefix DP's energy.

    ``dp_backend="fused"`` folds the DP on device in one jitted scan
    (:func:`repro.core.jdob.og_plan_fused`) instead of one batched
    dispatch per level — bit-identical energies/groups/per-user energies,
    O(1) dispatches per plan.  An unbounded pareto frontier that outgrows
    the device beam buffer falls back to the dispatch fold (counted in
    ``PlannerStats.fused_fallbacks``), fleets past the
    :data:`~repro.core.jdob.FUSED_SCAN_MAX_LEVELS` crossover route
    straight to it (``PlannerStats.fused_routed`` — the scan's fixed-shape
    work loses to per-length bucketing there), and arbitrary ``inner``
    callables always fold host-side via the reference path."""
    assert dp in ("prefix", "pareto"), f"unknown dp mode {dp!r}"
    assert dp_backend in DP_BACKENDS, f"unknown dp backend {dp_backend!r}"
    if timeline is not None:
        t_free = max(t_free, timeline.t_free(0.0))
    if service is None:
        service = PlannerService(profile, edge, rho=rho)
    else:
        # the service's planners bake in ITS rho — reject disagreement
        # instead of returning plausible-but-wrong energies
        assert service.rho == rho, "service rho disagrees with rho argument"
    spec = service.spec_for(inner)
    if spec is None:
        # ``inner`` is authoritative: an arbitrary callable always takes
        # the sequential path, even when a prebuilt planner was supplied
        return optimal_grouping_reference(profile, fleet, edge, inner,
                                          t_free, rho, max_groups,
                                          timeline=timeline, dp=dp,
                                          frontier_eps=frontier_eps,
                                          beam_width=beam_width)
    if planner is None:
        planner = service.planner(**spec)
    else:
        # a prebuilt planner takes over solving, so it must actually
        # replicate the requested inner/rho — fail loudly on disagreement
        # instead of returning plausible-but-wrong energies
        want_parts = spec.get("partitions")
        assert (planner.sort_keys == tuple(spec.get("sort_keys", ("gamma",)))
                and planner.edge_dvfs == spec.get("edge_dvfs", True)
                and planner.partitions == (None if want_parts is None
                                           else tuple(want_parts))
                and planner.rho == rho), \
            "prebuilt planner configuration disagrees with inner/rho"

    with span("repro.og.plan", users=fleet.M):
        M = fleet.M
        order = np.argsort(fleet.deadline, kind="stable")
        sorted_fleet = fleet.subset(order)

        # lazy segment construction: the dispatch DP touches all O(M²)
        # contiguous segments of the sorted fleet, the fused path only the
        # winning chain's
        sub: dict[tuple[int, int], DeviceFleet] = {}

        def seg(i: int, j: int) -> DeviceFleet:
            if (i, j) not in sub:
                sub[(i, j)] = sorted_fleet.subset(np.arange(i, j))
            return sub[(i, j)]

        # per-length shape buckets: each segment solves at the smallest of 2-3
        # power-of-two user widths covering it, so a level's dispatches stop
        # paying for masked users of short segments (the seed padded everything
        # to the fleet-wide bucket, which sank the large-M speedup).  Padding
        # is bit-invariant, so bucketing can never change results.
        buckets = service.level_buckets(M)
        # cache keyed exactly like the sequential DP's memo:
        # (i, j, round(tf, 9))
        cache: dict[tuple[int, int, float], Schedule] = {}

        def solve_many(pairs: Sequence[tuple[int, int, float]]):
            with span("repro.og.segments"):
                by_bucket: dict[int, list[tuple[int, int, float]]] = {}
                for (i, j, tf) in pairs:
                    by_bucket.setdefault(service.bucket_for(j - i, buckets),
                                         []).append((i, j, tf))
                work = [(b, part, [seg(i, j) for (i, j, _) in part])
                        for b, part in sorted(by_bucket.items())]
            # dispatch every bucket before materializing any: the device
            # works on bucket k+1 while bucket k's winners
            # transfer/reconstruct
            pending = [(part, planner.plan_async(
                segs, [tf for (_, _, tf) in part], m_pad=b,
                g_pad=service.level_group_pad(buckets, len(part))))
                for b, part, segs in work]
            for part, plans in pending:
                for (i, j, tf), p in zip(part, plans.get()):
                    cache[(i, j, round(tf, 9))] = p

        def solve(i: int, j: int, tf: float) -> Schedule:
            key = (i, j, round(tf, 9))
            if key not in cache:
                solve_many([(i, j, tf)])
            return cache[key]

        def finish(chain) -> GroupedSchedule:
            out = _collect_chain(chain, order, solve, TimelineCursor(t_free),
                                 timeline)
            if _count_plan:
                planner.stats.og_plans += 1
                planner.stats.og_dispatches += planner.stats.dispatches - d0
            return out

        d0 = planner.stats.dispatches
        if dp_backend == "fused":
            if not fused_scan_viable(M):
                # size crossover: past it the scan's fixed-shape work loses
                # more compute than one-dispatch folding saves — route to the
                # dispatch fold (a policy decision, counted, not a failure)
                planner.stats.fused_routed += 1
            else:
                res = og_plan_fused(planner, sorted_fleet, t_free=t_free,
                                    mode=dp, frontier_eps=frontier_eps,
                                    beam_width=_resolve_beam(beam_width),
                                    stats=planner.stats)
                if res.overflow:
                    planner.stats.fused_fallbacks += 1
                else:
                    return finish(_fused_chain(
                        [[(0.0, t_free, -1, 0)]] + res.rows, M))

        # dispatch backend (and the fused overflow fallback): overlap XLA
        # compiles with the DP's early levels by background-compiling every
        # shape this fleet can need, in first-need order
        for b, g in service.level_shapes(M):
            planner.prefetch(b, g)

        def level_prefetch(j: int, states) -> None:
            # level-synchronous batching: when level j folds, dp[0..j-1] are
            # final, so the threaded t_free of every candidate (i, state, j)
            # is known — warm all of the level's missing solves in ONE
            # batched dispatch (the pareto DP's frontier states of one level
            # can share a rounded t_free, hence the seen-set dedup)
            need, seen = [], set()
            for i in range(j):
                for st in _entry_states(states[i]):
                    key = (i, j, round(st[1].t_free, 9))
                    if np.isfinite(st[0]) and key not in cache \
                            and key not in seen:
                        seen.add(key)
                        need.append((i, j, st[1].t_free))
            if need:
                solve_many(need)

        if dp == "pareto":
            chain = _run_dp_pareto(M, TimelineCursor(t_free), solve,
                                   level_prefetch, frontier_eps=frontier_eps,
                                   beam_width=_resolve_beam(beam_width),
                                   stats=planner.stats)
        else:
            chain = _run_dp(M, TimelineCursor(t_free), solve, level_prefetch)
        return finish(chain)


class IncrementalOgState:
    """Incremental OG: the prefix DP under fleet churn.

    The DP of :func:`_run_dp` is lower-triangular in the prefix end j, so a
    single arrival or departure at deadline-sorted position k leaves every
    prefix [0, j) with j ≤ k — and every memoized segment solve with both
    endpoints ≤ k — untouched.  This class caches the per-prefix DP state
    (best cost, threaded cursor, winning split) plus the segment-solve memo
    across fleet changes and re-folds ONLY levels > k, instead of the
    O(M²)-segment from-scratch solve.  Results are bit-identical to
    :func:`optimal_grouping` on the current fleet: the suffix re-fold runs
    the same recurrence over the same solver with the same memo keys and
    tie-breaks, and the batched core is padding-invariant, so caching can
    never change a value (parity-tested in tests/core/test_scale.py).

    Segment solves behind position k are REMAPPED, not recomputed: after an
    arrival at k, old segment (i, j) with i ≥ k is the new segment
    (i+1, j+1) over the same users, so its memo entries carry over; only
    segments straddling k are dropped.  Amortized work per update is one
    DP suffix (M − k levels, each a few batched dispatches) instead of the
    full triangle.

    Usage::

        state = IncrementalOgState(profile, fleet, edge, service=svc)
        plan = state.plan()          # == optimal_grouping(profile, fleet, ..)
        plan = state.arrive(row)     # row: an M==1 DeviceFleet
        plan = state.depart(m)       # m: index into state.fleet

    ``t_free`` is fixed at construction (the state plans a fleet snapshot
    at one occupancy origin — reconstruct for a new origin).  Timelines are
    not threaded here; the serialized scalar cursor is the DP's contract.
    """

    def __init__(self, profile, fleet: DeviceFleet, edge,
                 inner: Callable = jdob_schedule, t_free: float = 0.0,
                 rho: float = 0.03e9,
                 service: PlannerService | None = None,
                 dp: str = "prefix", frontier_eps: float = 0.0,
                 beam_width: int | str | None = None,
                 dp_backend: str = "dispatch"):
        assert dp in ("prefix", "pareto"), f"unknown dp mode {dp!r}"
        assert dp_backend in DP_BACKENDS, \
            f"unknown dp backend {dp_backend!r}"
        if service is None:
            service = PlannerService(profile, edge, rho=rho)
        else:
            assert service.rho == rho, \
                "service rho disagrees with rho argument"
        spec = service.spec_for(inner)
        assert spec is not None, \
            "IncrementalOgState requires a planner-family inner solver"
        self.profile, self.edge, self.rho = profile, edge, rho
        self.t_free = float(t_free)
        self.service = service
        self.planner = service.planner(**spec)
        #: which recurrence the re-fold runs: the prefix DP or the
        #: Pareto-frontier DP — the truncate-past-the-churn-point resume
        #: protocol is identical, only the per-level state differs
        self.dp_mode = dp
        #: "dispatch" re-folds the suffix host-side (one batched dispatch
        #: per re-folded level); "fused" re-folds it as one device scan
        #: starting at the churn level — bit-identical to a scratch fused
        #: fold, because a level's fold reads only earlier levels
        self.dp_backend = dp_backend
        self.frontier_eps = frontier_eps
        # an adaptive beam is stateful: one long-lived instance per state,
        # with its per-level widening history recorded so churn truncation
        # can rewind it (see _run_dp_pareto's beam_hist contract)
        self.beam_width = _resolve_beam(beam_width)
        self._anchor: list = [0]
        self._beam_hist: list = []
        #: memoized plan() result — valid while no churn truncated the DP
        self._last_plan: GroupedSchedule | None = None
        self.fleet = fleet                       # current fleet, append order
        #: deadline-sorted positions -> current-fleet indices (stable order)
        self._order = list(np.argsort(fleet.deadline, kind="stable"))
        self._sorted_fleet = fleet.subset(np.array(self._order, dtype=int))
        self._sub: dict[tuple[int, int], DeviceFleet] = {}
        self._cache: dict[tuple[int, int, float], Schedule] = {}
        self._dp: list = ([[(0.0, TimelineCursor(self.t_free), -1, 0)]]
                          if dp == "pareto"
                          else [(0.0, TimelineCursor(self.t_free), -1)])
        #: levels re-folded by the last plan()/arrive()/depart() call —
        #: the bench's incrementality observable
        self.last_refold_levels = 0

    @property
    def M(self) -> int:
        return self.fleet.M

    # -- solver plumbing (mirrors optimal_grouping's closures exactly) ----
    def _seg(self, i: int, j: int) -> DeviceFleet:
        key = (i, j)
        if key not in self._sub:
            self._sub[key] = self._sorted_fleet.subset(np.arange(i, j))
        return self._sub[key]

    def _solve_many(self, pairs, buckets) -> None:
        by_bucket: dict[int, list[tuple[int, int, float]]] = {}
        for (i, j, tf) in pairs:
            by_bucket.setdefault(
                self.service.bucket_for(j - i, buckets), []).append((i, j, tf))
        pending = []
        for b, part in sorted(by_bucket.items()):
            pending.append((part, self.planner.plan_async(
                [self._seg(i, j) for (i, j, _) in part],
                [tf for (_, _, tf) in part], m_pad=b,
                g_pad=self.service.level_group_pad(buckets, len(part)))))
        for part, plans in pending:
            for (i, j, tf), p in zip(part, plans.get()):
                self._cache[(i, j, round(tf, 9))] = p

    def _solver(self):
        buckets = self.service.level_buckets(self.M)

        def solve(i: int, j: int, tf: float) -> Schedule:
            key = (i, j, round(tf, 9))
            if key not in self._cache:
                self._solve_many([(i, j, tf)], buckets)
            return self._cache[key]

        def level_prefetch(j: int, states) -> None:
            need, seen = [], set()
            for i in range(j):
                for st in _entry_states(states[i]):
                    key = (i, j, round(st[1].t_free, 9))
                    if np.isfinite(st[0]) and key not in self._cache \
                            and key not in seen:
                        seen.add(key)
                        need.append((i, j, st[1].t_free))
            if need:
                self._solve_many(need, buckets)

        return solve, level_prefetch

    # -- fleet churn ------------------------------------------------------
    def arrive(self, user: DeviceFleet) -> GroupedSchedule:
        """Admit a one-user fleet row; re-folds the DP suffix from its
        deadline-sorted position and returns the new plan."""
        assert user.M == 1, "arrive() takes a single-user fleet row"
        d = float(user.deadline[0])
        # stable argsort puts the newest (largest original index) after
        # every equal deadline — i.e. searchsorted side='right'
        k = int(np.searchsorted(self._sorted_fleet.deadline, d,
                                side="right"))
        self.fleet = self.fleet.concat(user)
        self._order.insert(k, self.fleet.M - 1)
        self._sorted_fleet = self.fleet.subset(np.array(self._order,
                                                        dtype=int))
        # remap caches across the insertion point; drop straddlers
        self._sub = {(i + (i >= k), j + (j > k)): f
                     for (i, j), f in self._sub.items()
                     if j <= k or i >= k}
        self._cache = {(i + (i >= k), j + (j > k), tf): s
                       for (i, j, tf), s in self._cache.items()
                       if j <= k or i >= k}
        self._truncate(k)
        return self.plan()

    def depart(self, m: int) -> GroupedSchedule:
        """Remove the user at index ``m`` of the current fleet; re-folds
        the DP suffix from its deadline-sorted position."""
        k = self._order.index(m)
        keep = [u for u in range(self.fleet.M) if u != m]
        self.fleet = self.fleet.subset(np.array(keep, dtype=int))
        del self._order[k]
        self._order = [u - (u > m) for u in self._order]
        self._sorted_fleet = self.fleet.subset(np.array(self._order,
                                                        dtype=int))
        self._sub = {(i - (i > k), j - (j > k)): f
                     for (i, j), f in self._sub.items()
                     if j <= k or i >= k + 1}
        self._cache = {(i - (i > k), j - (j > k), tf): s
                       for (i, j, tf), s in self._cache.items()
                       if j <= k or i >= k + 1}
        self._truncate(k)
        return self.plan()

    def _truncate(self, k: int) -> None:
        """Drop every DP level past the churn point, keeping the anchor
        and beam-widening history in lockstep so the suffix re-fold is
        exactly the from-scratch recurrence (an adaptive beam rewinds its
        widening state to what a scratch fold would hold at level k)."""
        del self._dp[k + 1:]
        del self._anchor[k + 1:]
        del self._beam_hist[k + 1:]
        self._last_plan = None

    # -- solve ------------------------------------------------------------
    def plan(self) -> GroupedSchedule:
        """The OG plan for the current fleet, re-folding only the DP
        levels invalidated since the last call (all of them on first
        use).  A churn-free repeat call is O(1): the previous plan is
        returned from the memo without touching the DP or the solver."""
        M = self.M
        if self._last_plan is not None and len(self._dp) == M + 1:
            self.last_refold_levels = 0
            return self._last_plan
        solve, level_prefetch = self._solver()
        self.last_refold_levels = M + 1 - len(self._dp)
        self._truncate(M)
        d0 = self.planner.stats.dispatches
        chain = None
        if self.dp_backend == "fused":
            if not fused_scan_viable(M):
                self.planner.stats.fused_routed += 1
            else:
                chain = self._fold_fused(M)
                if chain is None:
                    self.planner.stats.fused_fallbacks += 1
        if chain is None:
            for b, g in self.service.level_shapes(M):
                self.planner.prefetch(b, g)
            if self.dp_mode == "pareto":
                chain = _run_dp_pareto(M, TimelineCursor(self.t_free),
                                       solve, level_prefetch, dp=self._dp,
                                       frontier_eps=self.frontier_eps,
                                       beam_width=self.beam_width,
                                       stats=self.planner.stats,
                                       anchor=self._anchor,
                                       beam_hist=self._beam_hist)
            else:
                chain = _run_dp(M, TimelineCursor(self.t_free), solve,
                                level_prefetch, dp=self._dp)
        order = np.array(self._order, dtype=int)
        self._last_plan = _collect_chain(chain, order, solve,
                                         TimelineCursor(self.t_free))
        self.planner.stats.og_plans += 1
        self.planner.stats.og_dispatches += \
            self.planner.stats.dispatches - d0
        return self._last_plan

    def _fold_fused(self, M: int):
        """Suffix re-fold on the fused backend: feed the trusted host DP
        prefix into the device scan as its initial tables, fold levels
        ``len(dp)..M`` on device, and extend the host state from the
        scan's rows — bit-identical to the host re-fold (same recurrence,
        same float64 accumulation, same sweep).  Returns the winning
        chain, or ``None`` when the scan overflowed (caller falls back to
        the host fold over the same, untouched state)."""
        pareto = self.dp_mode == "pareto"
        rows0 = [[(st[0], st[1].t_free, st[2], st[3] if len(st) > 3 else 0)
                  for st in _entry_states(lvl)] for lvl in self._dp]
        adaptive = pareto and isinstance(self.beam_width, AdaptiveBeam)
        w0, n0 = 1, 0
        if adaptive:
            # mirror _run_dp_pareto's resume protocol: restore the beam
            # from the recorded per-level history, or record the initial
            # state on first use
            if self._beam_hist:
                w0, n0 = self._beam_hist[-1]
            else:
                w0, n0 = self.beam_width.width, self.beam_width.widenings
                self._beam_hist.append((w0, n0))
        res = og_plan_fused(self.planner, self._sorted_fleet,
                            t_free=self.t_free, mode=self.dp_mode,
                            frontier_eps=self.frontier_eps,
                            beam_width=self.beam_width,
                            init_rows=rows0, init_anchor=self._anchor,
                            width0=w0, widen0=n0,
                            stats=self.planner.stats)
        if res.overflow:
            return None
        for states in res.rows:
            if pareto:
                self._dp.append([(e, TimelineCursor(tf), sp, si)
                                 for (e, tf, sp, si) in states])
            else:
                e, tf, sp, _ = states[0]
                self._dp.append((e, TimelineCursor(tf), sp))
        if adaptive:
            self._anchor.extend(res.anchor)
            self._beam_hist.extend(res.beam_hist)
            self.beam_width.width = res.width
            self.beam_width.widenings = res.widenings
        return _fused_chain(rows0 + res.rows, M)


def optimal_grouping_reference(profile, fleet: DeviceFleet, edge,
                               inner: Callable = jdob_schedule,
                               t_free: float = 0.0, rho: float = 0.03e9,
                               max_groups: int | None = None,
                               timeline: GpuTimeline | None = None,
                               dp: str = "prefix",
                               frontier_eps: float = 0.0,
                               beam_width: int | str | None = None,
                               dp_backend: str = "dispatch"
                               ) -> GroupedSchedule:
    """The seed's sequential DP: one ``inner`` dispatch per (segment,
    t_free) with per-prefix t_free threading.  O(M²) dispatches — kept as
    the benchmark baseline / oracle and the arbitrary-``inner`` fallback.
    ``dp="pareto"`` runs the Pareto-frontier recurrence sequentially (the
    arbitrary-``inner`` route to frontier-sound plans).  ``dp_backend``
    is accepted for signature parity with :func:`optimal_grouping` and
    validated, but the reference always folds host-side — it IS the
    oracle both backends are tested against."""
    assert dp in ("prefix", "pareto"), f"unknown dp mode {dp!r}"
    assert dp_backend in DP_BACKENDS, f"unknown dp backend {dp_backend!r}"
    M = fleet.M
    order = np.argsort(fleet.deadline, kind="stable")
    sorted_fleet = fleet.subset(order)

    # memoized inner solve for contiguous [i, j) at a given t_free
    cache: dict = {}

    def solve(i: int, j: int, tf: float) -> Schedule:
        key = (i, j, round(tf, 9))
        if key not in cache:
            cache[key] = inner(profile, sorted_fleet.subset(np.arange(i, j)),
                               edge, t_free=tf, rho=rho)
        return cache[key]

    if timeline is not None:
        t_free = max(t_free, timeline.t_free(0.0))
    if dp == "pareto":
        chain = _run_dp_pareto(M, TimelineCursor(t_free), solve,
                               frontier_eps=frontier_eps,
                               beam_width=_resolve_beam(beam_width))
    else:
        chain = _run_dp(M, TimelineCursor(t_free), solve)
    return _collect_chain(chain, order, solve, TimelineCursor(t_free),
                          timeline)


def bruteforce_grouping(profile, fleet: DeviceFleet, edge,
                        inner: Callable = jdob_schedule,
                        t_free: float = 0.0, rho: float = 0.03e9
                        ) -> GroupedSchedule:
    """Exhaustive grouping oracle: every one of the 2^(M-1) contiguous
    partitions of the deadline-sorted fleet, each evaluated left to right
    with the occupancy cursor threaded exactly as the DPs thread it (and
    energies summed in the same left-to-right order, so a DP that finds
    the same chain reproduces the same float).  Exponential — the
    hypothesis oracle for :func:`_run_dp_pareto` at M ≤ ~8, nothing
    more."""
    M = fleet.M
    assert M <= 16, "bruteforce_grouping is 2^(M-1) — oracle sizes only"
    order = np.argsort(fleet.deadline, kind="stable")
    sorted_fleet = fleet.subset(order)
    cache: dict = {}

    def solve(i: int, j: int, tf: float) -> Schedule:
        key = (i, j, round(tf, 9))
        if key not in cache:
            cache[key] = inner(profile, sorted_fleet.subset(np.arange(i, j)),
                               edge, t_free=tf, rho=rho)
        return cache[key]

    best_e, best_chain = np.inf, [(0, M)]
    for mask in range(1 << max(M - 1, 0)):
        bounds = [0] + [b + 1 for b in range(M - 1)
                        if (mask >> b) & 1] + [M]
        cursor = TimelineCursor(t_free)
        total = 0.0
        chain = list(zip(bounds[:-1], bounds[1:]))
        for (i, j) in chain:
            s = solve(i, j, cursor.t_free)
            total = total + s.energy
            cursor = cursor.advance(s)
        if total < best_e:
            best_e, best_chain = total, chain
    return _collect_chain(best_chain, order, solve, TimelineCursor(t_free))


def single_group(profile, fleet, edge, inner=jdob_schedule,
                 t_free: float = 0.0, rho: float = 0.03e9) -> GroupedSchedule:
    """No grouping: the whole fleet as one group (identical-deadline runs)."""
    s = inner(profile, fleet, edge, t_free=t_free, rho=rho)
    return GroupedSchedule(s.energy, [np.arange(fleet.M)], [s], s.t_free_end)

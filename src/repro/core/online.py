"""Online co-inference scheduling (the paper's stated future work, §V).

Requests arrive over time (no arrival predictions).  Each request m has an
absolute deadline ``a_m + T_m``.  A queued request can still be served
*locally* as long as its device starts by ``d_m − l_min(m)`` (minimum local
latency at f_max) — that instant is its **point of no return** τ_m.  The
scheduler accumulates a queue and flushes it through the offline J-DOB
inner module (with the GPU-occupancy time threaded) at a policy-chosen
moment:

* ``immediate`` — flush on every arrival (no batching across arrivals).
* ``window``    — flush when the oldest queued request has waited Δ.
* ``slack``     — adaptive: flush when waiting longer would erode some
  queued request's remaining deadline budget below ``keep_frac`` of its
  original T_m.  Batches grow exactly when arrivals are dense relative to
  deadlines, and every request keeps most of its DVFS slack.
* ``lastcall``  — flush at the point of no return τ_m (maximum batching).
  Kept as a cautionary baseline: it never violates deadlines but destroys
  the latency budget J-DOB turns into energy savings — measured WORSE
  than local computing (EXPERIMENTS.md §Online).

Two layers:

* :class:`OnlineScheduler` — the production core: an **event-driven**
  scheduler over a time-ordered heap of arrival / flush / gpu-free events.
  Requests are submitted at any time (out of order before :meth:`run`, or
  incrementally between :meth:`step` calls — the live-server regime);
  whenever the queue changes, the policy re-arms the flush timer; a flush
  plans through the shared :class:`~repro.core.planner_service.\
PlannerService` and books a :class:`~repro.core.timeline.Reservation`
  on the scheduler's :class:`~repro.core.timeline.GpuTimeline` —
  serialized mode reproduces the scalar Eq. 22 horizon bit for bit, while
  ``occupancy="interleaved"`` gap-fills small batches into idle windows
  and re-selects f_e per flush against the reservation's actual slack —
  emitting a gpu-free event other components can key off.
  ``on_flush`` / ``on_gpu_free`` callbacks let a real server execute the
  planned batch on a model the moment it is scheduled —
  :class:`repro.serving.CoInferenceServer` drives exactly this hook.
* :func:`simulate_online` — the historical one-shot API, now a thin driver
  that submits a trace and runs the scheduler to completion.  Results are
  bit-identical to the seed flush-loop simulator, which survives as
  :func:`simulate_online_reference` (the test oracle).

The offline **oracle bound** runs OG+J-DOB over all requests with arrival
times ignored (clairvoyant, free to batch anything) — a lower bound no
online policy can beat.

**The channel** (:mod:`repro.core.channel`) threads through every flush:
plans price Eqs. 3-4 at the channel's contended-rate snapshot (the jitted
grid is unchanged — rates were already a per-user input array), the
flush's uploads are then *realized* on the channel and the actual
``gpu_start`` derived from the realized finish times, with a bounded
replan / edge-DVFS actualization pass when they diverge from the plan
(:meth:`OnlineScheduler._actualize`).  Without a channel — or with the
static one — every step collapses to the pre-channel path bit for bit.
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
from typing import Callable

import numpy as np

from .baselines import jdob_plus, local_computing
from .channel import ChannelModel
from .cost_models import DeviceFleet, EdgeProfile
from .grouping import optimal_grouping
from .jdob import BatchedPlanner, Schedule
from .planner_service import PlannerService, planner_spec
from .task_model import TaskProfile
from .telemetry import (NULL_TRACER, TID_GPU, TID_PLANNER, TID_UPLINK,
                        Telemetry, span, tenant_tid)
from .timeline import (OCCUPANCY_MODES, GpuTimeline, rescale_edge_dvfs,
                       respeed_edge_dvfs)

POLICIES = ("immediate", "window", "slack", "lastcall")


@dataclasses.dataclass
class OnlineArrival:
    user: int
    arrival: float            # seconds
    rel_deadline: float       # T_m^(d), relative to arrival
    payload: object = None    # opaque caller data (e.g. the actual Request)

    @property
    def abs_deadline(self) -> float:
        return self.arrival + self.rel_deadline


@dataclasses.dataclass
class OnlineResult:
    energy: float
    n_flushes: int
    batch_sizes: list[int]
    violations: int
    per_user_energy: np.ndarray
    flush_times: list[float]
    #: per-flush edge frequency (Hz) actually dispatched — ``None`` for
    #: all-local flushes; under interleaved occupancy this is the
    #: slack-rescaled f_e, not necessarily the planner grid's choice
    f_edges: list = dataclasses.field(default_factory=list)
    #: channel observability (all zero without a channel / with the
    #: static one): summed |realized − planned| upload completion (s),
    #: bounded actualization re-plans taken when realized rates diverged,
    #: and offloaded requests whose REALIZED batch end slipped past their
    #: deadline (on top of the flush-time ``violations`` count).
    #: ``metadata={"aggregate": True}`` marks a counter for automatic
    #: cross-scheduler summation (telemetry.aggregate_counter_fields —
    #: the tenancy layer and bench emitters derive their merge lists from
    #: it, so a new counter cannot be silently dropped)
    upload_error: float = dataclasses.field(
        default=0.0, metadata={"aggregate": True})
    channel_replans: int = dataclasses.field(
        default=0, metadata={"aggregate": True})
    realized_late: int = dataclasses.field(
        default=0, metadata={"aggregate": True})
    #: flushes re-priced against staggered upload starts (``channel_stagger``)
    stagger_replans: int = dataclasses.field(
        default=0, metadata={"aggregate": True})
    #: gap probes skipped because the per-batch busy-time lower bound
    #: could not fit the idle window (ROADMAP timeline follow-up (b))
    pruned_probes: int = dataclasses.field(
        default=0, metadata={"aggregate": True})


@dataclasses.dataclass(eq=False)
class FlushEvent:
    """One scheduler flush: the batch it drained and the plan it booked."""

    time: float
    arrivals: list[OnlineArrival]
    users: np.ndarray         # fleet indices, queue (arrival) order
    schedule: Schedule
    gpu_free: float           # absolute time the GPU frees (Eq. 22)
    violations: int           # requests past their point of no return
    seq: int = -1             # index into the scheduler's flush timeline
    replanned: int = 0        # preemption re-plans applied (tenancy layer)
    #: the per-user effective-rate snapshot the plan priced Eqs. 3-4 with
    #: (None = the fleet's solo view) — re-plans of this batch reuse it so
    #: trial-cache solves stay bit-identical to fresh ones
    plan_rates: np.ndarray | None = None
    #: planned vs channel-realized completion of the batch's LAST upload
    #: (absolute s; NaN without a channel)
    upload_planned: float = float("nan")
    upload_actual: float = float("nan")
    #: the channel session holding this flush's realized upload spans
    upload_session: object = None
    channel_replans: int = 0  # actualization re-plans this flush took


@dataclasses.dataclass(eq=False)
class GpuFreeEvent:
    """The GPU occupancy booked by ``flush`` has ended."""

    time: float
    flush: FlushEvent


@dataclasses.dataclass(eq=False)
class UploadEvent:
    """The channel realized the LAST upload of ``flush``'s batch — the
    instant the accelerator can genuinely start it.  ``planned`` is where
    Eqs. 3-4 at the plan's rates expected that upload to land; the
    scheduler's actualization pass has already reconciled the divergence
    by the time this event fires."""

    time: float               # realized completion (absolute s)
    flush: FlushEvent
    planned: float


@dataclasses.dataclass(eq=False)
class _SpecEntry:
    """One link of the plan-ahead speculation chain: the exact run key it
    predicts (``(scheduler id, arrival identities, fire time)``), the
    channel digest its rate snapshot priced, and — for the chain head
    only — the live occupancy cursor it planned behind (deeper links
    derive theirs from the predecessor's future, so it is ``None`` until
    consumption checks it against reality)."""

    key: tuple
    dig: tuple | None
    t_free: float | None


class OnlineScheduler:
    """Event-driven online J-DOB scheduler (see module docstring).

    The scheduler is deliberately deterministic: given the same submitted
    trace it reproduces :func:`simulate_online_reference` bit for bit —
    the flush decision compares the next arrival against the *policy* time
    with arrivals winning ties, and the flush itself fires at
    ``max(policy_time, newest queued arrival)``.
    """

    def __init__(self, profile: TaskProfile, fleet: DeviceFleet,
                 edge: EdgeProfile, *, policy: str = "slack",
                 window: float = 0.0, keep_frac: float = 0.7,
                 rho: float = 0.03e9, inner: Callable = jdob_plus,
                 service: PlannerService | None = None,
                 on_flush: Callable[[FlushEvent], None] | None = None,
                 on_gpu_free: Callable[[GpuFreeEvent], None] | None = None,
                 on_replan: Callable[[FlushEvent], None] | None = None,
                 on_upload: Callable[[UploadEvent], None] | None = None,
                 history: int | None = None,
                 occupancy: str = "serialized",
                 timeline: GpuTimeline | None = None,
                 channel: ChannelModel | None = None,
                 channel_aware: bool = True,
                 channel_stagger: bool = False,
                 channel_replan_limit: int = 1,
                 dvfs_slack_frac: float = 0.0,
                 dvfs_quiescent: bool = True,
                 batch_window: float = 0.0,
                 plan_workers: int = 0,
                 plan_depth: int = 1,
                 telemetry: Telemetry | None = None):
        assert policy in POLICIES, f"unknown policy {policy!r}"
        assert batch_window >= 0.0
        assert plan_workers >= 0
        assert plan_depth >= 1
        assert occupancy in OCCUPANCY_MODES, \
            f"unknown occupancy mode {occupancy!r}"
        assert 0.0 <= dvfs_slack_frac <= 1.0
        self.profile = profile
        self.fleet = fleet
        self.edge = edge
        self.policy = policy
        self.window = window
        self.keep_frac = keep_frac
        self.rho = rho
        self.inner = inner
        self.service = (service if service is not None
                        else PlannerService(profile, edge, rho=rho))
        assert self.service.rho == rho, "service rho disagrees"
        self._planner = self.service.planner_for(inner)
        self.on_flush = on_flush
        self.on_gpu_free = on_gpu_free
        self.on_replan = on_replan
        self.on_upload = on_upload
        #: the uplink capacity owner (repro.core.channel): explicit arg
        #: wins, else the fleet's attached channel, else None — the seed's
        #: frozen-scalar semantics with zero channel bookkeeping
        self.channel = channel if channel is not None else fleet.channel
        #: plan against the channel's contended-rate snapshot (True) or at
        #: the nominal solo rates (False — the baseline the channel bench
        #: measures channel-aware planning against)
        self.channel_aware = channel_aware
        #: stagger-aware pricing (ROADMAP plan/realize follow-up (c)): the
        #: contended snapshot assumes the WHOLE batch uploads concurrently
        #: from the flush instant, but uploads really start staggered at
        #: each device's compute finish — once the first plan commits the
        #: f_m's, one bounded re-plan re-prices Eqs. 3-4 at the channel's
        #: staggered-rate view of those starts (never more pessimistic
        #: than the concurrent snapshot, so the plan only tightens)
        self.channel_stagger = channel_stagger
        #: bounded actualization: how many re-plans one flush may take
        #: when realized rates diverge beyond what edge DVFS can absorb
        self.channel_replan_limit = channel_replan_limit
        # point of no return offsets: minimum local latency at f_max
        self._l_min = fleet.zeta * profile.v()[-1] / fleet.f_max
        # the smallest GPU busy time any offload of this profile can have
        # (best block boundary, batch of 1, f_e,max) — idle windows
        # narrower than this cannot host a flush, so gap probes skip them
        _phi_base, _phi_slope = edge.phi_coeffs(profile)
        self._min_gap = float(np.min(_phi_base[:-1] + _phi_slope[:-1])
                              / edge.f_max)
        # per-partition single-sample busy time at f_e,max — the φ part of
        # the per-batch busy-time lower bound gap-probe pruning uses
        self._phi1 = (_phi_base[:-1] + _phi_slope[:-1]) / edge.f_max
        #: epsilon batching window for :meth:`step_batch` (s): an arrival
        #: landing within this of the armed flush time is absorbed into
        #: the waiting batch instead of flushing first.  0 (default) keeps
        #: :meth:`run_batched` bit-identical to the event-at-a-time
        #: :meth:`run` — the parity the scale tests pin.
        self.batch_window = batch_window
        #: plan-ahead workers for :meth:`run_batched` (0 = synchronous):
        #: while batch k's flush finishes its bookkeeping, a pool worker
        #: speculatively solves the PREDICTED flush k+1; the event loop
        #: consumes the result only on an exact prediction match, so
        #: results are bit-identical at every worker count (parity-gated)
        self.plan_workers = plan_workers
        #: speculation depth: how many successive drained runs the
        #: plan-ahead pool may look past the booked flush.  Depth d > 1
        #: chains the PREDICTED occupancy cursor — entry d's solve waits
        #: on entry d−1's speculative end — and the whole chain dies on
        #: any divergence (mid-run submit, preemption commit, channel
        #: digest drift, cursor mismatch), so results stay bit-identical
        #: at every depth.  1 (default) is PR 7's one-flush lookahead.
        self.plan_depth = plan_depth
        self._plan_ahead = None                   # PlanAheadPool while piped
        self._mirror = None                       # sorted arrival-pop replay
        self._mirror_pos = 0
        self._spec_chain: list = []               # outstanding speculations
        self._seq = itertools.count()
        self._arrivals: list = []                 # heap of pending arrivals
        self._timers: list = []                   # heap of gpu-free events
        self._queue: list[OnlineArrival] = []
        self.now = 0.0
        #: the occupancy subsystem this scheduler books against — its own
        #: private timeline by default, the arbiter's SHARED one in the
        #: multi-tenant regime
        self.occupancy = occupancy
        self.timeline = (timeline if timeline is not None
                         else GpuTimeline(mode=occupancy))
        self.tenant_id = 0
        #: telemetry (None = disabled): emission sites are read-only
        #: observers guarded on ``self._tr.enabled`` — results are
        #: bit-identical with tracing on vs off, and the null tracer is
        #: allocation-free on the hot paths (tests/core/test_telemetry.py)
        self.telemetry = telemetry
        self._tr = telemetry.tracer if telemetry is not None else NULL_TRACER
        if self._tr.enabled:
            self.timeline.tracer = self._tr
            self._tr.name_track(TID_GPU, "GPU")
            self._tr.name_track(TID_UPLINK, "uplink")
            self._tr.name_track(TID_PLANNER, "planner")
        #: per-flush DVFS aggressiveness while traffic is still pending:
        #: the fraction of a TAIL slot's residual slack the edge-frequency
        #: rescale may consume.  Stretching the tail extends the horizon
        #: every later flush plans behind (measured net-negative under
        #: load), so the default is 0 — tail slots stretch only when the
        #: system is quiescent (no pending arrivals anywhere), where the
        #: full window to the batch deadline is free.  Gap-filled slots
        #: always use their full window: it is bounded by an existing
        #: reservation, so the occupancy cost is already sunk.
        self.dvfs_slack_frac = dvfs_slack_frac
        #: whether a quiescent tail (no pending arrivals anywhere) may
        #: stretch to its deadline for free.  Safe for one-shot traces —
        #: nothing submitted can ever plan behind the stretch — but a
        #: LIVE server feeding ``submit()`` between ``step()`` calls
        #: looks quiescent between bursts, and a request arriving right
        #: after a stretch plans behind the inflated horizon: such
        #: deployments should pass ``dvfs_quiescent=False``
        self.dvfs_quiescent = dvfs_quiescent
        self._slot_limit = np.inf                 # abs end bound of the slot
        self._slot_saved = 0.0                    # DVFS J saved this flush
        self._slot_tf = 0.0                       # residual the plan used
        self._slot_stretch_orig = None            # pre-quiescent-stretch s
        self._flush_upload = None                 # (planned, actual) abs s
        self._flush_session = None                # channel UploadSession
        self._flush_rates = None                  # effective-rate snapshot
        self.upload_error = 0.0
        self.channel_replans = 0
        self.stagger_replans = 0
        self.realized_late = 0
        self.probe_prunes = 0
        self.gpu_free = 0.0                       # mirror: timeline horizon
        #: rich per-flush events; a live server running forever should cap
        #: this with ``history=N`` (aggregates below are always complete —
        #: they are scalars, not pinned payloads/schedules)
        self.flushes: list[FlushEvent] = []
        self.history = history
        self.violations = 0
        self.per_user_energy = np.zeros(fleet.M)
        self._batches: list[int] = []
        self._flush_times: list[float] = []
        self._f_edges: list = []

    # ---- submission ----------------------------------------------------
    def submit(self, arrival: OnlineArrival) -> None:
        """Queue a future arrival (heap-ordered; equal times keep
        submission order, matching the reference's stable sort).

        Arrivals must be causal: once :meth:`step` has advanced the clock,
        submitting an arrival earlier than ``now`` would rewind the event
        heap past decisions already taken (flushes planned, GPU booked), so
        it raises instead of silently corrupting the timeline."""
        assert 0 <= arrival.user < self.fleet.M
        if arrival.arrival < self.now:
            raise ValueError(
                f"arrival at t={arrival.arrival:.9g}s is earlier than the "
                f"scheduler clock t={self.now:.9g}s; the event heap cannot "
                f"rewind — submit arrivals in causal order")
        self._unstretch_tail(arrival.arrival)
        heapq.heappush(self._arrivals,
                       (arrival.arrival, next(self._seq), arrival))
        if self._mirror is not None:
            # a mid-run submission invalidates the pop-order replay the
            # plan-ahead prediction walks; disable speculation (results
            # are unchanged — every flush falls back to the synchronous
            # solve) rather than track live heap edits
            self._mirror = None
            self._invalidate_speculation()

    def _unstretch_tail(self, t: float) -> None:
        """ROADMAP timeline follow-up (a): a quiescent-tail DVFS stretch
        was free only because nothing could plan behind it — the arrival
        being submitted breaks that premise, so every stretched
        reservation of THIS scheduler whose GPU run has not started by
        ``t`` is restored to its unstretched f_e (geometry via
        :meth:`GpuTimeline.unstretch`, accounting via
        :meth:`replan_flush` with the snapshotted pre-stretch schedule).
        One-shot traces are untouched: they submit everything before the
        clock moves, when no reservation exists yet."""
        tl = self.timeline
        if tl.mode != "interleaved":
            return
        for r in list(tl.reservations):
            if (r.tenant == self.tenant_id and r.flush is not None
                    and r.stretched_from is not None and r.gpu_start > t):
                orig = r.stretched_from
                tl.unstretch(r, end=r.flush.time + orig.t_free_end,
                             f_edge=orig.f_edge)
                self.replan_flush(r.flush, 0.0, schedule=orig)
                self.gpu_free = tl.horizon

    def submit_many(self, arrivals) -> None:
        for a in arrivals:
            self.submit(a)

    # ---- telemetry emission (read-only observers) ----------------------
    def _ttid(self) -> int:
        """This scheduler's tenant track id (named lazily — the tenancy
        layer assigns ``tenant_id`` after construction)."""
        tid = tenant_tid(self.tenant_id)
        self._tr.name_track(tid, f"tenant {self.tenant_id}")
        return tid

    def _trace_arrival(self, a: OnlineArrival) -> None:
        self._tr.instant("arrival", a.arrival, self._ttid(),
                         {"user": int(a.user), "deadline": a.abs_deadline})
        self.telemetry.metrics.inc("loop.arrivals")

    # ---- policy --------------------------------------------------------
    def _policy_time(self) -> float:
        """The armed flush time for the current (non-empty) queue."""
        return self._policy_time_of(self._queue)

    def _policy_time_of(self, q: list) -> float:
        """:meth:`_policy_time` over an explicit queue (the plan-ahead
        prediction replays policy math over hypothetical queues)."""
        if self.policy == "immediate":
            return q[-1].arrival
        if self.policy == "window":
            return q[0].arrival + self.window
        if self.policy == "slack":             # keep ≥ keep_frac budget
            return min(a.arrival + (1.0 - self.keep_frac) * a.rel_deadline
                       for a in q)
        # lastcall: the earliest point of no return
        return min(a.abs_deadline - float(self._l_min[a.user])
                   for a in q) - 1e-6

    # ---- planning ------------------------------------------------------
    def _plan(self, sub: DeviceFleet, t_free: float) -> Schedule:
        """Plan one (sub-fleet, t_free) batch through the shared service
        (sequential fallback for arbitrary ``inner`` callables)."""
        if self._tr.enabled:
            # sim-time dispatch marker; the wall-clock materialization
            # latency lives in PlannerStats' perf_counter_ns histogram
            self._tr.instant("plan.dispatch", self.now, TID_PLANNER,
                             {"tenant": self.tenant_id,
                              "batch": int(sub.M), "t_free": t_free})
            self.telemetry.metrics.inc("planner.dispatches")
        if self._planner is not None:
            return self._planner.plan([sub], [t_free])[0]
        return self.inner(self.profile, sub, self.edge, t_free=t_free,
                          rho=self.rho)

    def _plan_event(self, ev: FlushEvent, t_free: float) -> Schedule:
        """Re-plan an existing flush's batch (same members, same flush
        time) against a different residual occupancy — accounting-free.
        Re-plans price Eqs. 3-4 at the SAME effective-rate snapshot the
        original plan used (``ev.plan_rates``), so a cached trial solve
        and a fresh one stay bit-identical."""
        rel = np.array([a.abs_deadline - ev.time for a in ev.arrivals])
        sub = dataclasses.replace(self.fleet.subset(ev.users), deadline=rel)
        if ev.plan_rates is not None:
            sub = dataclasses.replace(sub, rate=ev.plan_rates)
        return self._plan(sub, t_free)

    # ---- GPU booking hooks (overridden by the tenancy layer) -----------
    def _t_free(self, now: float, sub: DeviceFleet | None = None,
                arrivals: list[OnlineArrival] | None = None) -> float:
        """Residual GPU occupancy (s) the flush at ``now`` plans against
        behind EVERYTHING reserved (the serialized tail).  The base
        scheduler owns its timeline alone; the tenancy layer overrides
        this to request a slot from the shared timeline (and possibly
        preempt queued batches)."""
        return self.timeline.t_free(now)

    def _plan_slot(self, now: float, sub: DeviceFleet,
                   arrivals: list[OnlineArrival]) -> Schedule:
        """Plan the flush into its occupancy slot.  Serialized mode plans
        behind the booking horizon — the scalar Eq. 22 path, bit for bit.
        Interleaved mode first tries the timeline's idle windows in start
        order (earliest feasible slot): a plan that fits entirely inside a
        gap commits there, in front of later reservations; otherwise the
        flush falls through to the serialized tail.  ``_slot_limit``
        records the slot's absolute end bound for the per-flush DVFS
        rescale."""
        self._slot_limit = np.inf
        self._slot_saved = 0.0
        self._slot_stretch_orig = None
        if self.occupancy == "interleaved":
            t_tail = self.timeline.t_free(now)
            for g0, g1 in self.timeline.gaps(now):
                tf = max(g0 - now, 0.0)
                if tf >= t_tail - 1e-15:
                    break                     # reached the serialized tail
                if g1 - max(g0, now) < self._min_gap:
                    continue                  # too narrow for any offload
                if now + self._min_busy_bound(sub, tf) > g1 + 1e-9:
                    # ROADMAP follow-up (b): no offload of THIS batch can
                    # end inside the window, so don't pay a planner
                    # dispatch to find that out (an all-local plan is
                    # slot-independent, so skipping cannot change results)
                    self.probe_prunes += 1
                    continue
                s = self._plan(sub, tf)
                if not s.offload.any():
                    self._slot_tf = tf
                    return s                  # no GPU needed at all
                if now + s.t_free_end <= g1 + 1e-12:
                    self._slot_limit = g1
                    self._slot_tf = tf
                    self.timeline.gap_fills += 1
                    return s
        tf = self._t_free(now, sub, arrivals)
        self._slot_tf = tf
        s = self._take_plan_ahead(now, arrivals, tf)
        if s is not None:
            return s
        return self._plan(sub, tf)

    def _min_busy_bound(self, sub: DeviceFleet, tf: float) -> float:
        """A lower bound (s, relative to now) on the END of any offloading
        plan for this batch behind ``tf`` seconds of residual occupancy:
        the GPU cannot finish before the fastest member's fastest-boundary
        upload lands (γ at f_max, the plan's own rates) plus one sample's
        suffix at f_e,max.  Bounds every (ñ, f_e, batch) choice from
        below, so pruning a window it cannot fit never changes results."""
        v = self.profile.v()
        gam = (self.profile.O[:-1] / sub.rate[:, None]
               + sub.zeta[:, None] * v[:-1] / sub.f_max[:, None]).min(axis=0)
        return float(np.min(np.maximum(tf, gam) + self._phi1))

    def _post_plan(self, now: float, arrivals: list[OnlineArrival],
                   s: Schedule) -> Schedule:
        """Hook between planning and accounting.  Under interleaved
        occupancy the committed flush re-selects its edge frequency
        against the reservation's ACTUAL slack — the window from the GPU
        start to the earlier of the batch's tightest deadline and the
        slot's end bound (closed form, see
        :func:`~repro.core.timeline.rescale_edge_dvfs`).  Serialized mode
        is the identity: Eq. 22 behaviour, bit for bit."""
        if self.occupancy != "interleaved" or not s.offload.any():
            return s
        # bound by the tightest OFFLOADED member's deadline — a local
        # member's completion never depends on the GPU run, and the
        # reservation records the same offloaded bound (its ``deadline``
        # field), so the stretched end stays inside what the timeline
        # promises
        deadline = min(a.abs_deadline
                       for a, off in zip(arrivals, s.offload) if off)
        limit = min(deadline, self._slot_limit)
        window = limit - (now + s.gpu_start)
        tail = not np.isfinite(self._slot_limit)
        quiet = (tail and self.dvfs_quiescent and not self._pending_work())
        if tail and not quiet:
            # tail slot with traffic still pending: stretching extends the
            # horizon every later flush plans behind, so consume only the
            # configured fraction of the slack (default: none).  A
            # quiescent tail — nothing left anywhere that could plan
            # behind this reservation — stretches to the deadline for
            # free, and a gap-filled slot's window is bounded by an
            # existing reservation (sunk cost) and is used in full.
            window = s.gpu_busy + self.dvfs_slack_frac * (window
                                                          - s.gpu_busy)
        pre = s
        s, saved = rescale_edge_dvfs(s, window=window, f_min=self.edge.f_min)
        if saved > 0.0:
            self.timeline.dvfs_rescales += 1
            self.timeline.dvfs_energy_saved += saved
            self._slot_saved = saved        # booked onto the reservation
            if self._tr.enabled:
                self._tr.instant(
                    "dvfs.rescale", now, TID_GPU,
                    {"tenant": self.tenant_id, "saved_j": saved,
                     "f_edge_ghz": s.f_edge / 1e9, "quiescent": quiet})
                self.telemetry.metrics.inc("dvfs.rescales")
                self.telemetry.metrics.inc("dvfs.energy_saved_j", saved)
            if quiet:
                # snapshot the unstretched plan so a submit() arriving
                # before this reservation starts can roll the stretch
                # back (follow-up (a) — the stretch was free only while
                # nothing could plan behind it)
                self._slot_stretch_orig = pre
        return s

    def _stagger_replan(self, now: float, arrivals: list[OnlineArrival],
                        idx: np.ndarray, sub: DeviceFleet, s: Schedule
                        ) -> tuple[DeviceFleet, Schedule]:
        """One bounded re-plan at the channel's stagger-aware rates
        (``channel_stagger``).  The first plan committed the device
        frequencies, hence each member's compute finish — the actual,
        STAGGERED upload starts.  Pricing those against the channel
        (:meth:`~repro.core.channel.ChannelModel.staggered_rates`) is
        never more pessimistic than the flush-instant concurrent
        snapshot, so the re-plan can only recover headroom; the updated
        ``sub`` flows into actualization so planned-vs-realized is judged
        against the rates the plan actually priced."""
        ch = self.channel
        if (not self.channel_stagger or ch is None or ch.static
                or not self.channel_aware or not s.offload.any()):
            return sub, s
        comp, nbytes, solo, keys = self._upload_geometry(s, idx, now)
        r_stag = ch.staggered_rates(solo, comp, nbytes, keys=keys)
        rates = np.array(sub.rate, np.float64)
        if np.allclose(r_stag, rates[s.offload], rtol=1e-9, atol=0.0):
            return sub, s                # stagger bought nothing: keep s
        rates[s.offload] = r_stag
        sub2 = dataclasses.replace(sub, rate=rates)
        s2 = self._plan(sub2, self._slot_tf)
        if (np.isfinite(self._slot_limit) and s2.offload.any()
                and now + s2.t_free_end > self._slot_limit + 1e-12):
            # the re-plan outgrew its gap-filled window (a faster uplink
            # can justify a bigger batch): keep the plan that fits
            return sub, s
        self._flush_rates = rates
        self.stagger_replans += 1
        return sub2, s2

    def _pending_work(self) -> bool:
        """Is any traffic still pending that could flush behind the
        reservation being committed?  The base scheduler owns the GPU
        alone, so only its own heaps matter; the tenancy layer asks the
        whole arbiter."""
        return bool(self._arrivals or self._queue)

    def _book(self, now: float, s: Schedule) -> float:
        """The absolute GPU-free time the flush event reports: the
        reservation's own Eq. 22 end for an offloading flush; all-local
        flushes leave occupancy alone, but the event reports when the GPU
        is actually free, never before the flush."""
        if s.offload.any():
            return now + s.t_free_end
        return max(self.timeline.horizon, now)

    def _after_flush(self, ev: FlushEvent) -> None:
        """Post-booking hook, runs before ``on_flush``: registers the
        flush's reservation on the timeline (tenancy extends this with
        re-planning of preempted batches + queue scrubbing)."""
        if ev.schedule.offload.any():
            self.timeline.book(self.tenant_id, ev,
                               dvfs_saved=self._slot_saved,
                               stretched_from=self._slot_stretch_orig,
                               upload_planned=ev.upload_planned,
                               upload_actual=ev.upload_actual)
        self.gpu_free = self.timeline.horizon
        # booking done → the next flush's occupancy snapshot is (usually)
        # final: launch its speculative solve so it overlaps the rest of
        # this flush's bookkeeping + the next arrival drain.  No-op when
        # pipelining is off.
        self._speculate()

    # ---- channel actualization -----------------------------------------
    def _upload_geometry(self, s: Schedule, users: np.ndarray, at: float):
        """One flush's upload geometry: ``(starts, nbytes, solo, keys)``.
        Each offloader's upload begins at its device-compute finish (the
        committed f_m) and carries the partition boundary's activation —
        the single source both flush-time realization and re-plan
        re-realization derive from."""
        off = s.offload
        nbytes = float(self.profile.O[s.partition])
        v_nt = float(self.profile.v()[s.partition])
        comp = at + self.fleet.zeta[users][off] * v_nt / s.f_device[off]
        solo = self.fleet.rate[users][off]
        keys = [(self.tenant_id, int(u)) for u in users[off]]
        return comp, nbytes, solo, keys

    def _actualize(self, now: float, arrivals: list[OnlineArrival],
                   idx: np.ndarray, sub: DeviceFleet, s: Schedule,
                   depth: int = 0) -> Schedule:
        """Realize the flush's uploads on the channel and reconcile the
        plan with what the medium actually delivered.  The actual
        ``gpu_start`` is derived from the realized upload finishes:

        * realized == planned (no channel, the static one, or divergence
          below noise) — the schedule is returned untouched, bit for bit;
        * uploads landed EARLY — the occupancy simply shifts forward
          (later flushes inherit the shorter queue);
        * uploads landed LATE — the reservation window shrank: first the
          per-flush DVFS machinery runs the edge FASTER into what is left
          (:func:`~repro.core.timeline.respeed_edge_dvfs`); when even
          f_e,max cannot close the gap, a BOUNDED re-plan
          (``channel_replan_limit``) re-solves the batch at the observed
          rates — the planner may drop members to local or move the
          partition — and the result is realized again.  Residual misses
          are counted in ``realized_late``.
        """
        ch = self.channel
        if ch is None or not s.offload.any():
            return s
        off = s.offload
        comp, nbytes, solo, keys = self._upload_geometry(s, idx, now)
        planned_fin = comp + nbytes / sub.rate[off]
        real_fin, session = ch.realize(solo, comp, nbytes, keys=keys)
        self._flush_session = session
        up_plan = float(planned_fin.max())
        up_real = float(real_fin.max())
        self._flush_upload = (up_plan, up_real)
        err = abs(up_real - up_plan)
        self.upload_error += err
        tf_abs = now + self._slot_tf      # the residual the plan was given
        g_plan = now + s.gpu_start
        g_real = max(tf_abs, up_real)
        deadline = min(a.abs_deadline
                       for a, o in zip(arrivals, s.offload) if o)
        limit = min(deadline, self._slot_limit)
        if g_real > g_plan and now + (g_real - now) + s.gpu_busy > \
                limit + 1e-9:
            window = limit - g_real
            f_need = (s.edge_phi / window if window > 0 else np.inf)
            if (f_need > self.edge.f_max * (1 + 1e-9)
                    and depth < self.channel_replan_limit):
                # even flat-out the edge cannot close the gap: re-plan at
                # the observed per-user rates (bounded) — the planner may
                # move the partition or drop members to local computing
                ch.retract(session)
                self._flush_session = None
                self._flush_upload = None
                if self._slot_saved > 0.0:
                    # the pre-actualization stretch never materializes
                    self.timeline.dvfs_rescales -= 1
                    self.timeline.dvfs_energy_saved -= self._slot_saved
                    self._slot_saved = 0.0
                self._slot_stretch_orig = None
                if np.isfinite(self._slot_limit):
                    # a gap-filled slot that diverged this badly falls
                    # back to the serialized tail: re-validating the
                    # shrunken window is not worth risking a re-plan
                    # whose end overlaps the reservation behind the gap
                    self._slot_tf = self.timeline.t_free(now)
                    self._slot_limit = np.inf
                    self.timeline.gap_fills -= 1
                rates_obs = np.array(sub.rate, np.float64)
                rates_obs[off] = nbytes / np.maximum(real_fin - comp, 1e-12)
                sub2 = dataclasses.replace(sub, rate=rates_obs)
                self.channel_replans += 1
                if self._tr.enabled:
                    self._tr.instant(
                        "channel.replan", now, TID_UPLINK,
                        {"tenant": self.tenant_id, "depth": depth + 1,
                         "planned": up_plan, "realized": up_real})
                    self.telemetry.metrics.inc("channel.replans")
                self._flush_rates = rates_obs
                s2 = self._plan(sub2, self._slot_tf)
                return self._actualize(now, arrivals, idx, sub2, s2,
                                       depth + 1)
        # ---- terminal: reconcile the committed plan with what happened --
        if err > 1e-12 and abs(g_real - g_plan) > 1e-12:
            shifted = dataclasses.replace(
                s, t_free_end=(g_real - now) + s.gpu_busy)
            if g_real > g_plan and now + shifted.t_free_end > limit + 1e-9:
                # late uploads shrank the window: run the edge faster
                # (clipped at f_e,max — the residue is a realized miss)
                shifted, extra = respeed_edge_dvfs(shifted,
                                                   window=limit - g_real,
                                                   f_max=self.edge.f_max)
                if extra > 0.0 and self._tr.enabled:
                    self._tr.instant(
                        "dvfs.respeed", now, TID_GPU,
                        {"tenant": self.tenant_id, "extra_j": extra,
                         "f_edge_ghz": shifted.f_edge / 1e9})
                    self.telemetry.metrics.inc("dvfs.respeeds")
                    self.telemetry.metrics.inc("dvfs.energy_extra_j", extra)
                if extra > 0.0 and self._slot_saved > 0.0:
                    # the speed-up eats into the per-flush stretch this
                    # same flush was credited with — the reports must not
                    # claim a saving the channel took back
                    undo = min(extra, self._slot_saved)
                    self._slot_saved -= undo
                    self.timeline.dvfs_energy_saved -= undo
                    if self._slot_saved <= 0.0:
                        self.timeline.dvfs_rescales -= 1
                        self._slot_stretch_orig = None
            s = shifted
        # Eq. 4 actualization: the radio is on for the REALIZED upload,
        # so each offloader's uplink energy is (finish − start)·p_u — the
        # plan priced it at the snapshot rate.  Sub-ppb deltas are pure
        # float reassociation noise ((start + d) − start ≠ d in FP), not
        # channel divergence: zeroing them keeps the static channel (and
        # every realized-as-planned upload) bit-identical to the seed
        # accounting.  This is the term that makes nominal-rate planning
        # pay for its optimism on a contended medium.
        dur_plan = nbytes / sub.rate[off]
        diff = real_fin - comp - dur_plan
        diff = np.where(np.abs(diff) <= 1e-9 * np.maximum(dur_plan, 1e-12),
                        0.0, diff)
        d_up = diff * sub.p_up[off]
        d_sum = float(d_up.sum())
        if d_up.any():
            peu = np.array(s.per_user_energy, np.float64)
            peu[off] = peu[off] + d_up
            s = dataclasses.replace(
                s, per_user_energy=peu, energy=s.energy + d_sum,
                terms={**s.terms,
                       "uplink": s.terms.get("uplink", 0.0) + d_sum})
        if self._slot_stretch_orig is not None:
            # keep the un-stretch snapshot coherent with the realized
            # channel: same upload realization (membership and device
            # frequencies are identical pre/post stretch), so the same
            # shift and Eq. 4 delta apply to it
            o = self._slot_stretch_orig
            if err > 1e-12 and abs(g_real - g_plan) > 1e-12:
                o = dataclasses.replace(
                    o, t_free_end=(g_real - now) + o.gpu_busy)
            if d_up.any():
                peu_o = np.array(o.per_user_energy, np.float64)
                peu_o[off] = peu_o[off] + d_up
                o = dataclasses.replace(
                    o, per_user_energy=peu_o, energy=o.energy + d_sum,
                    terms={**o.terms,
                           "uplink": o.terms.get("uplink", 0.0) + d_sum})
            self._slot_stretch_orig = o
        # realized misses: only when the channel genuinely diverged (a
        # non-diverged plan's end is the planner's own feasible one — the
        # float32 grid must not trip a float64 re-check), and never for
        # requests the flush already counted late (past their point of no
        # return — one miss, one violation)
        if err > 1e-12:
            end = now + s.t_free_end
            if end > deadline + 1e-9:
                late = sum(
                    1 for a, o in zip(arrivals, s.offload)
                    if o and end > a.abs_deadline + 1e-9
                    and (a.abs_deadline - now
                         >= self._l_min[a.user] - 1e-12))
                self.realized_late += late
                if late and self._tr.enabled:
                    self._tr.instant(
                        "realized.late", now, TID_UPLINK,
                        {"tenant": self.tenant_id, "count": late,
                         "end": end})
                    self.telemetry.metrics.inc("channel.realized_late", late)
        return s

    # ---- event processing ----------------------------------------------
    def _fire_timers(self, upto: float) -> None:
        while self._timers and self._timers[0][0] <= upto:
            t, _, ev = heapq.heappop(self._timers)
            if isinstance(ev, UploadEvent):
                if ev.flush.upload_actual != t:
                    continue        # flush re-planned away: stale timer
                if self.on_upload is not None:
                    self.on_upload(ev)
                continue
            if ev.flush.gpu_free != t:
                continue            # booking re-planned away: stale timer
            if self.on_gpu_free is not None:
                self.on_gpu_free(ev)

    def _flush(self, now: float) -> FlushEvent:
        with span("repro.loop.flush", flush=len(self._batches),
                  batch=len(self._queue)):
            self.now = now
            q, self._queue = self._queue, []
            idx = np.array([a.user for a in q])
            rel = np.array([a.abs_deadline - now for a in q])
            late = int(np.sum(rel < self._l_min[idx] - 1e-12))
            self.violations += late
            sub = dataclasses.replace(self.fleet.subset(idx), deadline=rel)
            self._flush_upload = None
            self._flush_session = None
            self._flush_rates = None
            if (self.channel is not None and not self.channel.static
                    and self.channel_aware):
                # plan against the channel's contended-rate snapshot: the
                # batch's members plus every upload already in flight
                # assumed concurrent (the jitted grid is unchanged — rates
                # were already a per-user input array)
                eff = self.channel.effective_rates(
                    sub.rate, now,
                    keys=[(self.tenant_id, int(u)) for u in idx])
                sub = dataclasses.replace(sub, rate=eff)
                self._flush_rates = eff
            s = self._plan_slot(now, sub, q)
            with span("repro.loop.book"):
                ev, s, gpu_free = self._book_flush(now, q, idx, sub, s, late)
            if self.on_flush is not None:
                self.on_flush(ev)
            if s.offload.any():
                heapq.heappush(self._timers,
                               (gpu_free, next(self._seq),
                                GpuFreeEvent(gpu_free, ev)))
            return ev

    def _book_flush(self, now: float, q: list, idx: np.ndarray,
                    sub: DeviceFleet, s: Schedule, late: int):
        """Everything between a flush's plan and its ``on_flush`` hook: the
        stagger re-plan, the post-plan rescale, channel actualization,
        energy accounting, the booking and the :class:`FlushEvent`.
        Returns the event, its final schedule and its GPU-free time."""
        sub, s = self._stagger_replan(now, q, idx, sub, s)
        s = self._post_plan(now, q, s)
        s = self._actualize(now, q, idx, sub, s)
        # np.add.at, not fancy-index +=: a user may appear twice in a batch
        np.add.at(self.per_user_energy, idx, s.per_user_energy)
        if s.offload.any():
            # edge energy attributed evenly across the batch
            np.add.at(self.per_user_energy, idx[s.offload], s.edge_share)
        gpu_free = self._book(now, s)
        ev = FlushEvent(now, q, idx, s, gpu_free, late,
                        seq=len(self._batches),
                        plan_rates=self._flush_rates,
                        upload_session=self._flush_session)
        if self._flush_upload is not None:
            ev.upload_planned, ev.upload_actual = self._flush_upload
            heapq.heappush(self._timers,
                           (ev.upload_actual, next(self._seq),
                            UploadEvent(ev.upload_actual, ev,
                                        ev.upload_planned)))
        self._batches.append(int(s.offload.sum()))
        self._flush_times.append(now)
        self._f_edges.append(float(s.f_edge) if s.offload.any() else None)
        self.flushes.append(ev)
        if self.history is not None and len(self.flushes) > self.history:
            del self.flushes[:-self.history]
        if self._tr.enabled:
            self._trace_flush(now, q, sub, s, ev)
        self._after_flush(ev)
        return ev, s, gpu_free

    def _trace_flush(self, now: float, q: list, sub: DeviceFleet,
                     s: Schedule, ev: FlushEvent) -> None:
        """Emit one flush's telemetry: the flush instant, the realized
        upload span, and every member's request-lifecycle span + record
        (arrival → flush → gpu_start → done, slack at completion).  A
        read-only observer — called only when tracing is enabled and
        never touching scheduler state."""
        tr = self._tr
        met = self.telemetry.metrics
        ttid = self._ttid()
        n_off = int(s.offload.sum())
        args = {"seq": ev.seq, "users": len(q), "batch": n_off,
                "partition": int(s.partition), "energy_j": float(s.energy),
                "late": ev.violations, "t_free": self._slot_tf}
        if n_off:
            args["f_edge_ghz"] = float(s.f_edge) / 1e9
        tr.instant("flush", now, ttid, args)
        if ev.upload_actual == ev.upload_actual:          # not NaN
            tr.span(f"upload b{ev.seq}", now, ev.upload_actual, TID_UPLINK,
                    {"tenant": self.tenant_id,
                     "planned": ev.upload_planned,
                     "realized": ev.upload_actual,
                     "err_s": abs(ev.upload_actual - ev.upload_planned)})
        met.inc("loop.flushes")
        met.inc("loop.violations", ev.violations)
        met.observe("loop.batch_size", n_off)
        for term, joules in s.terms.items():
            met.inc(f"energy.{term}_j", float(joules))
        done_off = now + float(s.t_free_end)
        g_start = now + float(s.gpu_start)
        v_tot = float(self.profile.v()[-1])
        edge_share = s.edge_share
        record = (self.telemetry.record_request
                  if self.telemetry.request_log else None)
        for i, a in enumerate(q):
            off_i = bool(s.offload[i])
            done = (done_off if off_i else
                    now + float(sub.zeta[i]) * v_tot / float(s.f_device[i]))
            slack = a.abs_deadline - done
            tr.span(f"req u{a.user}", a.arrival, done, ttid,
                    {"user": int(a.user), "offloaded": off_i,
                     "slack_s": slack})
            met.observe("loop.slack_s", slack)
            if record is not None:
                record({"tenant": self.tenant_id, "user": int(a.user),
                        "arrival": a.arrival, "flushed": now,
                        "gpu_start": g_start if off_i else None,
                        "done": done, "slack": slack, "offloaded": off_i,
                        "flush_seq": ev.seq,
                        "energy_j": float(s.per_user_energy[i])
                        + (edge_share if off_i else 0.0)})

    def replan_flush(self, ev: FlushEvent, t_free: float,
                     idle_gpu_free: float | None = None,
                     schedule: Schedule | None = None) -> Schedule:
        """Re-plan an already-flushed, queued-but-not-started batch against
        an updated residual occupancy (the tenancy layer's preemption
        path).  The old schedule's accounting is undone and the batch
        re-planned at its ORIGINAL flush time with the new ``t_free`` —
        bit-identical to having planned it there in the first place: flush
        time, membership and the violation count are unchanged; energies,
        batch size and the booked occupancy follow the new plan.  Fires
        ``on_replan`` (a live server re-executes the batch) and re-arms the
        gpu-free timer.  ``idle_gpu_free`` is the absolute GPU-free time to
        report if the new plan offloads nothing (defaults to the flush
        time).  ``schedule`` short-circuits the re-solve with a plan the
        caller already holds — the arbiter's preemption what-if caches its
        victim trial solves, and the caller guarantees the cached plan
        equals a fresh ``_plan_event(ev, t_free)`` bit for bit (the
        audit-trail test pins this).  Returns the new schedule."""
        old = ev.schedule
        idx = ev.users
        old_gpu_free = ev.gpu_free
        np.add.at(self.per_user_energy, idx, -old.per_user_energy)
        if old.offload.any():
            np.add.at(self.per_user_energy, idx[old.offload],
                      -old.edge_share)
        s = schedule if schedule is not None else self._plan_event(ev, t_free)
        np.add.at(self.per_user_energy, idx, s.per_user_energy)
        if s.offload.any():
            np.add.at(self.per_user_energy, idx[s.offload], s.edge_share)
            gpu_free = ev.time + s.t_free_end
        else:
            gpu_free = max(idle_gpu_free if idle_gpu_free is not None
                           else ev.time, ev.time)
        ev.schedule = s
        ev.gpu_free = gpu_free
        ev.replanned += 1
        if self._tr.enabled:
            self._tr.instant(
                "flush.replan", max(self.now, ev.time), self._ttid(),
                {"seq": ev.seq, "replanned": ev.replanned,
                 "energy_j": float(s.energy),
                 "delta_j": float(s.energy - old.energy)})
            self.telemetry.metrics.inc("loop.flush_replans")
        if 0 <= ev.seq < len(self._batches):
            self._batches[ev.seq] = int(s.offload.sum())
        if 0 <= ev.seq < len(self._f_edges):
            self._f_edges[ev.seq] = (float(s.f_edge) if s.offload.any()
                                     else None)
        self._rerealize_uploads(ev)
        # the old timer (if any) went stale via ev.gpu_free; re-arm unless
        # a still-valid timer already sits on the identical instant
        if s.offload.any() and not (old.offload.any()
                                    and gpu_free == old_gpu_free):
            heapq.heappush(self._timers,
                           (gpu_free, next(self._seq),
                            GpuFreeEvent(gpu_free, ev)))
        if self.on_replan is not None:
            self.on_replan(ev)
        return s

    def _rerealize_uploads(self, ev: FlushEvent) -> None:
        """A re-planned batch's uploads replace its old ones on the
        channel's books (span bookkeeping only — divergence reconciliation
        is bounded to the primary flush's actualization pass)."""
        if self.channel is None:
            return
        self.channel.retract(ev.upload_session)
        ev.upload_session = None
        old_actual = ev.upload_actual
        s = ev.schedule
        if not s.offload.any():
            ev.upload_planned = ev.upload_actual = float("nan")
            return
        off = s.offload
        comp, nbytes, solo, keys = self._upload_geometry(s, ev.users,
                                                         ev.time)
        rates = (ev.plan_rates if ev.plan_rates is not None
                 else self.fleet.rate[ev.users])[off]
        real_fin, ev.upload_session = self.channel.realize(
            solo, comp, nbytes, keys=keys)
        ev.upload_planned = float((comp + nbytes / rates).max())
        ev.upload_actual = float(real_fin.max())
        if ev.upload_actual != old_actual:
            heapq.heappush(self._timers,
                           (ev.upload_actual, next(self._seq),
                            UploadEvent(ev.upload_actual, ev,
                                        ev.upload_planned)))

    def next_event_time(self) -> float | None:
        """Absolute time of this scheduler's next event (arrival enqueue
        or policy flush), or ``None`` when drained — the peek a
        multi-tenant arbiter orders tenants by.  Mirrors :meth:`step`'s
        decision rule exactly and never mutates state."""
        if not self._queue:
            return self._arrivals[0][0] if self._arrivals else None
        t_policy = self._policy_time()
        if self._arrivals and self._arrivals[0][0] <= t_policy:
            return self._arrivals[0][0]
        return max(t_policy, self._queue[-1].arrival)

    def step(self):
        """Process the next event; returns it (:class:`OnlineArrival` for
        an enqueue, :class:`FlushEvent` for a flush) or ``None`` when
        drained.  GPU-free timers fire as the clock passes them."""
        if not self._queue:
            if not self._arrivals:
                self._fire_timers(np.inf)
                return None
            t, _, a = heapq.heappop(self._arrivals)
            self._mirror_pos += 1
            self._fire_timers(t)
            self.now = t
            self._queue.append(a)
            if self._tr.enabled:
                self._trace_arrival(a)
            return a
        t_policy = self._policy_time()
        if self._arrivals and self._arrivals[0][0] <= t_policy:
            t, _, a = heapq.heappop(self._arrivals)
            self._mirror_pos += 1
            self._fire_timers(t)
            self.now = t
            self._queue.append(a)
            if self._tr.enabled:
                self._trace_arrival(a)
            return a
        t_fire = max(t_policy, self._queue[-1].arrival)
        self._fire_timers(t_fire)
        return self._flush(t_fire)

    def run(self) -> OnlineResult:
        """Drain every pending event and summarize."""
        while self.step() is not None:
            pass
        return self.result()

    # ---- batched event loop (the fleet-scale path) ----------------------
    def _drain_arrivals(self, eps: float, gate=None,
                        admit=None) -> float | None:
        """Pop every arrival the event-at-a-time loop would pop before the
        next flush — plus, with ``eps`` > 0, arrivals landing within
        ``eps`` of the armed flush time — in ONE pass, maintaining the
        policy time incrementally (O(1) per absorbed arrival instead of
        :meth:`_policy_time`'s O(queue) rescan per event).  Returns the
        armed policy time for the drained queue, or ``None`` when the
        caller must not flush: either nothing is left anywhere, or
        ``gate`` stopped the drain (multi-tenant arbitration — another
        tenant's event is due first; re-arbitrate).

        ``gate(t) -> bool`` is consulted with each candidate arrival time
        before popping; returning False ends the drain (the arbiter's
        "would this tenant still win?" predicate — it may fire other
        tenants' timers as a side effect, which is why it is only called
        on times actually consumed or refused, never speculatively).
        ``admit(a) -> bool`` is consulted after each pop; returning False
        removes the arrival from the queue again (admission fallback) and
        the policy time is re-derived by full rescan — removals break the
        running-min argument, incremental updates don't.

        At ``eps == 0`` the absorb condition is exactly :meth:`step`'s
        arrival-wins-ties comparison, and each incremental policy update
        equals the full rescan (running min over the same floats; the
        lastcall ``− 1e-6`` commutes with ``min`` because float
        subtraction is monotone) — so the drain is bit-identical to
        stepping arrivals one at a time."""
        q, arr = self._queue, self._arrivals
        pol = self.policy
        t_policy = self._policy_time() if q else None
        while True:
            if not arr:
                return t_policy                     # None when q empty too
            t = arr[0][0]
            if q and t > t_policy + eps:
                return t_policy                     # policy says flush
            if gate is not None and not gate(t):
                return None                         # arbitration capped
            t, _, a = heapq.heappop(arr)
            self._mirror_pos += 1
            self._fire_timers(t)
            self.now = t
            q.append(a)
            if self._tr.enabled:
                self._trace_arrival(a)
            if admit is not None and not admit(a):
                q.pop()                             # admission fallback
                t_policy = self._policy_time() if q else None
                continue
            if t_policy is None:                    # queue was just seeded
                t_policy = self._policy_time()
            elif pol == "immediate":
                t_policy = t
            elif pol == "slack":
                t_policy = min(t_policy, a.arrival +
                               (1.0 - self.keep_frac) * a.rel_deadline)
            elif pol == "lastcall":
                t_policy = min(t_policy, a.abs_deadline
                               - float(self._l_min[a.user]) - 1e-6)
            # window: pinned by q[0], unchanged as the queue grows

    def step_batch(self):
        """Batched event processing: drain the whole arrival run preceding
        the next flush in one pass, then fire that flush.  Returns the
        :class:`FlushEvent` (every drained arrival is inside it) or
        ``None`` when the scheduler is empty.  With ``batch_window == 0``
        a :meth:`run_batched` drive is bit-identical to :meth:`run` —
        same flushes, same batches, same accounting — it just takes one
        pass per flush instead of one per event."""
        with span("repro.loop.drain"):
            t_policy = self._drain_arrivals(self.batch_window)
            if t_policy is None:
                self._fire_timers(np.inf)
                return None
            if self._planner is not None:
                # warm the flush's batch shape on the background compile
                # pool (no-op when cached) so a first-seen size overlaps
                # its XLA compile with the timer/bookkeeping work below,
                # and the next flush of this size class pays nothing
                from .jdob import _bucket
                self._planner.prefetch(
                    _bucket(len(self._queue), self._planner.min_user_bucket),
                    1)
            t_fire = max(t_policy, self._queue[-1].arrival)
            self._fire_timers(t_fire)
        return self._flush(t_fire)

    def run_batched(self) -> OnlineResult:
        """Drain every pending event through the batched loop and
        summarize.  Bit-identical to :meth:`run` at ``batch_window=0``
        (parity-gated in tests/core/test_scale.py); an epsilon window
        trades a bounded flush deferral for larger batches under load.

        With ``plan_workers > 0`` the loop pipelines: after each flush
        books its reservation, pool workers speculatively solve the
        PREDICTED next ``plan_depth`` flushes (queue membership + fire
        times replayed from the arrival heap's pop order, occupancy read
        from the timeline for the head and chained speculatively for
        deeper links, channel rates priced at the digest-pinned snapshot)
        while the main thread drains the next arrival run; a flush
        consumes the chain head only when its exact (members, fire-time,
        channel-digest, t_free) inputs match reality — any divergence
        (gap fill, preemption what-if, admission removal, channel
        actualization, mid-run ``submit()``) falls back to the
        synchronous solve and kills the chain.  The planner is
        deterministic for identical inputs, so consumed plans are bitwise
        the ones the synchronous path would have computed — pipelining
        changes wall-clock only, never results."""
        if self.plan_workers <= 0 or self._planner is None:
            while self.step_batch() is not None:
                pass
            return self.result()
        pool = self.service.plan_pool(self.plan_workers)
        self._pipeline_begin(pool)
        try:
            while self.step_batch() is not None:
                pass
        finally:
            self._pipeline_end()
            pool.flush()
        return self.result()

    # ---- pipelined planning (plan/execute overlap) ----------------------
    def _pipeline_begin(self, pool) -> None:
        """Arm plan-ahead speculation: snapshot the arrival heap's pop
        order (heap entries are ``(t, seq, a)`` with unique ``seq``, so
        ascending sort IS the exact pop order) and launch the first
        speculative solves."""
        self._plan_ahead = pool
        self._mirror = sorted(self._arrivals)
        self._mirror_pos = 0
        self._spec_chain = []
        self._speculate()

    def _pipeline_end(self) -> None:
        self._invalidate_speculation()
        self._plan_ahead = None
        self._mirror = None
        self._mirror_pos = 0

    def _peek_run_from(self, arr, pos: int, q: list):
        """One drained run replayed from mirror position ``pos`` with
        seed queue ``q``: ``(queue, fire time, next position)``, or
        ``None`` when nothing is left.  No state is touched — timers,
        gates and admission run only in the real drain (their absence
        here just turns a wrong prediction into a key miss)."""
        pol, eps = self.policy, self.batch_window
        t_policy = self._policy_time_of(q) if q else None
        while True:
            if pos >= len(arr):
                if not q:
                    return None
                return q, max(t_policy, q[-1].arrival), pos
            t = arr[pos][0]
            if q and t > t_policy + eps:
                return q, max(t_policy, q[-1].arrival), pos
            a = arr[pos][2]
            pos += 1
            q.append(a)
            if t_policy is None:
                t_policy = self._policy_time_of(q)
            elif pol == "immediate":
                t_policy = t
            elif pol == "slack":
                t_policy = min(t_policy, a.arrival +
                               (1.0 - self.keep_frac) * a.rel_deadline)
            elif pol == "lastcall":
                t_policy = min(t_policy, a.abs_deadline
                               - float(self._l_min[a.user]) - 1e-6)

    def _peek_runs(self, k: int) -> list:
        """Pure replay of :meth:`_drain_arrivals` over the pop-order
        mirror for the next (up to) ``k`` successive runs: the queue and
        fire time each of those flushes WILL have, as a list of
        ``(queue, fire time)``.  Each flush drains its whole queue, so
        run d + 1 reseeds from empty at run d's stopping position."""
        runs = []
        pos = self._mirror_pos
        q = list(self._queue)
        while len(runs) < k:
            nxt = self._peek_run_from(self._mirror, pos, q)
            if nxt is None:
                break
            q, t_fire, pos = nxt
            runs.append((q, t_fire))
            q = []
        return runs

    def _chan_digest(self):
        """The channel fingerprint a speculative plan's rate pricing is
        valid against: ``None`` on the bit-identical static path (no
        contended snapshot is taken there), the channel's
        ``state_digest()`` otherwise.  Equal digests + equal fire time
        guarantee a bitwise-equal ``effective_rates`` snapshot, which is
        what lets plan-ahead run under dynamic channels at all."""
        ch = self.channel
        if ch is None or ch.static or not self.channel_aware:
            return None
        return ch.state_digest()

    def _discard_chain(self, keep: int = 0) -> None:
        """Drop every speculation chained past position ``keep`` (pool
        entry + telemetry per evicted link)."""
        dead = self._spec_chain[keep:]
        if not dead:
            return
        del self._spec_chain[keep:]
        pool = self._plan_ahead
        for e in dead:
            if pool is not None:
                pool.discard(e.key)
            if self._tr.enabled:
                self._tr.instant("spec.evict", self.now, TID_PLANNER,
                                 {"tenant": self.tenant_id})
                self.telemetry.metrics.inc("spec.evictions")

    def _invalidate_speculation(self) -> None:
        """Kill the whole plan-ahead chain.  Called on every event that
        breaks the chained prediction wholesale: a mid-run ``submit()``
        (heap replay stale), a preemption commit (the shared occupancy
        cursor every link planned behind just moved), and pipeline
        teardown."""
        self._discard_chain(0)

    @staticmethod
    def _spec_solve(planner, sub, t_fire, h_in=None, tf=None, after=None):
        """The pool callable for one speculative run: solve ``sub`` at
        fire time ``t_fire`` behind either a cursor known at submit time
        (``h_in``/``tf`` — the live timeline, chain head) or the
        PREDICTED cursor of the previous link (``after``, a pool future —
        depth k > 1).  Returns ``(t_free used, predicted absolute horizon
        after this run, schedule)``; both derived values replicate
        :meth:`GpuTimeline.t_free` / :meth:`GpuTimeline.book` float ops
        exactly, so an undisturbed serialized tail chains bit-identical
        cursors and every link can hit."""
        def solve():
            if after is not None:
                _, h, _ = after.result()      # predecessor's predicted end
                t = max(h - t_fire, 0.0)      # == GpuTimeline.t_free
            else:
                h, t = h_in, tf
            s = planner.plan([sub], [t])[0]
            h2 = max(h, t_fire + s.t_free_end) if s.offload.any() else h
            return (t, h2, s)
        return solve

    def _speculate(self) -> None:
        """Predict the next ``plan_depth`` drained runs and keep the
        plan-ahead chain for them live.  Link 0 plans behind the live
        timeline cursor; link d > 0 plans behind link d−1's speculative
        end (its worker waits on the predecessor's future).  Under a
        dynamic channel in channel-aware mode, each link prices the
        effective-rate snapshot at its predicted fire time and records
        the channel digest it priced against — the link is consumed only
        while that digest still matches reality, so results stay
        bit-identical to the synchronous loop.  Chain maintenance is
        prefix-keep: the longest prefix whose predicted runs, digests and
        (for the head) live cursor are unchanged survives; everything
        past the first divergence is discarded and resubmitted."""
        pool = self._plan_ahead
        if pool is None or self._mirror is None or self._planner is None:
            return
        # deeper chains than the pool backlog would evict their own heads
        depth = min(self.plan_depth, 2 * pool.workers)
        runs = self._peek_runs(depth)
        dig = self._chan_digest()
        keys = [(id(self), tuple(id(a) for a in q), t_fire)
                for q, t_fire in runs]
        keep = 0
        for e, key in zip(self._spec_chain, keys):
            if e.key != key or e.dig != dig:
                break
            if e.t_free is not None and \
                    e.t_free != self.timeline.t_free(e.key[2]):
                break                 # head cursor stale (e.g. preemption)
            keep += 1
        self._discard_chain(keep)
        planner, ch = self._planner, self.channel
        for i in range(keep, len(runs)):
            q, t_fire = runs[i]
            if i > 0 and pool.peek(keys[i - 1]) is None:
                break                 # predecessor gone (backlog evicted)
            idx = np.array([a.user for a in q])
            rel = np.array([a.abs_deadline - t_fire for a in q])
            sub = dataclasses.replace(self.fleet.subset(idx), deadline=rel)
            if dig is not None:
                # exactly the contended snapshot _flush will take at this
                # fire time — bitwise, as long as the digest holds
                eff = ch.effective_rates(
                    sub.rate, t_fire,
                    keys=[(self.tenant_id, int(u)) for u in idx])
                sub = dataclasses.replace(sub, rate=eff)
            if i == 0:
                h = self.timeline.horizon
                tf = self.timeline.t_free(t_fire)
                fn = self._spec_solve(planner, sub, t_fire, h_in=h, tf=tf)
            else:
                tf = None             # known only once link i−1 resolves
                fn = self._spec_solve(planner, sub, t_fire,
                                      after=pool.peek(keys[i - 1]))
            pool.submit(keys[i], fn)
            self._spec_chain.append(_SpecEntry(keys[i], dig, tf))
            if self._tr.enabled:
                self._tr.instant("spec.start", self.now, TID_PLANNER,
                                 {"tenant": self.tenant_id, "batch": len(q),
                                  "t_fire": t_fire, "depth": i})
                self.telemetry.metrics.inc("spec.starts")
                if i > 0:
                    self.telemetry.metrics.inc("spec.chain_extends")
        if self._tr.enabled and self._spec_chain:
            self.telemetry.metrics.observe("spec.chain_depth",
                                           len(self._spec_chain))

    def _take_plan_ahead(self, now: float, arrivals: list,
                         tf: float) -> Schedule | None:
        """The speculative plan for THIS flush, or ``None`` (synchronous
        fallback).  The chain head is consumed only when its run key
        (membership + fire time), its channel digest and the occupancy
        cursor its worker actually planned behind all match reality
        bitwise; any mismatch kills the ENTIRE chain — deeper links
        planned behind the dead prediction's cursor.  The tenancy layer's
        preemption what-if plants ``_trial_plan`` for :meth:`_plan` to
        consume, which this must never bypass."""
        pool = self._plan_ahead
        if pool is None or not self._spec_chain:
            return None
        if getattr(self, "_trial_plan", None) is not None:
            return None
        stats = self._planner.stats if self._planner is not None else None
        head = self._spec_chain[0]
        key = (id(self), tuple(id(a) for a in arrivals), now)
        why, s = None, None
        if key != head.key:
            why = "key"
        elif head.dig != self._chan_digest():
            why = "digest"
        else:
            del self._spec_chain[:1]
            res = pool.take(key)
            if res is None:
                why = "taken"
            else:
                tf_used, _, s = res
                if tf_used != tf:
                    why, s = "t_free", None
        if why is not None:
            self._invalidate_speculation()
            if stats is not None:
                stats.plan_ahead_misses += 1
            if self._tr.enabled:
                self._tr.instant("spec.miss", now, TID_PLANNER,
                                 {"tenant": self.tenant_id, "why": why})
                self.telemetry.metrics.inc("spec.misses")
            return None
        if stats is not None:
            stats.plan_ahead_hits += 1
        if self._tr.enabled:
            self._tr.instant("spec.hit", now, TID_PLANNER,
                             {"tenant": self.tenant_id,
                              "batch": len(arrivals)})
            self.telemetry.metrics.inc("spec.hits")
        return s

    def result(self) -> OnlineResult:
        return OnlineResult(float(self.per_user_energy.sum()),
                            len(self._batches), list(self._batches),
                            self.violations, self.per_user_energy.copy(),
                            list(self._flush_times), list(self._f_edges),
                            upload_error=self.upload_error,
                            channel_replans=self.channel_replans,
                            realized_late=self.realized_late,
                            stagger_replans=self.stagger_replans,
                            pruned_probes=self.probe_prunes)


def simulate_online(arrivals: list[OnlineArrival],
                    profile: TaskProfile, fleet: DeviceFleet,
                    edge: EdgeProfile, *, policy: str = "slack",
                    window: float = 0.0, keep_frac: float = 0.7,
                    rho: float = 0.03e9,
                    inner: Callable = jdob_plus,
                    service: PlannerService | None = None,
                    occupancy: str = "serialized",
                    channel: ChannelModel | None = None,
                    channel_aware: bool = True,
                    channel_stagger: bool = False,
                    batch_window: float = 0.0,
                    batch_events: bool = False) -> OnlineResult:
    """One-shot simulation: submit a whole trace, run to completion.  A
    thin driver over :class:`OnlineScheduler`; under serialized occupancy
    (the default) with a static channel, bit-identical to
    :func:`simulate_online_reference` for every policy on traces with at
    most one arrival per user per flush.  (With duplicate users inside ONE
    flush the scheduler's accounting is the correct one — ``np.add.at``
    accumulates both requests' energies where the seed loop's fancy-index
    ``+=`` silently dropped duplicates.)"""
    sched = OnlineScheduler(profile, fleet, edge, policy=policy,
                            window=window, keep_frac=keep_frac, rho=rho,
                            inner=inner, service=service,
                            occupancy=occupancy, channel=channel,
                            channel_aware=channel_aware,
                            channel_stagger=channel_stagger,
                            batch_window=batch_window)
    sched.submit_many(sorted(arrivals, key=lambda a: a.arrival))
    return sched.run_batched() if batch_events else sched.run()


def simulate_online_reference(arrivals: list[OnlineArrival],
                              profile: TaskProfile, fleet: DeviceFleet,
                              edge: EdgeProfile, *, policy: str = "slack",
                              window: float = 0.0, keep_frac: float = 0.7,
                              rho: float = 0.03e9,
                              inner: Callable = jdob_plus) -> OnlineResult:
    """The seed's flush-loop simulator, kept verbatim as the oracle the
    event-driven scheduler must reproduce bit for bit."""
    arrivals = sorted(arrivals, key=lambda a: a.arrival)
    M = fleet.M
    l_min = fleet.zeta * profile.v()[-1] / fleet.f_max     # (M,)
    per_user = np.zeros(M)
    gpu_free = 0.0
    queue: list[OnlineArrival] = []
    batches: list[int] = []
    flush_times: list[float] = []
    f_edges: list = []
    violations = 0
    i = 0

    spec = planner_spec(inner, profile)
    planner = (BatchedPlanner(profile, edge, rho=rho, **spec)
               if spec is not None else None)

    def plan_flush(sub: DeviceFleet, t_free: float) -> Schedule:
        if planner is not None:
            return planner.plan([sub], [t_free])[0]
        return inner(profile, sub, edge, t_free=t_free, rho=rho)

    def flush(now: float):
        nonlocal gpu_free, violations
        idx = np.array([a.user for a in queue])
        rel = np.array([a.abs_deadline - now for a in queue])
        violations += int(np.sum(rel < l_min[idx] - 1e-12))
        sub = dataclasses.replace(fleet.subset(idx), deadline=rel)
        s: Schedule = plan_flush(sub, max(gpu_free - now, 0.0))
        per_user[idx] += s.per_user_energy
        if s.offload.any():
            per_user[idx[s.offload]] += s.edge_share
            gpu_free = now + s.t_free_end
        batches.append(int(s.offload.sum()))
        flush_times.append(now)
        f_edges.append(float(s.f_edge) if s.offload.any() else None)
        queue.clear()

    while i < len(arrivals) or queue:
        if not queue:
            queue.append(arrivals[i])
            i += 1
            continue
        next_arrival = arrivals[i].arrival if i < len(arrivals) else np.inf
        if policy == "immediate":
            t_flush = queue[-1].arrival
        elif policy == "window":
            t_flush = queue[0].arrival + window
        elif policy == "slack":                 # keep ≥ keep_frac budget
            t_flush = min(a.arrival + (1.0 - keep_frac) * a.rel_deadline
                          for a in queue)
        else:                                   # lastcall (point of no return)
            t_flush = min(a.abs_deadline - float(l_min[a.user])
                          for a in queue) - 1e-6
        if next_arrival <= t_flush:
            queue.append(arrivals[i])
            i += 1
        else:
            flush(max(t_flush, queue[-1].arrival))

    return OnlineResult(float(per_user.sum()), len(batches), batches,
                        violations, per_user, flush_times, f_edges)


def _present_fleet(arrivals: list[OnlineArrival], fleet: DeviceFleet
                   ) -> DeviceFleet:
    """The sub-fleet of users actually present in ``arrivals``, with each
    user's deadline replaced by their arrival's relative deadline.  The
    seed silently assumed exactly one arrival per user indexed 0..M-1;
    partial traces mis-paired deadlines with users."""
    by_user = sorted(arrivals, key=lambda a: a.user)
    users = np.array([a.user for a in by_user], dtype=int)
    assert len(np.unique(users)) == len(users), \
        "duplicate arrivals for a user — offline bounds need one request " \
        "per user (aggregate repeat traffic before calling)"
    rel = np.array([a.rel_deadline for a in by_user])
    return dataclasses.replace(fleet.subset(users), deadline=rel)


def oracle_bound(arrivals: list[OnlineArrival], profile: TaskProfile,
                 fleet: DeviceFleet, edge: EdgeProfile,
                 rho: float = 0.03e9,
                 service: PlannerService | None = None) -> float:
    """Clairvoyant lower bound: OG + J-DOB over the relative deadlines of
    the users actually present, arrival times ignored."""
    sub = _present_fleet(arrivals, fleet)
    return optimal_grouping(profile, sub, edge, rho=rho,
                            service=service).energy


def all_local_energy(arrivals, profile, fleet, edge) -> float:
    sub = _present_fleet(arrivals, fleet)
    return local_computing(profile, sub, edge).energy


def poisson_arrivals(M: int, rate_hz: float, fleet: DeviceFleet,
                     seed: int = 0) -> list[OnlineArrival]:
    rng = np.random.default_rng(seed)
    times = np.cumsum(rng.exponential(1.0 / rate_hz, size=M))
    return [OnlineArrival(m, float(times[m]), float(fleet.deadline[m]))
            for m in range(M)]

"""Co-inference serving: J-DOB-scheduled multi-user batched execution.

``CoInferenceServer`` is the system the paper describes, end to end:

  1. ``M`` device requests arrive (tokens + per-user deadline β).
  2. The outer OG module groups users by deadline; per group the J-DOB
     inner module picks (ñ, M'_o, f_e, {f_m}).
  3. Devices compute blocks 1..ñ on their inputs (executed here on the
     same weights), "upload" the boundary activation, and the edge runs
     blocks ñ+1..N as ONE batch (greedy batching) on the batched engine.
  4. Local users run the whole model themselves.

Outputs are bit-exact with the monolithic forward (tests assert this), and
the returned report carries the cost-model energy/latency bookkeeping so
examples can print the paper's tables from a live run.

Two entry points share one :class:`~repro.core.PlannerService` (planners,
shape buckets and compiled XLA programs are reused across them):

* :meth:`CoInferenceServer.serve` — one-shot: a full wave of requests,
  grouped by the OG outer module, planned and executed batch by batch.
* :meth:`CoInferenceServer.serve_online` — event-driven: requests arrive
  over time (``Request.arrival``); the :class:`~repro.core.OnlineScheduler`
  batches them under a flush policy and each flush executes on the model
  the moment it is booked, with GPU occupancy threaded between flushes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import (ChannelModel, DeviceFleet, EdgeProfile, FlushEvent,
                        MultiTenantResult, MultiTenantScheduler,
                        OnlineArrival, OnlineResult, OnlineScheduler,
                        PlannerService, Schedule, TaskProfile, Telemetry,
                        Tenant, jdob_plus, jdob_schedule)
from repro.core.telemetry import span
from .engine import BlockwiseExecutor


@dataclasses.dataclass
class Request:
    user: int
    tokens: np.ndarray              # (S,) int32
    deadline: float                 # seconds (relative to arrival)
    vision: np.ndarray | None = None
    arrival: float = 0.0            # seconds (online serving)


@dataclasses.dataclass
class ServeReport:
    logits: np.ndarray              # (M, S, V) — last block's output
    schedules: list[Schedule]
    groups: list[np.ndarray]
    energy: float
    per_user_energy: np.ndarray
    batch_sizes: list[int]
    partitions: list[int]
    t_free_end: float


@dataclasses.dataclass
class OnlineServeReport:
    """Event-driven serving outcome: one logits row per request (request
    order), plus the scheduler's flush timeline and energy bookkeeping."""

    logits: np.ndarray              # (n_requests, S, V)
    result: OnlineResult
    flushes: list[FlushEvent]
    energy: float
    violations: int
    gpu_busy_until: float           # absolute time the GPU frees (Eq. 22)
    #: per-flush edge frequency actually dispatched (Hz; None for
    #: all-local flushes) — under ``occupancy="interleaved"`` this is the
    #: slack-rescaled f_e, not necessarily the planner grid's choice
    f_edges: list = dataclasses.field(default_factory=list)
    occupancy: str = "serialized"
    gap_fills: int = 0
    dvfs_rescales: int = 0
    dvfs_energy_saved: float = 0.0
    #: channel observability (zero on the default static uplink):
    #: Σ|realized − planned| upload completion (s), bounded actualization
    #: re-plans, realized deadline slips, pruned gap probes
    channel: str = "static"
    upload_error: float = 0.0
    channel_replans: int = 0
    realized_late: int = 0
    stagger_replans: int = 0
    pruned_probes: int = 0


def run_partitioned(executor: BlockwiseExecutor, vocab_size: int,
                    requests: list[Request], sched: Schedule) -> np.ndarray:
    """Execute one planned batch on a real model: local users run the whole
    network, offloaded users run blocks 1..ñ "on device", upload the
    boundary activation, and the edge batches the suffix.  Block index
    mapping: J-DOB block n ∈ {1..N} is transformer layer n (embedding
    folded into block 1, LM head into block N — matching
    ``core.task_model.profile_from_arch``).

    The result lives in a buffer of ``executor.outputs``, handed out again
    once nothing refers to this result or a view of it.  It is not zeroed:
    the local and the offloaded rows together write every row."""
    ex = executor
    off = sched.offload
    loc = ~off
    if off.shape != (len(requests),):
        raise ValueError(f"offload mask of shape {off.shape} for "
                         f"{len(requests)} requests")
    with span("repro.exec.prepare") as sp:
        tokens = jnp.asarray(np.stack([r.tokens for r in requests]))
        vision = None
        if requests[0].vision is not None:
            vision = jnp.asarray(np.stack([r.vision for r in requests]))
        h = ex.embed(tokens)
        out, reused = ex.outputs.take(
            (len(requests),) + h.shape[1:-1] + (vocab_size,), np.float32)
        if sp is not None:                  # the no-op span enters as None
            sp.set_metadata(reused=int(reused))
    n_layers = len(ex.layers)
    nt = sched.partition
    if loc.any():
        with span("repro.exec.split"):
            hl = h[loc]
            vl = None if vision is None else vision[loc]
        with span("repro.exec.blocks", lo=0, hi=n_layers):
            hl = ex.run_blocks(hl, 0, n_layers, vision=vl)
        _head_to(out, loc, ex, hl)
    if off.any():
        with span("repro.exec.split"):
            ho = h[off]
            vo = None if vision is None else vision[off]
        # device side: blocks 1..nt  (nt layers of the transformer, capped
        # at n_layers — block N is the head, edge-only here)
        dev_hi = min(nt, n_layers)
        with span("repro.exec.blocks", lo=0, hi=dev_hi):
            ho = ex.run_blocks(ho, 0, dev_hi, vision=vo)
        # "upload" boundary activation; edge batches the suffix
        with span("repro.exec.blocks", lo=dev_hi, hi=n_layers):
            ho = ex.run_blocks(ho, dev_hi, n_layers, vision=vo)
        _head_to(out, off, ex, ho)
    return out


def _head_to(out: np.ndarray, rows: np.ndarray, ex: BlockwiseExecutor,
             h) -> None:
    """The head on ``h``, its logits written into ``out[rows]``: device
    wait, host copy and scatter each a span of their own."""
    with span("repro.exec.head"):
        logits = ex.head(h)
    with span("repro.exec.wait"):
        jax.block_until_ready(logits)
    with span("repro.exec.to_host", bytes=logits.nbytes):
        host = np.asarray(logits)
    with span("repro.exec.scatter"):
        out[np.where(rows)[0]] = host


class CoInferenceServer:
    def __init__(self, cfg: ArchConfig, params, profile: TaskProfile,
                 fleet: DeviceFleet, edge: EdgeProfile,
                 inner: Callable = jdob_schedule, rho: float = 0.03e9,
                 service: PlannerService | None = None):
        self.cfg = cfg
        self.executor = BlockwiseExecutor(cfg, params)
        self.profile = profile
        self.fleet = fleet
        self.edge = edge
        self.inner = inner
        self.rho = rho
        # one planner service per server: OG's segment solves, every
        # subsequent serve() and the online scheduler share its planners
        # and compiled shapes (J-DOB inner family only; arbitrary inner
        # callables plan sequentially)
        self.service = (service if service is not None
                        else PlannerService(profile, edge, rho=rho))
        self.planner = self.service.planner_for(inner)
        n_layers = len(self.executor.layers)
        assert profile.N == n_layers, \
            f"profile N={profile.N} vs layers={n_layers}"

    def _run_schedule(self, requests: list[Request], sched: Schedule):
        return run_partitioned(self.executor, self.cfg.vocab_size,
                               requests, sched)

    def serve(self, requests: list[Request], t_free: float = 0.0, *,
              cohort_size: int | None = None, merge_window: int = 4,
              planner: str | None = None,
              beam_width: int | str | None = None,
              dp_backend: str | None = None,
              telemetry: Telemetry | None = None) -> ServeReport:
        """One-shot wave: OG-group, plan and execute every request.

        ``cohort_size`` bounds the exact OG problem size: fleets larger
        than it are planned hierarchically (deadline-sorted cohorts +
        boundary-merge DP — :func:`~repro.core.cohort.cohort_grouping`);
        fleets that fit stay on the exact path, bit-identical to the
        previous releases.  ``None`` defers to the planner service's
        ``default_cohort_size``.  ``planner`` picks the grouping DP —
        ``"prefix"`` or ``"pareto"`` (occupancy-coupling-sound frontier
        DP) — defaulting to the service's ``default_planner``;
        ``beam_width`` bounds the pareto frontier (``"auto"`` self-sizes
        it, never above the prefix DP's energy).  ``dp_backend`` picks the
        grouping-DP fold — ``"dispatch"`` or ``"fused"`` (one device scan
        per fold, bit-identical plans) — defaulting to the service's
        ``default_dp_backend``."""
        fleet = dataclasses.replace(
            self.fleet,
            deadline=np.asarray([r.deadline for r in requests]))
        grouped = self.service.plan_fleet(
            fleet, self.inner, t_free=t_free, cohort_size=cohort_size,
            merge_window=merge_window, planner=planner,
            beam_width=beam_width, dp_backend=dp_backend,
            tracer=None if telemetry is None else telemetry.tracer)
        S = len(requests[0].tokens)
        logits = np.zeros((len(requests), S, self.cfg.vocab_size),
                          np.float32)
        for g, sched in zip(grouped.groups, grouped.schedules):
            sub = [requests[i] for i in g]
            logits[g] = self._run_schedule(sub, sched)
        return ServeReport(
            logits=logits, schedules=grouped.schedules,
            groups=grouped.groups, energy=grouped.energy,
            per_user_energy=grouped.per_user_energy,
            batch_sizes=[s.batch_size for s in grouped.schedules],
            partitions=[s.partition for s in grouped.schedules],
            t_free_end=grouped.t_free_end)

    def scheduler(self, *, policy: str = "slack", window: float = 0.0,
                  keep_frac: float = 0.7, occupancy: str = "serialized",
                  channel: ChannelModel | None = None,
                  channel_aware: bool = True,
                  channel_stagger: bool = False,
                  batch_window: float = 0.0, plan_workers: int = 0,
                  plan_depth: int = 1,
                  on_flush=None, on_gpu_free=None,
                  telemetry: Telemetry | None = None) -> OnlineScheduler:
        """An event-driven scheduler wired to this server's fleet and
        planner service (compiled shapes shared with ``serve``).
        ``occupancy`` picks the GPU timeline mode: ``"serialized"`` is the
        paper's scalar Eq. 22 horizon; ``"interleaved"`` gap-fills small
        batches into idle windows and re-selects f_e per flush.
        ``channel`` attaches an uplink model (shared-medium contention /
        fading traces — :mod:`repro.core.channel`); flush plans then price
        the contended-rate snapshot (``channel_aware=False`` keeps the
        nominal solo rates) and realized uploads drive the actual GPU
        start."""
        return OnlineScheduler(self.profile, self.fleet, self.edge,
                               policy=policy, window=window,
                               keep_frac=keep_frac, rho=self.rho,
                               inner=self.inner, service=self.service,
                               occupancy=occupancy, channel=channel,
                               channel_aware=channel_aware,
                               channel_stagger=channel_stagger,
                               batch_window=batch_window,
                               plan_workers=plan_workers,
                               plan_depth=plan_depth,
                               on_flush=on_flush, on_gpu_free=on_gpu_free,
                               telemetry=telemetry)

    def serve_online(self, requests: list[Request], *,
                     policy: str = "slack", window: float = 0.0,
                     keep_frac: float = 0.7,
                     occupancy: str = "serialized",
                     channel: ChannelModel | None = None,
                     channel_aware: bool = True,
                     channel_stagger: bool = False,
                     batch_window: float = 0.0,
                     batch_events: bool = False,
                     plan_workers: int = 0, plan_depth: int = 1,
                     telemetry: Telemetry | None = None) -> OnlineServeReport:
        """Serve requests arriving over time (``Request.arrival``).

        Each policy flush executes its planned batch on the model the
        moment the scheduler books it — devices run blocks 1..ñ, the edge
        batches the suffix — with GPU occupancy threaded between flushes
        through the scheduler's :class:`~repro.core.GpuTimeline`.
        Unlike :meth:`serve`, a user may appear in several flushes (repeat
        traffic) and requests need not cover the fleet.
        ``batch_events`` drives the fleet-scale batched event loop
        (:meth:`~repro.core.OnlineScheduler.run_batched`): events sharing
        a timestamp — or falling inside ``batch_window`` seconds — drain
        in one pass; at ``batch_window=0`` the outcome is bit-identical to
        the event-at-a-time loop.  ``plan_workers > 0`` (batched loop
        only) pipelines each flush's solve against the previous flush's
        execution — results stay bit-identical at any worker count;
        ``plan_depth`` speculates that many flushes ahead by chaining the
        predicted occupancy cursor (still bit-identical — see
        :meth:`~repro.core.OnlineScheduler.run_batched`)."""
        S = len(requests[0].tokens)
        logits = np.zeros((len(requests), S, self.cfg.vocab_size),
                          np.float32)

        def execute(ev: FlushEvent) -> None:
            reqs = [a.payload for a in ev.arrivals]
            rows = [r for (r, _) in reqs]
            logits[rows] = self._run_schedule([r for (_, r) in reqs],
                                              ev.schedule)

        sched = self.scheduler(policy=policy, window=window,
                               keep_frac=keep_frac, occupancy=occupancy,
                               channel=channel, channel_aware=channel_aware,
                               channel_stagger=channel_stagger,
                               batch_window=batch_window,
                               plan_workers=plan_workers if batch_events
                               else 0, plan_depth=plan_depth,
                               on_flush=execute, telemetry=telemetry)
        for row, r in enumerate(requests):
            sched.submit(OnlineArrival(r.user, r.arrival, r.deadline,
                                       payload=(row, r)))
        result = sched.run_batched() if batch_events else sched.run()
        return OnlineServeReport(logits=logits, result=result,
                                 flushes=sched.flushes, energy=result.energy,
                                 violations=result.violations,
                                 gpu_busy_until=sched.gpu_free,
                                 f_edges=result.f_edges,
                                 occupancy=occupancy,
                                 gap_fills=sched.timeline.gap_fills,
                                 dvfs_rescales=sched.timeline.dvfs_rescales,
                                 dvfs_energy_saved=(
                                     sched.timeline.dvfs_energy_saved),
                                 channel=(sched.channel.name
                                          if sched.channel is not None
                                          else "static"),
                                 upload_error=result.upload_error,
                                 channel_replans=result.channel_replans,
                                 realized_late=result.realized_late,
                                 stagger_replans=result.stagger_replans,
                                 pruned_probes=result.pruned_probes)


# ---------------------------------------------------------------------------
# multi-tenant serving: N models sharing one edge GPU
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TenantModel:
    """One tenant's model + scheduling bundle for
    :class:`MultiTenantServer`: its architecture/weights, its J-DOB task
    profile (one block per layer), its device fleet, its batch cost model
    on the shared accelerator, and its flush policy."""

    name: str
    cfg: ArchConfig
    params: Any
    profile: TaskProfile
    fleet: DeviceFleet
    edge: EdgeProfile
    policy: str = "slack"
    window: float = 0.0
    keep_frac: float = 0.7
    inner: Callable = jdob_plus

    def tenant(self) -> Tenant:
        return Tenant(self.profile, self.fleet, self.edge, name=self.name,
                      policy=self.policy, window=self.window,
                      keep_frac=self.keep_frac, inner=self.inner)


@dataclasses.dataclass
class MultiTenantServeReport:
    """Per-tenant logits (request order) + the arbiter's outcome.  A
    request row is guaranteed written iff ``served[tid][row]`` — rejected
    requests (admission control) keep their zero rows."""

    logits: list[np.ndarray]
    served: list[np.ndarray]        # (n_requests,) bool per tenant
    result: MultiTenantResult
    energy: float
    violations: int
    preemptions: int
    gpu_busy_until: float


class MultiTenantServer:
    """N co-resident models sharing one edge GPU through the tenancy
    subsystem (:mod:`repro.core.tenancy`).

    Each tenant's flushes execute on ITS model the moment the shared
    ledger books them; a preempted queued batch re-executes under its
    re-planned schedule (partitions may shift — logits are bit-equal
    either way, which the per-tenant monolithic-forward check pins);
    admission-degraded requests run monolithically "on device".  All
    tenants plan through one :class:`~repro.core.PlannerService` family,
    so compiled planner shapes amortize across models."""

    def __init__(self, models: Sequence[TenantModel], *,
                 rho: float = 0.03e9,
                 service: PlannerService | None = None,
                 preemption: bool = True, admission: str = "admit",
                 occupancy: str = "serialized",
                 channel: ChannelModel | None = None,
                 channel_aware: bool = True,
                 channel_stagger: bool = False,
                 batch_window: float = 0.0, plan_workers: int = 0,
                 plan_depth: int = 1,
                 telemetry: Telemetry | None = None):
        assert len(models) >= 1
        self.models = list(models)
        self.executors = [BlockwiseExecutor(m.cfg, m.params)
                          for m in self.models]
        for m, ex in zip(self.models, self.executors):
            assert m.profile.N == len(ex.layers), \
                f"{m.name}: profile N={m.profile.N} vs layers={len(ex.layers)}"
        self.rho = rho
        self.preemption = preemption
        self.admission = admission
        self.occupancy = occupancy
        #: ONE uplink every tenant's devices share (None = static scalars)
        self.channel = channel
        self.channel_aware = channel_aware
        self.channel_stagger = channel_stagger
        self.batch_window = batch_window
        self.plan_workers = plan_workers
        self.plan_depth = plan_depth
        self.telemetry = telemetry
        self.service = (service if service is not None
                        else PlannerService(self.models[0].profile,
                                            self.models[0].edge, rho=rho))

    def serve_online(self, requests: Sequence[Sequence[Request]], *,
                     batch_events: bool = False) -> MultiTenantServeReport:
        """Serve one request stream per tenant (``Request.arrival`` times
        interleave freely across tenants).  ``batch_events`` drives the
        arbitrated batched event loop
        (:meth:`~repro.core.MultiTenantScheduler.run_batched`) —
        bit-identical to event-at-a-time at ``batch_window=0``."""
        assert len(requests) == len(self.models)
        # a tenant may have no traffic in the window: zero flushes, an
        # empty logits block
        logits = [np.zeros((len(reqs),
                            len(reqs[0].tokens) if reqs else 0,
                            m.cfg.vocab_size), np.float32)
                  for m, reqs in zip(self.models, requests)]
        served = [np.zeros(len(reqs), bool) for reqs in requests]

        def execute(tid: int, ev: FlushEvent) -> None:
            pairs = [a.payload for a in ev.arrivals]
            rows = [row for (row, _) in pairs]
            logits[tid][rows] = run_partitioned(
                self.executors[tid], self.models[tid].cfg.vocab_size,
                [r for (_, r) in pairs], ev.schedule)
            served[tid][rows] = True

        def degrade(tid: int, arrival: OnlineArrival, energy: float) -> None:
            row, r = arrival.payload
            out = run_partitioned(
                self.executors[tid], self.models[tid].cfg.vocab_size, [r],
                dataclasses.replace(_ALL_LOCAL, offload=np.zeros(1, bool)))
            logits[tid][row] = out[0]
            served[tid][row] = True

        mts = MultiTenantScheduler(
            [m.tenant() for m in self.models], rho=self.rho,
            service=self.service, preemption=self.preemption,
            admission=self.admission, occupancy=self.occupancy,
            channel=self.channel, channel_aware=self.channel_aware,
            channel_stagger=self.channel_stagger,
            batch_window=self.batch_window,
            plan_workers=self.plan_workers if batch_events else 0,
            plan_depth=self.plan_depth,
            on_flush=execute, on_replan=execute, on_degrade=degrade,
            telemetry=self.telemetry)
        for tid, reqs in enumerate(requests):
            order = sorted(range(len(reqs)), key=lambda i: reqs[i].arrival)
            for row in order:
                r = reqs[row]
                mts.submit(tid, OnlineArrival(r.user, r.arrival, r.deadline,
                                              payload=(row, r)))
        result = mts.run_batched() if batch_events else mts.run()
        return MultiTenantServeReport(
            logits=logits, served=served, result=result,
            energy=result.energy, violations=result.violations,
            preemptions=result.preemptions,
            gpu_busy_until=result.gpu_busy_until)


#: placeholder schedule for degraded (all-local) single-request execution —
#: only ``offload``/``partition`` matter to :func:`run_partitioned`
_ALL_LOCAL = Schedule(True, 0.0, 0, 0.0, np.zeros(1, bool),
                      np.zeros(1), 0.0, {}, np.zeros(1))

"""J-DOB: Joint DVFS, Offloading and Batching (paper Alg. 1 + Alg. 2).

Three layers:

* :func:`jdob_plan_batched` — the production core: a pure-JAX, fully jitted
  solver for **G padded groups at once**.  Each group is a user subset of a
  common width ``M_max`` with a boolean activity mask; masked users
  contribute exactly zero energy, sort behind every active user, and never
  enter the greedy batching set.  The paper's outer loop over partition
  points ñ (Alg. 1 line 3) is a ``vmap``; the edge-frequency sweep
  (Alg. 2 lines 6-24) is a dense (ñ × k × M) tensor evaluation; the whole
  thing is ``vmap``-ped once more over groups.  The paper's monotone-pointer
  update of the greedy batching set (Alg. 2 lines 7-12) becomes a
  ``searchsorted``-style first-true-index over the non-increasing threshold
  sequence — same semantics, O(1) depth.  The argmin over the (ñ, f_e) grid
  and the winning strategy's reconstruction also happen on device, so one
  dispatch plans an arbitrary number of groups.
* :func:`jdob_schedule` — the historical single-group API, now a thin
  wrapper that plans a batch of one.  Results are unchanged.
* :class:`BatchedPlanner` — a reusable handle that caches the task/edge
  constants and the frequency sweep, pads group widths to power-of-two
  buckets and chunks large batches, so repeated planning (the OG outer
  module, online flushes, the serving path) hits a handful of compiled
  shapes instead of recompiling per group size.

:mod:`repro.core.reference` holds ``jdob_reference`` — a line-by-line loop
transcription of the pseudocode used as the test oracle.

Internally everything is scaled to (GHz, seconds, J) so the math is well
conditioned in float32; public inputs/outputs stay SI (Hz).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .cost_models import DeviceFleet, EdgeProfile
from .task_model import TaskProfile
from .telemetry import span

_GHZ = 1e9
_INF = jnp.inf

#: per-user entries of the planner's constant dict (batched to (G, M_max))
_USER_KEYS = ("zeta", "ku", "fm_min", "fm_max", "rate", "p_up", "T")
#: neutral padding so masked users never produce inf/nan intermediates
_PAD_VALUES = dict(zeta=0.0, ku=0.0, fm_min=1.0, fm_max=1.0,
                   rate=1.0, p_up=0.0, T=1.0)


@dataclasses.dataclass
class Schedule:
    """One group's co-inference strategy 𝒳 = (M'_o, ñ, {f_m}, f_e)."""

    feasible: bool
    energy: float                 # total J (device + uplink + edge)
    partition: int                # ñ: offload after block ñ (ñ=N ⇒ all local)
    f_edge: float                 # Hz
    offload: np.ndarray           # (M,) bool
    f_device: np.ndarray          # (M,) Hz
    t_free_end: float             # Eq. 22: when the GPU frees up
    terms: dict                   # energy breakdown
    per_user_energy: np.ndarray   # (M,)
    # reservation geometry (consumed by core.timeline): the edge run is
    # gpu_busy = φ_ñ(B)/f_e seconds ending at t_free_end, and its energy
    # is edge_psi·f_e² — all zero for an all-local plan
    gpu_busy: float = 0.0         # s the GPU is genuinely occupied
    edge_phi: float = 0.0         # φ_ñ(B): suffix GPU cycles (Hz·s)
    edge_psi: float = 0.0         # ψ_ñ(B): edge energy / f_e² (J/Hz²)

    @property
    def batch_size(self) -> int:
        return int(self.offload.sum())

    @property
    def gpu_start(self) -> float:
        """When the GPU genuinely begins this batch (relative, like
        ``t_free_end``): uploads may delay it past the residual occupancy
        the plan was given."""
        return self.t_free_end - self.gpu_busy

    @property
    def edge_share(self) -> float:
        """Each offloader's share of what ``energy`` charges beyond
        ``per_user_energy``: the edge's ψ·f_e² plus the float32 grid's
        rounding against the float64 per-user sum, so that per-user
        accounting adds up to ``energy``."""
        n = self.batch_size
        spent = float(np.sum(self.per_user_energy, dtype=np.float64))
        return (self.energy - spent) / n if n else 0.0


def _prep_blocks(profile: TaskProfile, edge: EdgeProfile) -> dict:
    """Per-block constants shared by every group (scaled to GHz/s/J)."""
    phi_b, phi_s = edge.phi_coeffs(profile)
    psi_b, psi_s = edge.psi_coeffs(profile)
    return dict(
        v=jnp.asarray(profile.v() / _GHZ),               # Gcycles/ζ
        u=jnp.asarray(profile.u()),
        o_up=jnp.asarray(profile.O),                     # bytes
        phi_b=jnp.asarray(phi_b / _GHZ), phi_s=jnp.asarray(phi_s / _GHZ),
        psi_b=jnp.asarray(psi_b * _GHZ ** 2),
        psi_s=jnp.asarray(psi_s * _GHZ ** 2),
    )


def _pad_fleets(fleets: Sequence[DeviceFleet], m_pad: int):
    """Stack per-user constants of G fleets into (G, m_pad) arrays + mask."""
    G = len(fleets)
    out = {k: np.full((G, m_pad), _PAD_VALUES[k], np.float64)
           for k in _USER_KEYS}
    mask = np.zeros((G, m_pad), bool)
    for g, fl in enumerate(fleets):
        m = fl.M
        out["zeta"][g, :m] = fl.zeta
        out["ku"][g, :m] = fl.kappa * _GHZ ** 2
        out["fm_min"][g, :m] = fl.f_min / _GHZ
        out["fm_max"][g, :m] = fl.f_max / _GHZ
        out["rate"][g, :m] = fl.rate
        out["p_up"][g, :m] = fl.p_up
        out["T"][g, :m] = fl.deadline
        mask[g, :m] = True
    return {k: jnp.asarray(v) for k, v in out.items()}, jnp.asarray(mask)


def _pow2_sum(x):
    """Padding-invariant float sum: zero-pad to a power of two, then fold
    halves.  All-zero halves collapse exactly (x + 0.0 == x bitwise), so a
    group solved at any padded width M_pad ≥ M produces bit-identical sums
    to the unpadded solve — the property the batched-vs-solo equivalence
    tests assert.  (``jnp.sum`` picks a length-dependent reduction tree,
    which perturbs the last ulp across pad widths.)"""
    n = x.shape[0]
    p = 1
    while p < n:
        p *= 2
    if p != n:
        x = jnp.concatenate([x, jnp.zeros(p - n, x.dtype)])
    while p > 1:
        p //= 2
        x = x[:p] + x[p:]
    return x[0]


def _local_opt(c, act):
    """Per-user optimal all-local DVFS (Eq. 20 local branch): f, energy.
    Masked users get exactly zero energy (ku is padded to 0 as well)."""
    gamma_loc = c["zeta"] * c["v"][-1] / c["T"]
    f_loc = jnp.clip(gamma_loc, c["fm_min"], c["fm_max"])
    e_loc = jnp.where(act, c["ku"] * c["u"][-1] * f_loc ** 2, 0.0)
    return f_loc, e_loc


def _sorted_ctx(c, act, f_loc, nt, sort_key: str):
    """Alg. 1 lines 4-6 for partition ñ = nt: user ordering, suffix
    deadlines, batching thresholds.  Masked users sort last, have +inf
    thresholds (never join the batch), and +inf deadlines (never bind)."""
    M = c["T"].shape[0]
    # Alg.1 line 4: minimum latency cost γ_m^(ñ)  (Eq. 17)
    gamma = c["o_up"][nt] / c["rate"] + c["zeta"] * c["v"][nt] / c["fm_max"]
    # Alg.1 line 5: sort descending by γ (paper), or one of the
    # beyond-paper orderings (see EXPERIMENTS.md §Beyond-paper):
    #   budget — ascending T_m − γ_m: exact when deadlines differ
    #   energy — ascending local-opt energy: keeps the *costliest*
    #            (most offload-worthy) users in the greedy set longest
    if sort_key == "gamma":
        key = -gamma
    elif sort_key == "budget":
        key = c["T"] - gamma
    else:                                   # "energy"
        key = c["ku"] * c["u"][-1] * f_loc ** 2
    order = jnp.argsort(jnp.where(act, key, _INF))
    g_s = gamma[order]
    T_s = jnp.where(act, c["T"], _INF)[order]
    act_s = act[order]
    # suffix-min of deadlines: l_o for the set list[i:]
    suffT = jax.lax.associative_scan(jnp.minimum, T_s, reverse=True)
    # batch size if list[i:] offload = number of ACTIVE users in the suffix
    b_if_in = jax.lax.associative_scan(
        jnp.add, act_s.astype(jnp.float32), reverse=True)
    # Alg.1 line 6 / Eq. 18: thresholds (non-increasing over the active
    # prefix; +inf where the user cannot make its deadline at any f_e)
    phi_i = c["phi_b"][nt] + c["phi_s"][nt] * b_if_in
    denom = suffT - g_s
    th = jnp.where(act_s & (denom > 0),
                   phi_i / jnp.maximum(denom, 1e-30), _INF)
    # NOTE: membership under non-γ orderings is re-validated per candidate
    # (dev_ok / gpu_ok in _cell), so non-monotone threshold sequences remain
    # safe — infeasible (ñ, f_e) cells are masked to +inf, never selected.
    return dict(nt=nt, order=order, suffT=suffT, b_if_in=b_if_in, th=th)


def _cell(c, act, f_loc, e_loc, t_free, ctx, f_e):
    """Alg. 2's inner evaluation at one (ñ, f_e) grid cell."""
    M = c["T"].shape[0]
    nt = ctx["nt"]
    # greedy batching set under f_e: first index with th[i] <= f_e
    ok = ctx["th"] <= f_e
    j = jnp.where(jnp.any(ok), jnp.argmax(ok), M)
    jc = jnp.minimum(j, M - 1)
    B_o = jnp.where(j < M, ctx["b_if_in"][jc], 0.0)
    has = B_o > 0
    l_o = ctx["suffT"][jc]                              # Eq. 10
    phi = c["phi_b"][nt] + c["phi_s"][nt] * B_o
    psi = c["psi_b"][nt] + c["psi_s"][nt] * B_o
    # Eq. 6 / Alg.2 line 13: GPU availability
    gpu_ok = f_e * (l_o - t_free) >= phi
    # membership of each (unsorted) user
    rank = jnp.empty(M, jnp.int32).at[ctx["order"]].set(
        jnp.arange(M, dtype=jnp.int32))
    off = (rank >= j) & act
    # Eq. 19/20: optimal device DVFS
    slack = l_o - c["o_up"][nt] / c["rate"] - phi / f_e
    gamma_off = c["zeta"] * c["v"][nt] / jnp.maximum(slack, 1e-30)
    gamma_off = jnp.where(slack > 0, gamma_off, _INF)
    f_dev = jnp.where(off,
                      jnp.clip(gamma_off, c["fm_min"], c["fm_max"]),
                      f_loc)
    dev_ok = jnp.where(off, gamma_off <= c["fm_max"] * (1 + 1e-9), True)
    # Eq. 21: total energy
    e_up = c["o_up"][nt] / c["rate"] * c["p_up"]
    e_user = jnp.where(off, c["ku"] * c["u"][nt] * f_dev ** 2 + e_up,
                       e_loc)
    energy = _pow2_sum(e_user) + jnp.where(has, psi * f_e ** 2, 0.0)
    feas = has & gpu_ok & jnp.all(dev_ok)
    # Eq. 22: end of GPU occupation
    t_up = jnp.where(off, c["zeta"] * c["v"][nt] / f_dev
                     + c["o_up"][nt] / c["rate"], -_INF)
    t_end = jnp.maximum(t_free, jnp.max(t_up)) + phi / f_e
    return jnp.where(feas, energy, _INF), off, f_dev, t_end, e_user


def _solve_group(c, f_sweep, t_free, act, part_mask, n_partitions: int,
                 sort_key: str):
    """Dense Alg. 1+2 evaluation + argmin + winner reconstruction for ONE
    (masked) group.  ñ = n_partitions-1 (== N) rows are masked: that is the
    all-local strategy, handled in closed form by the host wrapper."""
    K = f_sweep.shape[0]
    f_loc, e_loc = _local_opt(c, act)

    def energies(nt):
        ctx = _sorted_ctx(c, act, f_loc, nt, sort_key)
        return jax.vmap(
            lambda f: _cell(c, act, f_loc, e_loc, t_free, ctx, f)[0]
        )(f_sweep)

    E = jax.vmap(energies)(jnp.arange(n_partitions))
    # mask ñ = N: "offloading after the last block" is local computing
    E = E.at[n_partitions - 1].set(_INF)
    if part_mask is not None:
        E = jnp.where(part_mask[:, None], E, _INF)
    flat = jnp.argmin(E.reshape(-1))
    nt_b = flat // K
    fi_b = flat % K
    # re-evaluate the winning cell (identical ops => identical bits)
    ctx_b = _sorted_ctx(c, act, f_loc, nt_b, sort_key)
    e_b, off, f_dev, t_end, e_user = _cell(c, act, f_loc, e_loc, t_free,
                                           ctx_b, f_sweep[fi_b])
    return dict(E=E, nt=nt_b, fi=fi_b, energy=E.reshape(-1)[flat],
                off=off, f_dev=f_dev, t_end=t_end, e_user=e_user)


@functools.partial(jax.jit, static_argnames=("n_partitions", "sort_key"))
def jdob_plan_batched(c_batch, f_sweep, t_free_batch, mask, part_mask=None,
                      *, n_partitions: int, sort_key: str = "gamma"):
    """Solve G padded groups in one jitted vmap.

    ``c_batch``: dict with per-block constants shaped (N+1,) (shared across
    groups) and per-user constants shaped (G, M_max) (see ``_USER_KEYS``);
    ``f_sweep``: (K,) shared GHz sweep; ``t_free_batch``: (G,) GPU release
    times; ``mask``: (G, M_max) bool — True for real users; ``part_mask``:
    optional (N+1,) bool restricting candidate partitions (the J-DOB-binary
    baseline).  Returns a dict of stacked grids/winners: ``E`` (G, N+1, K),
    ``nt``/``fi``/``energy``/``t_end`` (G,), ``off``/``f_dev``/``e_user``
    (G, M_max).  Masked users contribute exactly zero energy and never
    enter the greedy batching set.
    """
    axes = ({k: (0 if k in _USER_KEYS else None) for k in c_batch},
            None, 0, 0, None)
    return jax.vmap(
        lambda c, f, tf, act, pm: _solve_group(
            c, f, tf, act, pm, n_partitions, sort_key),
        in_axes=axes)(c_batch, f_sweep, t_free_batch, mask, part_mask)


def make_f_sweep(edge: EdgeProfile, rho: float = 0.03e9) -> np.ndarray:
    """Alg. 2's frequency sweep grid (descending, includes f_max & f_min)."""
    k = int(np.floor((edge.f_max - edge.f_min) / rho + 1e-9)) + 1
    f = edge.f_max - rho * np.arange(k)
    # Append f_min only when the grid genuinely stops short of it; when the
    # last grid point lands on f_min (up to rounding), snap instead of
    # appending — an absolute 1e-6 Hz test duplicated f_min whenever
    # floating error at GHz scale exceeded it.
    if f[-1] - edge.f_min > 1e-9 * rho:
        f = np.concatenate([f, [edge.f_min]])
    else:
        f[-1] = edge.f_min
    return f


def _bucket(n: int, minimum: int = 4) -> int:
    """Next power of two ≥ n (≥ minimum) — the shape-bucketing unit."""
    b = minimum
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class PlannerStats:
    """Per-planner compile/shape-cache counters + plan-latency histogram.

    ``hits``/``misses``/``evictions`` count this planner's lookups against
    its :class:`ExecutableCache` (misses trigger an XLA compile; evictions
    are entries this planner's compiles pushed out).  ``dispatches`` counts
    device launches, ``groups_planned`` real (unpadded) groups solved.

    ``plan_calls`` counts :meth:`BatchedPlanner.plan` invocations (one per
    online flush / OG level dispatch) and ``plan_ns`` holds their wall-time
    samples (ns, dispatch through host materialization — the latency a
    serving loop actually experiences), so planner cost is observable
    without an external profiler.  Samples whose dispatch triggered an XLA
    compile land in the separate ``compile_ns`` bucket
    (``compile_calls``/``compile_ns_max``) instead: a cold compile is
    3-5 orders of magnitude above a steady-state solve, so one warm-up
    sample would otherwise own ``max_ms`` and poison ``p99_ms`` for the
    whole run.  ``plan_ns`` percentiles are therefore STEADY-STATE
    latencies; the compile bucket is reported alongside them by
    :meth:`plan_latency`.  Both sample lists are deterministically
    decimated (every other sample dropped) past ``LATENCY_CAP`` entries —
    percentile estimates stay representative while a 100k-flush run stays
    bounded; ``plan_calls`` and min/max remain exact.

    ``frontier_states``/``frontier_max``/``dominance_pruned`` instrument the
    Pareto grouping DP (total surviving states across levels, largest single
    frontier, candidates discarded by the dominance sweep); all zero under
    the prefix DP.  ``frontier_levels`` samples the per-level survivor
    count (the frontier-size histogram exported through telemetry) and
    ``beam_widenings`` counts levels where an adaptive beam actually
    widened.  ``plan_ahead_hits``/``plan_ahead_misses`` count how
    often a pipelined event loop consumed a speculative plan vs fell back
    to a synchronous solve.

    :meth:`merge` and :meth:`as_dict` derive from ``dataclasses.fields``
    — a new counter is summed across planners and exported by default
    (override with ``metadata={"merge": "max"|"min_counted"}`` or
    ``metadata={"export": False}``), so it can never be silently dropped
    from aggregated summaries or bench JSON
    (tests/core/test_telemetry.py round-trips every field)."""

    hits: int = 0
    misses: int = 0
    evictions: int = 0
    dispatches: int = 0
    groups_planned: int = 0
    plan_calls: int = 0
    plan_ns_min: int = dataclasses.field(
        default=0, metadata={"merge": "min_counted"})
    plan_ns_max: int = dataclasses.field(default=0, metadata={"merge": "max"})
    plan_ns: list = dataclasses.field(
        default_factory=list, metadata={"export": False})
    compile_calls: int = 0
    compile_ns_max: int = dataclasses.field(default=0,
                                            metadata={"merge": "max"})
    compile_ns: list = dataclasses.field(
        default_factory=list, metadata={"export": False})
    frontier_states: int = 0
    frontier_max: int = dataclasses.field(default=0, metadata={"merge": "max"})
    dominance_pruned: int = 0
    frontier_levels: list = dataclasses.field(
        default_factory=list, metadata={"export": False})
    beam_widenings: int = 0
    plan_ahead_hits: int = 0
    plan_ahead_misses: int = 0
    #: grouping-DP plan accounting: ``og_plans`` counts top-level OG plans
    #: (offline/incremental/cohort), ``og_dispatches`` the device launches
    #: issued inside them — their ratio (``dispatches_per_plan``) is THE
    #: observable for the dispatch-path O(M) vs fused-path O(1) claim
    og_plans: int = 0
    og_dispatches: int = 0
    #: fused-scan accounting: one ``fused_scans`` tick per device-resident
    #: DP scan executed (``og_plan_fused``), wall-clock samples in
    #: ``fused_scan_ns`` (dispatch through ys materialization); scans whose
    #: lookup compiled land in ``fused_compiles`` instead of the
    #: steady-state samples (same cold/warm split as ``record_latency``).
    #: ``fused_fallbacks`` counts plans that overflowed the device beam
    #: buffer and re-ran on the dispatch path; ``fused_routed`` counts
    #: plans the size crossover routed straight to the dispatch fold
    #: (``fused_scan_viable`` — a policy decision, not a failure)
    fused_scans: int = 0
    fused_compiles: int = 0
    fused_fallbacks: int = 0
    fused_routed: int = 0
    fused_scan_ns_max: int = dataclasses.field(default=0,
                                               metadata={"merge": "max"})
    fused_scan_ns: list = dataclasses.field(
        default_factory=list, metadata={"export": False})

    LATENCY_CAP = 8192

    @property
    def compiles(self) -> int:
        return self.misses

    @property
    def dispatches_per_plan(self) -> float:
        """Device launches per top-level grouping plan — ≈M for the
        dispatch DP backend, O(1) for the fused scan backend (one scan
        dispatch + the winning chain's materialization).  0.0 until a
        grouping plan has run."""
        if not self.og_plans:
            return 0.0
        return self.og_dispatches / self.og_plans

    def record_fused_scan(self, ns: int, compiled: bool = False) -> None:
        self.fused_scans += 1
        if compiled:
            self.fused_compiles += 1
            return
        self.fused_scan_ns_max = max(self.fused_scan_ns_max, ns)
        self.fused_scan_ns.append(ns)
        if len(self.fused_scan_ns) > self.LATENCY_CAP:
            del self.fused_scan_ns[::2]

    def fused_scan_latency(self) -> dict:
        """count / p50 / max STEADY-STATE fused-scan wall time in ms
        (dispatch through ys materialization), plus how many scans paid a
        compile and how many plans fell back to the dispatch DP."""
        if self.fused_scan_ns:
            p50 = float(np.percentile(np.asarray(self.fused_scan_ns),
                                      50)) / 1e6
        else:
            p50 = 0.0
        return dict(count=self.fused_scans, p50_ms=p50,
                    max_ms=self.fused_scan_ns_max / 1e6,
                    compiles=self.fused_compiles,
                    fallbacks=self.fused_fallbacks,
                    routed=self.fused_routed)

    def record_latency(self, ns: int, compiled: bool = False) -> None:
        self.plan_calls += 1
        if compiled:
            self.compile_calls += 1
            self.compile_ns_max = max(self.compile_ns_max, ns)
            self.compile_ns.append(ns)
            if len(self.compile_ns) > self.LATENCY_CAP:
                del self.compile_ns[::2]
            return
        steady = self.plan_calls - self.compile_calls
        self.plan_ns_min = (ns if steady == 1
                            else min(self.plan_ns_min, ns))
        self.plan_ns_max = max(self.plan_ns_max, ns)
        self.plan_ns.append(ns)
        if len(self.plan_ns) > self.LATENCY_CAP:
            del self.plan_ns[::2]

    def plan_latency(self) -> dict:
        """min/p50/p99/max STEADY-STATE plan wall time in ms (zeros when
        never timed), plus the cold-compile bucket under ``compile``
        (count / p50 / max of samples whose dispatch compiled)."""
        if self.compile_ns:
            c50 = float(np.percentile(np.asarray(self.compile_ns), 50)) / 1e6
        else:
            c50 = 0.0
        compile_bucket = dict(count=self.compile_calls, p50_ms=c50,
                              max_ms=self.compile_ns_max / 1e6)
        if not self.plan_ns:
            return dict(count=self.plan_calls, min_ms=0.0, p50_ms=0.0,
                        p99_ms=0.0, max_ms=0.0, compile=compile_bucket)
        p50, p99 = np.percentile(np.asarray(self.plan_ns), [50, 99])
        return dict(count=self.plan_calls,
                    min_ms=self.plan_ns_min / 1e6,
                    p50_ms=float(p50) / 1e6, p99_ms=float(p99) / 1e6,
                    max_ms=self.plan_ns_max / 1e6, compile=compile_bucket)

    def as_dict(self) -> dict:
        out = {f.name: getattr(self, f.name)
               for f in dataclasses.fields(self)
               if f.metadata.get("export", True)}
        out["plan_latency"] = self.plan_latency()
        out["dispatches_per_plan"] = self.dispatches_per_plan
        return out

    def merge(self, other: "PlannerStats") -> "PlannerStats":
        """Field-driven merge: sum by default (``+`` also concatenates the
        latency sample lists), ``max`` / ``min_counted`` per metadata —
        adding a counter field needs no merge-list edit."""
        out = PlannerStats()
        for f in dataclasses.fields(self):
            a, b = getattr(self, f.name), getattr(other, f.name)
            how = f.metadata.get("merge", "sum")
            if how == "sum":
                v = a + b
            elif how == "max":
                v = max(a, b)
            elif how == "min_counted":
                # meaningful only for a side that ever recorded a
                # STEADY-STATE latency (compile-only sides hold the default)
                sn = self.plan_calls - self.compile_calls
                on = other.plan_calls - other.compile_calls
                if sn and on:
                    v = min(a, b)
                else:
                    v = a if sn else b
            else:                                  # pragma: no cover
                raise ValueError(f"unknown merge mode {how!r} for {f.name}")
            setattr(out, f.name, v)
        return out


class ExecutableCache:
    """Bounded LRU over AOT-compiled ``jdob_plan_batched`` executables.

    ``jax.jit`` keeps one executable per traced shape forever; a long-lived
    server sweeping many fleet sizes / bucket policies would grow that cache
    without bound.  Planners therefore compile through THIS cache instead
    (``jit(...).lower(args).compile()`` — which bypasses jit's own call
    cache), keyed by everything that determines the trace: the argument
    pytree structure, every leaf's (shape, dtype), and the static
    ``n_partitions`` / ``sort_key``.  Identical key ⇒ identical trace, so
    one executable safely serves every planner/profile that maps to it;
    evicting an entry drops the underlying XLA executable.

    :meth:`prefetch` compiles a shape on a small background thread pool
    (XLA compilation releases the GIL), so a caller that knows its future
    shapes — the OG level solver knows every per-length bucket a fleet can
    need — overlaps compiles with its early dispatches instead of stalling
    level by level.  A pending compile is installed into the LRU (and
    counted as the consuming planner's miss) at first lookup."""

    #: distinct thread-name prefix per cache instance, so tests (and
    #: operators) can attribute live compile threads to their owner
    _ids = itertools.count()

    def __init__(self, max_entries: int = 64):
        assert max_entries >= 1
        self.max_entries = max_entries
        self.thread_prefix = f"jdob-compile-{next(self._ids)}"
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._pending: dict = {}
        self._lock = threading.Lock()
        self._pool: ThreadPoolExecutor | None = None

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def keys(self):
        with self._lock:
            return tuple(self._entries)

    @staticmethod
    def _key(args, n_partitions: int, sort_key: str):
        leaves, treedef = jax.tree_util.tree_flatten(args)
        # works for concrete arrays AND jax.ShapeDtypeStruct placeholders
        avals = tuple((tuple(l.shape), np.dtype(l.dtype).name)
                      for l in leaves)
        return (treedef, avals, n_partitions, sort_key)

    @staticmethod
    def _compile(args, n_partitions: int, sort_key: str):
        with span("repro.plan.compile"):
            return jdob_plan_batched.lower(
                *args, n_partitions=n_partitions, sort_key=sort_key).compile()

    def _install(self, key, exe, stats: PlannerStats | None):
        """Insert under lock; LRU-evict past the bound."""
        with self._lock:
            self._pending.pop(key, None)
            self._entries[key] = exe
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                if stats is not None:
                    stats.evictions += 1
        return exe

    def lookup(self, args, n_partitions: int, sort_key: str,
               stats: PlannerStats | None = None):
        """Return the compiled executable for ``args``: LRU hit, pending
        prefetch (waits for the background compile), or a fresh compile."""
        key = self._key(args, n_partitions, sort_key)
        with self._lock:
            exe = self._entries.get(key)
            if exe is not None:
                self._entries.move_to_end(key)
                if stats is not None:
                    stats.hits += 1
                return exe
            fut = self._pending.get(key)
        if stats is not None:
            stats.misses += 1
        if fut is not None:
            try:
                return self._install(key, fut.result(), stats)
            except Exception:          # background compile failed: go sync
                with self._lock:
                    self._pending.pop(key, None)
        return self._install(key, self._compile(args, n_partitions,
                                                sort_key), stats)

    def lookup_general(self, args, statics, compile_fn,
                       stats: PlannerStats | None = None):
        """Like :meth:`lookup` for executables other than
        ``jdob_plan_batched`` (the fused grouping scan): ``statics`` is any
        hashable tuple folded into the key alongside the args' avals, and
        ``compile_fn(args)`` produces the executable on a miss.  Returns
        ``(exe, compiled)`` so the caller can classify its latency sample.
        General entries share the LRU bound with the batched-core entries
        but never go through the background prefetch pool."""
        key = self._key(args, -1, statics)
        with self._lock:
            exe = self._entries.get(key)
            if exe is not None:
                self._entries.move_to_end(key)
                if stats is not None:
                    stats.hits += 1
                return exe, False
        if stats is not None:
            stats.misses += 1
        return self._install(key, compile_fn(args), stats), True

    def prefetch(self, args, n_partitions: int, sort_key: str) -> None:
        """Schedule a background compile for a shape that will be needed
        soon (no-op if cached or already pending).  ``args`` leaves may be
        ``jax.ShapeDtypeStruct`` placeholders — only avals matter."""
        key = self._key(args, n_partitions, sort_key)
        with self._lock:
            if key in self._entries or key in self._pending:
                return
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=max(2, min(4, (os.cpu_count() or 2))),
                    thread_name_prefix=self.thread_prefix)
            self._pending[key] = self._pool.submit(
                self._compile, args, n_partitions, sort_key)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._pending.clear()

    def shutdown(self, wait: bool = True) -> None:
        """Stop the background prefetch pool (no-op if never started).
        Pending prefetches are dropped — a later :meth:`lookup` simply
        compiles synchronously — and the pool's worker threads exit, so a
        dropped private cache (e.g. a closed
        :class:`~repro.core.planner_service.PlannerService`) leaks no
        threads.  The cache itself stays usable; a new :meth:`prefetch`
        starts a fresh pool."""
        with self._lock:
            pool, self._pool = self._pool, None
            self._pending.clear()
        if pool is not None:
            pool.shutdown(wait=wait, cancel_futures=True)

    def resize(self, max_entries: int) -> None:
        assert max_entries >= 1
        with self._lock:
            self.max_entries = max_entries
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)


#: process-wide default cache — the bounded replacement for jit's own
#: unbounded per-shape cache (planners constructed without an explicit
#: ``cache`` share it, so throwaway planners still reuse compiles); sized
#: generously since correctness never depends on it, only recompile time —
#: long-lived servers wanting a tight bound pass their own cache / a
#: PlannerService(max_cached_shapes=...)
_SHARED_EXEC_CACHE = ExecutableCache(max_entries=256)


def shared_executable_cache() -> ExecutableCache:
    """The process-wide planner compile cache (see :class:`ExecutableCache`)."""
    return _SHARED_EXEC_CACHE


class BatchedPlanner:
    """Plans many co-inference groups per XLA dispatch.

    Caches the scaled task/edge constants and the frequency sweep; pads
    group widths to power-of-two buckets and splits large batches into
    fixed-size chunks so the jitted core compiles O(log M_max) shapes total
    no matter how many times / at what sizes it is invoked (OG segment
    enumeration, online flushes, serving).

    ``sort_keys`` with more than one entry evaluates the beyond-paper
    J-DOB+ ordering portfolio and keeps, per group, the best result
    (ties prefer the earlier key, matching the sequential portfolio).
    """

    def __init__(self, profile: TaskProfile, edge: EdgeProfile, *,
                 rho: float = 0.03e9, sort_keys: Sequence[str] = ("gamma",),
                 edge_dvfs: bool = True,
                 partitions: Sequence[int] | None = None,
                 group_chunk: int = 256, min_user_bucket: int = 4,
                 cache: ExecutableCache | None = None):
        self.profile = profile
        self.edge = edge
        self.rho = rho
        self.cache = cache if cache is not None else _SHARED_EXEC_CACHE
        self.stats = PlannerStats()
        self.sort_keys = tuple(sort_keys)
        self.edge_dvfs = edge_dvfs
        self.partitions = None if partitions is None else tuple(partitions)
        self.group_chunk = group_chunk
        self.min_user_bucket = min_user_bucket
        self.blocks = _prep_blocks(profile, edge)
        if edge_dvfs:
            self.f_sweep_np = make_f_sweep(edge, rho)
        else:
            self.f_sweep_np = np.asarray([edge.f_max])
        self.f_sweep = jnp.asarray(self.f_sweep_np / _GHZ)
        n = profile.N
        if partitions is not None:
            pm = np.zeros(n + 1, bool)
            pm[list(partitions)] = True
            self.part_mask = jnp.asarray(pm)
        else:
            self.part_mask = None
        self.phi_b, self.phi_s = edge.phi_coeffs(profile)
        self.psi_b, self.psi_s = edge.psi_coeffs(profile)
        self._vN = profile.v()[-1]
        self._uN = profile.u()[-1]

    def prefetch(self, m_pad: int, g_pad: int) -> None:
        """Kick off background compiles for the (g_pad, m_pad) batch shape
        under every sort key (see :meth:`ExecutableCache.prefetch`) —
        shape-only, no fleet data needed."""
        sds = jax.ShapeDtypeStruct
        f32 = np.dtype(np.float32)
        users = {k: sds((g_pad, m_pad), f32) for k in _USER_KEYS}
        c = {**self.blocks, **users}
        args = (c, self.f_sweep, sds((g_pad,), f32),
                sds((g_pad, m_pad), np.dtype(bool)), self.part_mask)
        for key in self.sort_keys:
            self.cache.prefetch(args, self.profile.N + 1, key)

    # ---- device passes -------------------------------------------------
    def _run(self, fleets, t_frees, m_pad: int):
        """One padded batch through the compiled core (per sort key)."""
        users, mask = _pad_fleets(fleets, m_pad)
        c = {**self.blocks, **users}
        tf = jnp.asarray(np.asarray(t_frees, np.float64))
        args = (c, self.f_sweep, tf, mask, self.part_mask)
        outs = []
        for key in self.sort_keys:
            exe = self.cache.lookup(args, self.profile.N + 1, key,
                                    stats=self.stats)
            self.stats.dispatches += 1
            outs.append(exe(*args))
        return outs

    def _dispatch(self, fleets: Sequence[DeviceFleet],
                  t_frees: Sequence[float], pad_users: bool,
                  m_pad: int | None, g_pad: int | None) -> list[tuple]:
        """Issue every device dispatch for a :meth:`plan` call and return
        the in-flight chunks as ``(start, n_real, outs_device)`` — the
        device→host transfer and winner reconstruction are deferred to
        :meth:`_materialize` (JAX dispatch is asynchronous, so work for
        every chunk is in flight before anything syncs)."""
        G = len(fleets)
        m_max = max(fl.M for fl in fleets)
        if m_pad is not None:
            assert m_pad >= m_max
        elif pad_users:
            m_pad = _bucket(m_max, self.min_user_bucket)
        else:
            m_pad = m_max
        # chunk + bucket the group dimension: large batches split into
        # fixed-size chunks, small ones pad to a power of two — every call
        # lands on one of O(log) compiled shapes instead of one per G
        chunk = self.group_chunk
        if G > chunk:
            starts = range(0, G, chunk)
        elif g_pad is not None:
            assert g_pad >= G
            starts = [0]
            chunk = g_pad
        else:
            starts = [0]
            # floor of 1, not min_user_bucket: a single-group plan (online
            # flushes) must not compute filler groups — G=1 is already a
            # stable compiled shape
            chunk = _bucket(G, 1) if pad_users else G
        pad_fleet = fleets[0].subset(np.arange(0))      # zero-user filler
        chunks = []
        for s in starts:
            part = list(fleets[s:s + chunk])
            tfs = list(t_frees[s:s + chunk])
            n_real = len(part)
            while len(part) < chunk:                    # ragged last chunk
                part.append(pad_fleet)
                tfs.append(0.0)
            chunks.append((s, n_real, self._run(part, tfs, m_pad)))
        return chunks

    def _materialize(self, fleets, t_frees, chunks) -> list[Schedule]:
        with span("repro.plan.fetch"):
            # ONE device→host transfer per output array, not one tiny
            # jnp slice per group: per-group indexing of jnp arrays was
            # ~90% of warm planning time at M = 80 ("E" stays on device —
            # reconstruction never reads the full grid)
            host = [(s, n_real, [{k: np.asarray(v) for k, v in o.items()
                                  if k != "E"} for o in outs])
                    for s, n_real, outs in chunks]
        schedules: list[Schedule] = []
        with span("repro.plan.reconstruct"):
            for s, n_real, outs in host:
                self.stats.groups_planned += n_real
                for g in range(n_real):
                    schedules.append(self._reconstruct(
                        fleets[s + g], float(t_frees[s + g]), outs, g))
        return schedules

    def plan(self, fleets: Sequence[DeviceFleet],
             t_frees: Sequence[float] | None = None,
             pad_users: bool = True, m_pad: int | None = None,
             g_pad: int | None = None) -> list[Schedule]:
        """Solve every group; returns one :class:`Schedule` per fleet.

        ``m_pad``/``g_pad`` pin the padded user width / group count so a
        caller issuing many variable-size batches (the OG level solver)
        hits a single compiled shape; by default both round up to a power
        of two.  Padding never changes results: masked users sum in as
        exact zeros (see ``_pow2_sum``) and filler groups are dropped."""
        return self.plan_async(fleets, t_frees, pad_users=pad_users,
                               m_pad=m_pad, g_pad=g_pad).get()

    def plan_async(self, fleets: Sequence[DeviceFleet],
                   t_frees: Sequence[float] | None = None,
                   pad_users: bool = True, m_pad: int | None = None,
                   g_pad: int | None = None) -> "PendingPlans":
        """Like :meth:`plan`, but returns a :class:`PendingPlans` handle
        with the results still device-resident: the dispatches are in
        flight, the device→host transfer and winner reconstruction wait
        until :meth:`PendingPlans.get`.  Callers with several independent
        batches (the OG level solver's per-length buckets, the tenancy
        what-if's paired trial solves) dispatch them ALL before paying any
        host sync, overlapping device work instead of serializing on each
        conversion.  ``get()`` is bit-identical to a direct ``plan``."""
        t0 = time.perf_counter_ns()
        G = len(fleets)
        if G == 0:
            return PendingPlans(self, [], [], [], t0)
        if t_frees is None:
            t_frees = [0.0] * G
        # compiles happen inside _dispatch (executable-cache misses): the
        # miss delta classifies this sample as cold-compile vs steady-state
        m0 = self.stats.misses
        with span("repro.plan.dispatch"):
            chunks = self._dispatch(fleets, t_frees, pad_users, m_pad, g_pad)
        return PendingPlans(self, list(fleets), list(t_frees), chunks, t0,
                            compiled=self.stats.misses > m0)

    # ---- host-side winner reconstruction ------------------------------
    def _reconstruct(self, fleet: DeviceFleet, t_free: float, outs,
                     g: int) -> Schedule:
        profile, edge = self.profile, self.edge
        # portfolio combine: strict < keeps the earlier sort key on ties
        best = 0
        e_best = float(np.asarray(outs[0]["energy"][g]))
        for i in range(1, len(outs)):
            e_i = float(np.asarray(outs[i]["energy"][g]))
            if e_i < e_best:
                best, e_best = i, e_i
        out = outs[best]
        # all-local fallback (ñ = N branch of Alg. 1; always feasible by the
        # standing assumption f_max can meet every deadline locally) —
        # float64 so the fallback agrees bit-for-bit with the LC baseline
        f_loc64 = np.clip(fleet.zeta * self._vN / fleet.deadline,
                          fleet.f_min, fleet.f_max)
        e_loc64 = fleet.kappa * self._uN * f_loc64 ** 2
        e_all_local = float(e_loc64.sum())
        if not np.isfinite(e_best) or e_all_local <= e_best:
            return Schedule(True, e_all_local, profile.N, float(edge.f_max),
                            np.zeros(fleet.M, bool), f_loc64, t_free,
                            dict(device=e_all_local, uplink=0.0, edge=0.0),
                            e_loc64)
        M = fleet.M
        nt = int(np.asarray(out["nt"][g]))
        fi = int(np.asarray(out["fi"][g]))
        off_b = np.asarray(out["off"][g])[:M]
        f_dev_b = np.asarray(out["f_dev"][g], np.float64)[:M] * _GHZ
        f_e = float(self.f_sweep_np[fi])
        eu = np.asarray(out["e_user"][g])[:M]
        # breakdown
        B = int(off_b.sum())
        up = float((profile.O[nt] / fleet.rate * fleet.p_up)[off_b].sum())
        edge_phi = float(self.phi_b[nt] + self.phi_s[nt] * B)
        edge_psi = float(self.psi_b[nt] + self.psi_s[nt] * B)
        edge_e = edge_psi * f_e ** 2
        dev = e_best - up - edge_e
        return Schedule(True, e_best, nt, f_e, off_b, f_dev_b,
                        float(np.asarray(out["t_end"][g])),
                        dict(device=dev, uplink=up, edge=edge_e), eu,
                        gpu_busy=edge_phi / f_e, edge_phi=edge_phi,
                        edge_psi=edge_psi)


class PendingPlans:
    """A dispatched-but-unmaterialized :meth:`BatchedPlanner.plan_async`
    batch.  The device outputs stay resident until :meth:`get`, which
    performs the single host transfer + winner reconstruction (memoized —
    repeated ``get`` returns the same list).  The planner's plan-latency
    sample covers dispatch through first materialization, so async callers
    report the latency they actually experienced; ``compiled`` marks
    samples whose dispatch triggered an XLA compile, routing them to the
    stats' cold-compile bucket instead of the steady-state histogram."""

    def __init__(self, planner: BatchedPlanner, fleets, t_frees, chunks,
                 t0_ns: int, compiled: bool = False):
        self._planner = planner
        self._fleets = fleets
        self._t_frees = t_frees
        self._chunks = chunks
        self._t0_ns = t0_ns
        self._compiled = compiled
        self._result: list[Schedule] | None = None

    @property
    def ready(self) -> bool:
        return self._result is not None

    def get(self) -> list[Schedule]:
        if self._result is None:
            self._result = self._planner._materialize(
                self._fleets, self._t_frees, self._chunks)
            self._planner.stats.record_latency(
                time.perf_counter_ns() - self._t0_ns,
                compiled=self._compiled)
            self._chunks = None          # free the device buffers
        return self._result


# ---------------------------------------------------------------------------
# Device-resident grouping DP (dp_backend="fused"): the whole level loop of
# the OG recurrence — candidate segment solves, float64 accumulation, the
# Pareto dominance sweep, beam truncation and the adaptive anchor re-fold —
# as ONE jitted lax.scan.  The dispatch backend issues O(M) device launches
# per plan (one per DP level); this backend issues exactly one for the scan
# plus the winning chain's materialization.
# ---------------------------------------------------------------------------

#: frontier buffer width used when a pareto DP runs with an UNBOUNDED
#: frontier on the fused backend; a level whose dominance survivors outgrow
#: it flags the scan as overflowed and the caller falls back to the
#: dispatch DP — exactness is never silently truncated away
FUSED_FRONTIER_WIDTH = 16

#: level-count crossover for the fused scan.  The scan's work is fixed-
#: shape — every level solves all L candidate segments at full fleet
#: width, O(L² · M · W) regardless of how short most segments are (a
#: built-in ~2x triangular waste: level j has only j real candidates) —
#: while the dispatch fold's per-length buckets solve short segments at
#: small padded widths.  Below the crossover the scan's one-dispatch
#: fold wins on launch overhead (measured 1.9-2.4x steady-state at
#: M ≤ 20 on CPU); past it the wasted full-width compute eats the win
#: (~0.95x at M = 40, 0.4-0.6x at M = 80), so ``dp_backend="fused"``
#: routes to the dispatch fold (counted in
#: ``PlannerStats.fused_routed``).  Fleet-scale callers rarely hit
#: this: ``plan_fleet`` sends big fleets through cohort planning, whose
#: ≤ cohort_size shards and atom-level merge DP are scan-sized.
FUSED_SCAN_MAX_LEVELS = 32


def fused_scan_viable(levels: int) -> bool:
    """Whether a fused DP scan over ``levels`` levels is expected to beat
    the dispatch fold (see :data:`FUSED_SCAN_MAX_LEVELS`)."""
    return levels <= FUSED_SCAN_MAX_LEVELS

_OG_SCAN_STATICS = ("n_partitions", "sort_keys", "mode", "width", "eps",
                    "beam", "growth", "cap", "anchor_mode", "prev_split")

# IEEE-754 binary64 as int64 bit patterns.  The fused scan keeps its
# energies and cursors in this form: for non-negative values the integer
# order is the float order and integer arithmetic is exact on every
# backend.  The v5e has no native float64; its emulated float64 neither
# holds nor adds binary64 values exactly, which broke the scan's bit
# parity with the host fold there.
_MANT = (1 << 52) - 1
_HIDDEN = 1 << 52
_INF_BITS = 0x7FF0000000000000


def _f64_add_bits(a, b):
    """``a + b`` in binary64, rounded to nearest even, for non-negative
    operands given and returned as int64 bit patterns (+inf stays +inf)."""
    a, b = jnp.maximum(a, b), jnp.minimum(a, b)
    ea, eb = a >> 52, b >> 52
    # significands with the hidden bit and 3 guard bits
    ma = jnp.where(ea > 0, (a & _MANT) | _HIDDEN, a & _MANT) << 3
    mb = jnp.where(eb > 0, (b & _MANT) | _HIDDEN, b & _MANT) << 3
    ea, eb = jnp.maximum(ea, 1), jnp.maximum(eb, 1)
    d = jnp.minimum(ea - eb, 60)
    sticky = (mb & ((jnp.int64(1) << d) - 1)) != 0
    s = ma + ((mb >> d) | sticky)
    carry = s >= (jnp.int64(1) << 56)
    s = jnp.where(carry, (s >> 1) | (s & 1), s)
    e = ea + carry
    m, low = s >> 3, s & 7
    m = m + ((low > 4) | ((low == 4) & ((m & 1) == 1)))
    over = m >= (jnp.int64(1) << 53)
    m = jnp.where(over, m >> 1, m)
    e = e + over
    bits = jnp.where(m >= _HIDDEN, e << 52, 0) | (m & _MANT)
    return jnp.where((a >= _INF_BITS) | (e >= 2047), _INF_BITS, bits)


def _f32_to_f64_bits(x):
    """Exact binary64 bit patterns of non-negative float32 values."""
    u = jax.lax.bitcast_convert_type(x, jnp.int32)
    e, m = u >> 23, (u & 0x7FFFFF).astype(jnp.int64)
    # subnormal: normalize on the leading bit's position p
    p = (31 - jax.lax.clz(jnp.maximum(m, 1).astype(jnp.int32))
         ).astype(jnp.int64)
    sub = ((p + (1023 - 149)) << 52) | ((m << (52 - p)) & _MANT)
    norm = ((e.astype(jnp.int64) + (1023 - 127)) << 52) | (m << 29)
    return jnp.where(e == 255, _INF_BITS,
                     jnp.where(e > 0, norm, jnp.where(m > 0, sub, 0)))


def _f64_bits_to_f32(b):
    """Non-negative binary64 bit patterns rounded to nearest-even
    float32 — what ``astype(float32)`` gives on an IEEE backend."""
    e, m = b >> 52, b & _MANT
    e32 = e - (1023 - 127)
    sig = jnp.where(e > 0, m | _HIDDEN, m)
    sh = jnp.minimum(29 + jnp.maximum(1 - e32, 0), 62)
    q, rem = sig >> sh, sig & ((jnp.int64(1) << sh) - 1)
    half = jnp.int64(1) << (sh - 1)
    q = q + ((rem > half) | ((rem == half) & ((q & 1) == 1)))
    out = jnp.where(e32 > 0, (e32 - 1) << 23, 0) + q
    out = jnp.where((e == 2047) | (out >= 0x7F800000), 0x7F800000, out)
    return jax.lax.bitcast_convert_type(out.astype(jnp.int32), jnp.float32)


@functools.partial(jax.jit, static_argnames=_OG_SCAN_STATICS)
def _og_scan(c_user, blocks, f_sweep, part_mask, bounds, e_all, t_free0,
             start, n_active, window, size_cap, e_tab, tf_tab, sp_tab,
             si_tab, va_tab, anc0, width0, widen0, *, n_partitions,
             sort_keys, mode, width, eps, beam, growth, cap,
             anchor_mode, prev_split):
    """The grouping DP's level loop as one ``lax.scan`` over levels.

    MUST be traced and executed under ``jax.enable_x64(True)``
    (see :func:`og_plan_fused`): the DP state tables and the dominance
    sweep hold binary64 values as int64 bit patterns and add them with
    :func:`_f64_add_bits`, which matches the host DP's float64
    accumulation bit for bit on any backend, while every segment solve
    stays in the float32 :func:`_solve_group` math (python scalars are
    weak types, so enabling x64 does not promote the inlined kernel).

    State layout — the frontier lives on device as fixed-width masked
    rows: ``e_tab``/``tf_tab`` (L+1, W) binary64 bit patterns of the
    energies / threaded cursors (all non-negative), ``sp_tab``/``si_tab``
    (L+1, W) int32 backpointers (split level, state slot), ``va_tab``
    (L+1, W) occupancy mask (valid slots are always a prefix; W == 1 is
    the prefix DP).  ``bounds`` (L+1,)
    generalizes the level axis: ``arange(M+1)`` for the user-level OG DP,
    the atom boundaries for the cohort merge DP (level j covers users
    ``[bounds[i], bounds[j])``).  Levels ``j <= start`` (incremental
    resume) and ``j > n_active`` (bucket padding) pass through unchanged.
    ``e_all`` rows carry the bit patterns of the precomputed float64
    all-local fallback energies (host ``_reconstruct`` semantics).  One
    ys row per level is the ONLY materialization — the host backtracks
    the winning chain from it and re-solves just that chain's
    segments."""
    L = bounds.shape[0] - 1
    W = width
    Mp = c_user["T"].shape[0]
    INF64 = jnp.asarray(_INF_BITS, jnp.int64)
    i_vec = jnp.arange(L, dtype=jnp.int32)
    slot = jnp.arange(W, dtype=jnp.int32)

    def solve_seg(lo, ln, tf32):
        # roll the sorted fleet so segment [lo, lo+ln) leads, mask the
        # rest: bitwise identical to the dispatch path's bucketed solve
        # (_pow2_sum is padding-invariant and masked lanes are neutral)
        rolled = {k: jnp.roll(c_user[k], -lo) for k in _USER_KEYS}
        act = jnp.arange(Mp, dtype=jnp.int32) < ln
        cc = {**blocks, **rolled}
        e_b = t_b = None
        for key in sort_keys:       # portfolio combine: earlier key wins ties
            out = _solve_group(cc, f_sweep, tf32, act, part_mask,
                               n_partitions, key)
            if e_b is None:
                e_b, t_b = out["energy"], out["t_end"]
            else:
                better = out["energy"] < e_b
                e_b = jnp.where(better, out["energy"], e_b)
                t_b = jnp.where(better, out["t_end"], t_b)
        return e_b, t_b

    def step(carry, xs):
        e_tab, tf_tab, sp_tab, si_tab, va_tab, anc, bw_w, bw_n = carry
        j, eall_row = xs
        lo = bounds[:L]
        ln = bounds[j] - lo
        seg_ok = (i_vec < j) & (i_vec >= j - window) & \
            ~((j - i_vec > 1) & (ln > size_cap))
        st_e, st_tf = e_tab[:L], tf_tab[:L]
        cand_ok = seg_ok[:, None] & va_tab[:L] & (st_e < INF64)
        # all (state slot, candidate split) segment solves of this level
        e32, t32 = jax.vmap(solve_seg)(
            jnp.broadcast_to(lo[:, None], (L, W)).reshape(-1),
            jnp.broadcast_to(ln[:, None], (L, W)).reshape(-1),
            _f64_bits_to_f32(st_tf).reshape(-1))
        e32 = e32.reshape(L, W)
        t32 = t32.reshape(L, W)
        # host _reconstruct's float64 all-local fallback: always feasible,
        # replaces the grid winner when cheaper-or-equal, passes the
        # cursor through unchanged
        e_seg = _f32_to_f64_bits(e32)
        all_local = (e_seg >= INF64) | (eall_row[:, None] <= e_seg)
        seg_e = jnp.where(all_local, eall_row[:, None], e_seg)
        seg_tf = jnp.where(all_local, st_tf, _f32_to_f64_bits(t32))
        cand_e = jnp.where(cand_ok, _f64_add_bits(st_e, seg_e), INF64)
        dflt_sp = (j - 1) if prev_split else jnp.zeros((), jnp.int32)

        if mode == "prefix":
            ce = cand_e[:, 0]
            bi = jnp.argmin(ce).astype(jnp.int32)   # first min == smallest i
            feas = ce[bi] < INF64
            row_e = jnp.where(feas, ce[bi], INF64)[None]
            row_tf = jnp.where(feas, seg_tf[bi, 0], t_free0)[None]
            row_sp = jnp.where(feas, bi, dflt_sp)[None].astype(jnp.int32)
            row_si = jnp.zeros((1,), jnp.int32)
            row_va = jnp.ones((1,), bool)
            anc_j = jnp.zeros((), jnp.int32)
            n_in = jnp.sum(ce < INF64).astype(jnp.int32)
            n_front = jnp.ones((), jnp.int32)
            inserted = jnp.zeros((), bool)
            overflow = jnp.zeros((), bool)
        else:
            fe = cand_e.reshape(-1)
            ftf = jnp.where(cand_ok, seg_tf, INF64).reshape(-1)
            fsp = jnp.broadcast_to(i_vec[:, None], (L, W)).reshape(-1)
            fsi = jnp.broadcast_to(slot[None, :], (L, W)).reshape(-1)
            fin = fe < INF64
            # _pareto_sweep's sort key (energy, t_free, split, state):
            # flat order is already (split, state) lexicographic, so two
            # stable sorts finish the key
            p = jnp.argsort(ftf, stable=True)
            p = p[jnp.argsort(fe[p], stable=True)]
            se, stf = fe[p], ftf[p]
            ssp, ssi, sfin = fsp[p], fsi[p], fin[p]
            if eps == 0.0:
                # keep iff strictly earlier than every kept predecessor ==
                # strictly below the exclusive prefix-min (dropped
                # candidates never lower the running min)
                cm = jax.lax.associative_scan(jnp.minimum, stf)
                pmin = jnp.concatenate([INF64[None], cm[:-1]])
                keep = sfin & (stf < pmin)
            else:
                # the epsilon threshold is one float64 product: exact on
                # an IEEE backend, emulated (not bit-exact) on the v5e
                f64 = functools.partial(jax.lax.bitcast_convert_type,
                                        new_dtype=jnp.float64)

                def sweep(btf, x):
                    tf_, ok = x
                    k = ok & (f64(tf_) < f64(btf) * (1.0 - eps))
                    return jnp.where(k, tf_, btf), k
                _, keep = jax.lax.scan(sweep, INF64, (stf, sfin))
            n_in = jnp.sum(sfin).astype(jnp.int32)
            n_sur = jnp.sum(keep).astype(jnp.int32)
            if beam == "adaptive":
                nbw_w, nbw_n = jax.lax.while_loop(
                    lambda s: (n_sur > s[0]) & (s[0] < cap),
                    lambda s: (jnp.minimum(s[0] * growth, cap), s[1] + 1),
                    (bw_w, bw_n))
                bw = nbw_w
                overflow = jnp.zeros((), bool)
            elif beam is None:
                nbw_w, nbw_n = bw_w, bw_n
                bw = jnp.asarray(W, jnp.int32)
                overflow = n_sur > W
            else:
                nbw_w, nbw_n = bw_w, bw_n
                bw = jnp.asarray(beam, jnp.int32)
                overflow = jnp.zeros((), bool)
            rank = keep.astype(jnp.int32).cumsum() - 1
            keep = keep & (rank < bw)
            n_front = jnp.sum(keep).astype(jnp.int32)
            # compact kept states to the row head, preserving sort order
            q = jnp.argsort(~keep, stable=True)[:W]
            row_va = slot < n_front
            row_e = jnp.where(row_va, se[q], INF64)
            row_tf = jnp.where(row_va, stf[q], t_free0)
            row_sp = jnp.where(row_va, ssp[q], 0).astype(jnp.int32)
            row_si = jnp.where(row_va, ssi[q], 0).astype(jnp.int32)
            # empty level -> the host's infeasible sentinel state
            empty = n_front == 0
            s0 = slot == 0
            row_va = row_va | (empty & s0)
            row_tf = jnp.where(empty & s0, t_free0, row_tf)
            row_sp = jnp.where(empty & s0, dflt_sp, row_sp)
            if anchor_mode:
                # re-fold the prefix-DP anchor chain over the SAME segment
                # results, then force-retain it in the frontier
                a_sl = anc[:L]
                ae = jnp.take_along_axis(st_e, a_sl[:, None], 1)[:, 0]
                a_se = jnp.take_along_axis(seg_e, a_sl[:, None], 1)[:, 0]
                a_stf = jnp.take_along_axis(seg_tf, a_sl[:, None], 1)[:, 0]
                a_va = jnp.take_along_axis(va_tab[:L], a_sl[:, None],
                                           1)[:, 0]
                a_ce = jnp.where(seg_ok & a_va & (ae < INF64),
                                 _f64_add_bits(ae, a_se), INF64)
                ab = jnp.argmin(a_ce).astype(jnp.int32)
                a_found = a_ce[ab] < INF64
                a_si = a_sl[ab]
                match = row_va & (row_sp == ab) & (row_si == a_si)
                ins = (~empty) & a_found & (~jnp.any(match))
                put = ins & (slot == n_front)       # n_front <= cap < W
                row_e = jnp.where(put, a_ce[ab], row_e)
                row_tf = jnp.where(put, a_stf[ab], row_tf)
                row_sp = jnp.where(put, ab, row_sp)
                row_si = jnp.where(put, a_si, row_si)
                row_va = row_va | put
                # re-sort by (e, tf, sp, si); identity when nothing was
                # inserted ((sp, si) pairs are distinct, so the order is
                # strict) — invalid slots carry +inf keys and stay last
                ke = jnp.where(row_va, row_e, INF64)
                ktf = jnp.where(row_va, row_tf, INF64)
                r = jnp.argsort(row_si, stable=True)
                r = r[jnp.argsort(row_sp[r], stable=True)]
                r = r[jnp.argsort(ktf[r], stable=True)]
                r = r[jnp.argsort(ke[r], stable=True)]
                row_e, row_tf = row_e[r], row_tf[r]
                row_sp, row_si, row_va = row_sp[r], row_si[r], row_va[r]
                match = row_va & (row_sp == ab) & (row_si == a_si)
                anc_j = jnp.where(empty | ~a_found, 0,
                                  jnp.argmax(match).astype(jnp.int32))
                inserted = ins
            else:
                anc_j = jnp.zeros((), jnp.int32)
                inserted = jnp.zeros((), bool)

        # resume/padding passthrough: only levels in (start, n_active]
        # fold; the rest keep their (possibly host-provided) rows
        active = (j > start) & (j <= n_active)
        row_e = jnp.where(active, row_e, e_tab[j])
        row_tf = jnp.where(active, row_tf, tf_tab[j])
        row_sp = jnp.where(active, row_sp, sp_tab[j])
        row_si = jnp.where(active, row_si, si_tab[j])
        row_va = jnp.where(active, row_va, va_tab[j])
        anc_j = jnp.where(active, anc_j, anc[j])
        e_tab = e_tab.at[j].set(row_e)
        tf_tab = tf_tab.at[j].set(row_tf)
        sp_tab = sp_tab.at[j].set(row_sp)
        si_tab = si_tab.at[j].set(row_si)
        va_tab = va_tab.at[j].set(row_va)
        anc = anc.at[j].set(anc_j)
        if mode != "prefix" and beam == "adaptive":
            bw_w = jnp.where(active, nbw_w, bw_w)
            bw_n = jnp.where(active, nbw_n, bw_n)
        ys = dict(e=row_e, tf=row_tf, sp=row_sp, si=row_si, va=row_va,
                  anchor=anc_j, width=bw_w, widen=bw_n, n_in=n_in,
                  n_front=n_front, inserted=inserted & active,
                  overflow=overflow & active, active=active)
        return (e_tab, tf_tab, sp_tab, si_tab, va_tab, anc, bw_w, bw_n), ys

    j_vec = jnp.arange(1, L + 1, dtype=jnp.int32)
    carry0 = (e_tab, tf_tab, sp_tab, si_tab, va_tab, anc0, width0, widen0)
    _, ys = jax.lax.scan(step, carry0, (j_vec, e_all))
    return ys


@dataclasses.dataclass
class FusedScanResult:
    """Host-side view of one fused DP scan (:func:`og_plan_fused`).

    ``rows[k]`` is the frontier of level ``start + 1 + k`` as numeric
    ``(energy, t_free, split, state_idx)`` tuples in frontier order
    (prefix DP: exactly one tuple per level); ``anchor``/``beam_hist``
    align with ``rows`` (adaptive-beam runs).  ``overflow`` means some
    level's unbounded frontier outgrew the device buffer — the rows are
    NOT authoritative and the caller must fall back to the dispatch DP."""

    rows: list
    anchor: list
    beam_hist: list
    overflow: bool
    width: int
    widenings: int


def og_plan_fused(planner: BatchedPlanner, sorted_fleet: DeviceFleet, *,
                  t_free: float = 0.0, mode: str = "prefix",
                  frontier_eps: float = 0.0, beam_width=None,
                  bounds: np.ndarray | None = None, n_active: int | None = None,
                  window: int | None = None, size_cap: int | None = None,
                  prev_split: bool = False, anchor_mode: bool | None = None,
                  init_rows: list | None = None,
                  init_anchor: list | None = None,
                  width0: int = 1, widen0: int = 0,
                  stats: PlannerStats | None = None) -> FusedScanResult:
    """Fold the grouping DP on device in ONE dispatch (see :func:`_og_scan`).

    ``sorted_fleet`` is the deadline-sorted fleet; ``bounds`` (default
    ``arange(M+1)``) maps DP levels to user positions, with levels past
    ``n_active`` padded out (cohort merge bucketing).  ``beam_width``
    follows the grouping knob: ``None`` (unbounded — overflow falls back),
    an int, or an adaptive-beam object (duck-typed on
    ``width``/``growth``/``cap``/``widenings``).  ``init_rows`` /
    ``init_anchor`` / ``width0`` / ``widen0`` resume an incremental fold:
    levels ``0..len(init_rows)-1`` are trusted verbatim and the scan
    starts at the churn level — bit-identical to a scratch fused fold by
    the same argument as the host resume (a level reads only earlier
    levels).  The scan's decisions are bit-identical to the host DP's, so
    the caller materializes the winning chain through the ordinary
    dispatch ``solve`` closure and inherits energy/group parity
    structurally.  Applies frontier/beam statistics to ``stats`` exactly
    as the host sweep would (skipped on overflow — the dispatch fallback
    will account for itself)."""
    assert mode in ("prefix", "pareto"), f"unknown dp mode {mode!r}"
    M = sorted_fleet.M
    if bounds is None:
        bounds = np.arange(M + 1, dtype=np.int32)
    bounds = np.asarray(bounds, np.int32)
    L = len(bounds) - 1
    n_act = L if n_active is None else int(n_active)
    adaptive = hasattr(beam_width, "fit")
    if anchor_mode is None:
        anchor_mode = adaptive and mode == "pareto"
    if mode == "prefix":
        W, beam, growth, cap = 1, None, 2, 1
    elif adaptive:
        growth, cap = int(beam_width.growth), int(beam_width.cap)
        W, beam = cap + 1, "adaptive"
    elif beam_width is None:
        W, beam, growth, cap = FUSED_FRONTIER_WIDTH, None, 2, 1
    else:
        W, beam, growth, cap = int(beam_width), int(beam_width), 2, 1

    rows0 = init_rows if init_rows is not None \
        else [[(0.0, float(t_free), -1, 0)]]
    start = len(rows0) - 1
    if any(len(states) > W for states in rows0):
        # a resumed host frontier wider than the device buffer cannot be
        # represented — let the caller fall back without a dispatch
        return FusedScanResult([], [], [], True, W, widen0)
    if not t_free >= 0.0:
        raise ValueError(f"the fused scan needs t_free >= 0, got {t_free}")
    e_t = np.full((L + 1, W), np.inf)
    tf_t = np.full((L + 1, W), float(t_free))
    sp_t = np.zeros((L + 1, W), np.int32)
    si_t = np.zeros((L + 1, W), np.int32)
    va_t = np.zeros((L + 1, W), bool)
    for lvl, states in enumerate(rows0):
        for s_i, (e, tf, sp, si) in enumerate(states):
            e_t[lvl, s_i] = e
            tf_t[lvl, s_i] = tf
            sp_t[lvl, s_i] = sp
            si_t[lvl, s_i] = si
            va_t[lvl, s_i] = True
    anc_np = np.zeros(L + 1, np.int32)
    if init_anchor:
        anc_np[:len(init_anchor)] = init_anchor

    # float64 all-local energies per (level, split) — np slice sums match
    # _reconstruct's ``e_loc64.sum()`` bitwise (same values, same order,
    # same pairwise reduction)
    f_loc = np.clip(sorted_fleet.zeta * planner._vN / sorted_fleet.deadline,
                    sorted_fleet.f_min, sorted_fleet.f_max)
    el = np.asarray(sorted_fleet.kappa * planner._uN * f_loc ** 2,
                    np.float64)
    e_all = np.zeros((L, L))
    for j in range(start + 1, n_act + 1):
        for i in range(j):
            e_all[j - 1, i] = el[bounds[i]:bounds[j]].sum()

    users, _ = _pad_fleets([sorted_fleet], M)
    c_user = {k: users[k][0] for k in _USER_KEYS}
    statics = dict(n_partitions=planner.profile.N + 1,
                   sort_keys=planner.sort_keys, mode=mode, width=W,
                   eps=float(frontier_eps), beam=beam, growth=growth,
                   cap=cap, anchor_mode=bool(anchor_mode),
                   prev_split=bool(prev_split))
    key = ("og_scan",) + tuple(sorted(statics.items()))
    t0 = time.perf_counter_ns()
    # the x64 scope covers compile AND execution: the compiled signature
    # carries int64 tables, and input conversion follows the ambient
    # config, so calling outside the scope would downcast them.  Float64
    # values cross to the device as their bit patterns.
    bits = lambda a: jnp.asarray(np.asarray(a, np.float64).view(np.int64))
    with jax.enable_x64(True):
        args = (c_user, planner.blocks, planner.f_sweep, planner.part_mask,
                jnp.asarray(bounds), bits(e_all), bits(t_free),
                jnp.asarray(np.int32(start)), jnp.asarray(np.int32(n_act)),
                jnp.asarray(np.int32(L if window is None else window)),
                jnp.asarray(np.int32(M if size_cap is None else size_cap)),
                bits(e_t), bits(tf_t), jnp.asarray(sp_t),
                jnp.asarray(si_t), jnp.asarray(va_t), jnp.asarray(anc_np),
                jnp.asarray(np.int32(width0)),
                jnp.asarray(np.int32(widen0)))
        exe, compiled = planner.cache.lookup_general(
            args, key, lambda a: _og_scan.lower(*a, **statics).compile(),
            stats=planner.stats)
        planner.stats.dispatches += 1
        ys = {k: np.asarray(v) for k, v in exe(*args).items()}
    ys["e"], ys["tf"] = ys["e"].view(np.float64), ys["tf"].view(np.float64)
    planner.stats.record_fused_scan(time.perf_counter_ns() - t0,
                                    compiled=compiled)

    active = ys["active"]
    overflow = bool(ys["overflow"].any())
    rows, anchor, beam_hist = [], [], []
    final_w, final_n = width0, widen0
    for idx in range(L):
        if not active[idx]:
            continue
        n = int(ys["va"][idx].sum())        # valid slots are a prefix
        rows.append([(float(ys["e"][idx, s]), float(ys["tf"][idx, s]),
                      int(ys["sp"][idx, s]), int(ys["si"][idx, s]))
                     for s in range(n)])
        anchor.append(int(ys["anchor"][idx]))
        final_w, final_n = int(ys["width"][idx]), int(ys["widen"][idx])
        beam_hist.append((final_w, final_n))
    if stats is not None and mode == "pareto" and not overflow:
        for idx in range(L):
            if not active[idx]:
                continue
            n_f = int(ys["n_front"][idx]) + int(ys["inserted"][idx])
            stats.frontier_states += n_f
            stats.frontier_max = max(stats.frontier_max, n_f)
            stats.dominance_pruned += \
                int(ys["n_in"][idx]) - int(ys["n_front"][idx])
            if len(stats.frontier_levels) < 4096:
                stats.frontier_levels.append(int(ys["n_front"][idx]))
        if adaptive:
            stats.beam_widenings += final_n - widen0
    return FusedScanResult(rows, anchor, beam_hist, overflow,
                           final_w, final_n)


def jdob_schedule(profile: TaskProfile,
                  fleet: DeviceFleet,
                  edge: EdgeProfile,
                  t_free: float = 0.0,
                  rho: float = 0.03e9,
                  partitions: Sequence[int] | None = None,
                  edge_dvfs: bool = True,
                  sort_key: str = "gamma") -> Schedule:
    """Run J-DOB for one group (a batch of one through the batched core).
    ``partitions`` restricts ñ candidates (``[0, N]`` gives the
    J-DOB-binary baseline); ``edge_dvfs=False`` pins f_e = f_e,max (the
    J-DOB-w/o-edge-DVFS baseline); ``sort_key="budget"`` selects the
    beyond-paper J-DOB+ user ordering."""
    planner = BatchedPlanner(profile, edge, rho=rho, sort_keys=(sort_key,),
                             edge_dvfs=edge_dvfs, partitions=partitions)
    return planner.plan([fleet], [t_free], pad_users=False)[0]


def jdob_energy_grid(profile: TaskProfile, fleet: DeviceFleet,
                     edge: EdgeProfile, t_free: float = 0.0,
                     rho: float = 0.03e9) -> np.ndarray:
    """(N+1, k) energy grid — diagnostics + the Pallas kernel's oracle."""
    blocks = _prep_blocks(profile, edge)
    users, mask = _pad_fleets([fleet], fleet.M)
    out = jdob_plan_batched({**blocks, **users},
                            jnp.asarray(make_f_sweep(edge, rho) / _GHZ),
                            jnp.asarray(np.asarray([t_free])), mask,
                            n_partitions=profile.N + 1)
    return np.asarray(out["E"][0])

"""Device-resident grouping DP (``dp_backend="fused"``): bitwise parity
with the dispatch fold across every DP mode and planning regime, the
anchor-retention property inside the scan, the O(1) dispatches-per-plan
observable, and the Pallas sweep inner backend vs the jitted core."""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import (IncrementalOgState, PlannerService, cohort_grouping,
                        make_edge_profile, make_fleet, mobilenet_v2_profile,
                        optimal_grouping, optimal_grouping_reference)
from repro.core.jdob import FUSED_FRONTIER_WIDTH, jdob_schedule

PROF = mobilenet_v2_profile()
EDGE = make_edge_profile(PROF)

#: one service per module: compiled shapes (including the fused scan's
#: executables) amortize across tests
SVC = PlannerService(PROF, EDGE)

#: the parity matrix's DP configurations: (dp, beam_width)
DP_CONFIGS = (("prefix", None), ("pareto", None), ("pareto", "auto"),
              ("pareto", 2))


def _assert_same_plan(a, b):
    assert a.energy == b.energy
    assert [list(g) for g in a.groups] == [list(g) for g in b.groups]
    np.testing.assert_array_equal(a.per_user_energy, b.per_user_energy)
    assert a.t_free_end == b.t_free_end


# ---------------------------------------------------------------------------
# offline parity: fused == dispatch bit for bit
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(M=st.integers(2, 12), beta_lo=st.floats(3.0, 10.0),
       spread=st.floats(1.0, 30.0), seed=st.integers(0, 99),
       t_free=st.floats(0.0, 0.05),
       config=st.sampled_from(DP_CONFIGS))
def test_property_fused_offline_matches_dispatch(M, beta_lo, spread, seed,
                                                 t_free, config):
    """One scan == one host fold: energies, groups, per-user energies and
    the threaded cursor all bitwise equal, for the prefix DP, the
    unbounded pareto DP, the adaptive beam and a hard beam cap."""
    dp, bw = config
    fleet = make_fleet(M, PROF, EDGE, beta=(beta_lo, beta_lo + spread),
                       seed=seed)
    d = optimal_grouping(PROF, fleet, EDGE, service=SVC, dp=dp,
                         beam_width=bw, t_free=t_free,
                         dp_backend="dispatch")
    f = optimal_grouping(PROF, fleet, EDGE, service=SVC, dp=dp,
                         beam_width=bw, t_free=t_free, dp_backend="fused")
    _assert_same_plan(d, f)


@settings(max_examples=6, deadline=None)
@given(M=st.integers(2, 8), seed=st.integers(0, 99),
       config=st.sampled_from(DP_CONFIGS))
def test_property_fused_matches_reference_oracle(M, seed, config):
    """The fused fold also agrees with the sequential seed oracle (which
    validates ``dp_backend`` but always folds host-side)."""
    dp, bw = config
    fleet = make_fleet(M, PROF, EDGE, beta=(4.0, 25.0), seed=seed)
    f = optimal_grouping(PROF, fleet, EDGE, service=SVC, dp=dp,
                         beam_width=bw, dp_backend="fused")
    ref = optimal_grouping_reference(PROF, fleet, EDGE, dp=dp,
                                     beam_width=bw, dp_backend="dispatch")
    assert f.energy == ref.energy
    assert [list(g) for g in f.groups] == [list(g) for g in ref.groups]


# ---------------------------------------------------------------------------
# incremental parity: suffix re-fold == scan starting at the churn level
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(M=st.integers(3, 8), beta_lo=st.floats(4.0, 10.0),
       spread=st.floats(1.0, 30.0), seed=st.integers(0, 99),
       new_beta=st.floats(2.0, 50.0),
       config=st.sampled_from(DP_CONFIGS))
def test_property_fused_incremental_matches_dispatch(M, beta_lo, spread,
                                                     seed, new_beta,
                                                     config):
    """Arrival and departure each re-fold only the suffix — as a device
    scan starting at the churn level — bit-identical to the dispatch
    incremental state AND to a from-scratch fused fold."""
    dp, bw = config
    fleet = make_fleet(M, PROF, EDGE, beta=(beta_lo, beta_lo + spread),
                       seed=seed)
    disp = IncrementalOgState(PROF, fleet, EDGE, service=SVC, dp=dp,
                              beam_width=bw, dp_backend="dispatch")
    fuse = IncrementalOgState(PROF, fleet, EDGE, service=SVC, dp=dp,
                              beam_width=bw, dp_backend="fused")
    _assert_same_plan(fuse.plan(), disp.plan())
    row = make_fleet(1, PROF, EDGE, beta=new_beta, seed=seed + 1)
    _assert_same_plan(fuse.arrive(row), disp.arrive(row))
    scratch = optimal_grouping(PROF, fuse.fleet, EDGE, service=SVC, dp=dp,
                               beam_width=bw, dp_backend="fused")
    _assert_same_plan(fuse.plan(), scratch)
    gone = seed % disp.M
    _assert_same_plan(fuse.depart(gone), disp.depart(gone))


# ---------------------------------------------------------------------------
# cohort parity: fused shard DPs + fused merge DP
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(M=st.integers(13, 26), C=st.integers(6, 12),
       mw=st.integers(2, 4), seed=st.integers(0, 99),
       config=st.sampled_from(DP_CONFIGS))
def test_property_fused_cohort_matches_dispatch(M, C, mw, seed, config):
    """Hierarchical planning above the cohort threshold: the fused shard
    folds and the fused merge DP (atom-boundary levels, fuse-window and
    size-cap masks) reproduce the dispatch plan bitwise."""
    dp, bw = config
    fleet = make_fleet(M, PROF, EDGE, beta=(3.0, 20.0), seed=seed)
    d = cohort_grouping(PROF, fleet, EDGE, cohort_size=C, merge_window=mw,
                        service=SVC, dp=dp, beam_width=bw,
                        dp_backend="dispatch")
    f = cohort_grouping(PROF, fleet, EDGE, cohort_size=C, merge_window=mw,
                        service=SVC, dp=dp, beam_width=bw,
                        dp_backend="fused")
    _assert_same_plan(d, f)


# ---------------------------------------------------------------------------
# anchor retention: the adaptive beam's safety rail survives the scan
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(M=st.integers(3, 10), beta_lo=st.floats(3.0, 10.0),
       spread=st.floats(1.0, 40.0), seed=st.integers(0, 99),
       t_free=st.floats(0.0, 0.08))
def test_property_fused_auto_beam_never_above_prefix(M, beta_lo, spread,
                                                     seed, t_free):
    """The scan re-folds the prefix-DP anchor chain on device and
    force-retains it in every level's frontier, so the fused adaptive
    beam inherits the host guarantee: never above the prefix DP."""
    fleet = make_fleet(M, PROF, EDGE, beta=(beta_lo, beta_lo + spread),
                       seed=seed)
    px = optimal_grouping(PROF, fleet, EDGE, service=SVC, t_free=t_free,
                          dp_backend="fused")
    au = optimal_grouping(PROF, fleet, EDGE, service=SVC, dp="pareto",
                          beam_width="auto", t_free=t_free,
                          dp_backend="fused")
    assert au.energy <= px.energy


# ---------------------------------------------------------------------------
# dispatches_per_plan: the O(M) -> O(1) claim as a number
# ---------------------------------------------------------------------------

def test_fused_dispatches_per_plan_constant_in_m():
    """The dispatch fold issues ~one launch per DP level (≈M); the fused
    fold issues the scan plus the winning chain's materialization — a
    per-plan count that does NOT grow with M."""
    counts = {}
    for backend in ("dispatch", "fused"):
        per_m = []
        for M in (8, 16, 24):
            svc = PlannerService(PROF, EDGE)
            fleet = make_fleet(M, PROF, EDGE, beta=(3.0, 20.0), seed=0)
            optimal_grouping(PROF, fleet, EDGE, service=svc, dp="pareto",
                             dp_backend=backend)
            st_ = svc.stats()
            assert st_.og_plans == 1
            per_m.append(st_.dispatches_per_plan)
        counts[backend] = per_m
    assert counts["dispatch"][-1] >= 24           # ≈ one per level
    # fused: scan + chain buckets; bounded well below the level count
    assert all(c <= 8 for c in counts["fused"])
    assert counts["fused"][-1] <= counts["fused"][0] + 2   # flat in M


def test_fused_size_crossover_routes_to_dispatch(monkeypatch):
    """Past ``FUSED_SCAN_MAX_LEVELS`` the scan's fixed-shape work loses
    to per-length bucketing, so the fused backend routes straight to the
    dispatch fold: same plan, zero scans, the routing counted as policy
    (``fused_routed``), not failure (``fused_fallbacks``)."""
    from repro.core import jdob
    monkeypatch.setattr(jdob, "FUSED_SCAN_MAX_LEVELS", 5)
    fleet = make_fleet(8, PROF, EDGE, beta=(3.0, 20.0), seed=11)
    svc = PlannerService(PROF, EDGE)
    d = optimal_grouping(PROF, fleet, EDGE, service=svc, dp="pareto",
                         dp_backend="dispatch")
    f = optimal_grouping(PROF, fleet, EDGE, service=svc, dp="pareto",
                         dp_backend="fused")
    _assert_same_plan(d, f)
    st_ = svc.stats()
    assert st_.fused_routed == 1 and st_.fused_scans == 0
    assert st_.fused_fallbacks == 0
    # incremental folds route the same way
    state = IncrementalOgState(PROF, fleet, EDGE, service=svc, dp="pareto",
                               dp_backend="fused")
    _assert_same_plan(state.plan(), d)
    assert svc.stats().fused_routed == 2
    # below the crossover the scan still runs
    small = make_fleet(4, PROF, EDGE, beta=(3.0, 20.0), seed=11)
    optimal_grouping(PROF, small, EDGE, service=svc, dp="pareto",
                     dp_backend="fused")
    assert svc.stats().fused_scans == 1


def test_fused_overflow_falls_back_to_dispatch():
    """An init frontier wider than the device buffer cannot be scanned:
    the fused state must fall back to the dispatch fold (counted) and
    still produce the exact plan."""
    fleet = make_fleet(6, PROF, EDGE, beta=(3.0, 20.0), seed=7)
    svc = PlannerService(PROF, EDGE)
    state = IncrementalOgState(PROF, fleet, EDGE, service=svc, dp="pareto",
                               dp_backend="fused")
    state.plan()
    wide = [(float(i), 0.0, 0, 0) for i in range(FUSED_FRONTIER_WIDTH + 1)]
    state._dp = [state._dp[0],
                 [(e, state._dp[0][0][1], sp, si)
                  for (e, tf, sp, si) in wide]]
    # direct probe of the resume guard: a too-wide host row refuses
    from repro.core.jdob import og_plan_fused
    planner = svc.planner()
    res = og_plan_fused(planner, state._sorted_fleet,
                        init_rows=[[(0.0, 0.0, -1, 0)], wide],
                        mode="pareto")
    assert res.overflow


# ---------------------------------------------------------------------------
# Pallas sweep inner backend == jitted core backend (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,seed,t_free", [(4, 0, 0.0), (8, 3, 1e-3),
                                           (12, 1, 0.0), (1, 2, 0.0)])
def test_jdob_sweep_backend_matches_core(M, seed, t_free):
    """The Pallas sweep kernel as the inner group solver: its grid argmin
    picks the same partition as the jitted core, and the winner re-solve
    returns the core's exact Schedule."""
    from repro.kernels import jdob_sweep_schedule
    fleet = make_fleet(M, PROF, EDGE, beta=(3.0, 20.0), seed=seed)
    a = jdob_schedule(PROF, fleet, EDGE, t_free=t_free)
    b = jdob_sweep_schedule(PROF, fleet, EDGE, t_free=t_free,
                            interpret=True)
    assert a.energy == b.energy and a.partition == b.partition
    np.testing.assert_array_equal(a.offload, b.offload)
    np.testing.assert_array_equal(a.per_user_energy, b.per_user_energy)


def test_jdob_sweep_backend_through_planner():
    """The sweep kernel feeds the production planner: routed as an
    ``inner`` through optimal_grouping's sequential fallback, the plan
    equals the jitted-core backend's."""
    from repro.kernels import jdob_sweep_schedule

    def inner(*a, **k):
        return jdob_sweep_schedule(*a, interpret=True, **k)

    fleet = make_fleet(6, PROF, EDGE, beta=(3.0, 20.0), seed=4)
    core = optimal_grouping(PROF, fleet, EDGE, jdob_schedule, service=SVC)
    pallas = optimal_grouping(PROF, fleet, EDGE, inner)
    assert core.energy == pallas.energy
    assert [list(g) for g in core.groups] == \
        [list(g) for g in pallas.groups]


# ---------------------------------------------------------------------------
# the scan's binary64-as-int64 arithmetic is IEEE-exact on any backend
# ---------------------------------------------------------------------------

def _binary64_operands():
    rng = np.random.default_rng(0)
    n = 20000
    a = np.concatenate([
        rng.random(n) * 3, rng.random(n),
        np.exp(rng.uniform(-700, 700, n)), rng.random(n) * 1e-310,
        1.0 + rng.integers(0, 2 ** 20, n) * 2.0 ** -52,
        [0.0, 0.0, np.inf, 1.7e308, 5e-324]])
    b = np.concatenate([
        rng.random(n) * 3,
        rng.random(n) * rng.choice([1e-16, 2.0 ** -53, 2.0 ** -52, 1.0], n),
        np.exp(rng.uniform(-700, 700, n)), rng.random(n) * 1e-310,
        np.full(n, 2.0 ** -53),                       # exact halfway ties
        [0.0, 2.5, 1.0, 1.7e308, 5e-324]])
    return a, b


@pytest.mark.parametrize("op", ["add", "f32_to_f64", "f64_to_f32"])
def test_binary64_bit_arithmetic_matches_ieee(op):
    import jax
    import jax.numpy as jnp
    from repro.core.jdob import (_f32_to_f64_bits, _f64_add_bits,
                                 _f64_bits_to_f32)
    a, b = _binary64_operands()
    bits = lambda x: jnp.asarray(x.view(np.int64))
    with jax.enable_x64(True), np.errstate(over="ignore"):
        if op == "add":
            got = np.asarray(jax.jit(_f64_add_bits)(bits(a), bits(b)))
            want = (a + b).view(np.int64)
        elif op == "f32_to_f64":
            x = np.concatenate([a, b]).astype(np.float32)
            x = np.concatenate([x, [1e-45, 3e-39, np.float32(np.inf)]]
                               ).astype(np.float32)
            got = np.asarray(jax.jit(_f32_to_f64_bits)(jnp.asarray(x)))
            want = x.astype(np.float64).view(np.int64)
        else:
            x = np.concatenate([a, b, [3e-39, 1e-46, 3.4028235677973366e38,
                                       3.5e38]])
            got = np.asarray(jax.jit(_f64_bits_to_f32)(bits(x))
                             ).view(np.int32)
            want = x.astype(np.float32).view(np.int32)
    np.testing.assert_array_equal(got, want)

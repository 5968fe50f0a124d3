"""Online scheduler (paper future work): correctness and dominance."""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import (FlushEvent, OnlineArrival, OnlineScheduler,
                        all_local_energy, make_edge_profile, make_fleet,
                        mobilenet_v2_profile, oracle_bound, poisson_arrivals,
                        simulate_online, simulate_online_reference)

PROF = mobilenet_v2_profile()
EDGE = make_edge_profile(PROF)

POLICIES = ("immediate", "window", "slack", "lastcall")


def _setup(M=8, beta=20.0, rate=100.0, seed=0):
    fleet = make_fleet(M, PROF, EDGE, beta=beta, seed=seed)
    arrivals = poisson_arrivals(M, rate, fleet, seed=seed)
    return fleet, arrivals


@pytest.mark.parametrize("policy", ["immediate", "window", "slack",
                                    "lastcall"])
def test_no_deadline_violations_and_all_served(policy):
    fleet, arrivals = _setup()
    r = simulate_online(arrivals, PROF, fleet, EDGE, policy=policy,
                        window=0.02)
    assert r.violations == 0
    assert np.all(r.per_user_energy > 0)          # everyone served
    assert sum(r.batch_sizes) <= fleet.M


@pytest.mark.parametrize("rate", [10.0, 100.0, 1000.0])
def test_online_never_beats_oracle(rate):
    fleet, arrivals = _setup(rate=rate)
    orc = oracle_bound(arrivals, PROF, fleet, EDGE)
    for policy in ("immediate", "window", "slack"):
        r = simulate_online(arrivals, PROF, fleet, EDGE, policy=policy,
                            window=0.02)
        assert r.energy >= orc * (1 - 1e-6), policy


@pytest.mark.parametrize("rate", [10.0, 100.0, 1000.0])
def test_slack_policy_beats_lc_and_tracks_oracle(rate):
    fleet, arrivals = _setup(rate=rate)
    lc = all_local_energy(arrivals, PROF, fleet, EDGE)
    orc = oracle_bound(arrivals, PROF, fleet, EDGE)
    r = simulate_online(arrivals, PROF, fleet, EDGE, policy="slack")
    assert r.energy < lc                          # online still saves energy
    assert r.energy <= orc * 1.10                 # within 10% of clairvoyant


def test_batches_grow_with_arrival_rate():
    fleet_lo, arr_lo = _setup(rate=5.0, seed=3)
    fleet_hi, arr_hi = _setup(rate=2000.0, seed=3)
    r_lo = simulate_online(arr_lo, PROF, fleet_lo, EDGE, policy="slack")
    r_hi = simulate_online(arr_hi, PROF, fleet_hi, EDGE, policy="slack")
    assert max(r_hi.batch_sizes) > max(r_lo.batch_sizes)


def test_gpu_occupancy_threads_between_flushes():
    """Two dense bursts: the second flush must respect the GPU time the
    first one booked (no overlapping batches)."""
    fleet, _ = _setup(M=8)
    from repro.core import OnlineArrival
    arrivals = ([OnlineArrival(m, 0.0, float(fleet.deadline[m]))
                 for m in range(4)]
                + [OnlineArrival(m, 1e-4, float(fleet.deadline[m]))
                   for m in range(4, 8)])
    r = simulate_online(arrivals, PROF, fleet, EDGE, policy="immediate")
    assert r.violations == 0
    assert len(r.flush_times) >= 2


@settings(max_examples=20, deadline=None)
@given(M=st.integers(2, 10), rate=st.floats(5.0, 2000.0),
       beta=st.floats(8.0, 40.0), seed=st.integers(0, 999))
def test_property_online_feasible_any_traffic(M, rate, beta, seed):
    fleet = make_fleet(M, PROF, EDGE, beta=beta, seed=seed)
    arrivals = poisson_arrivals(M, rate, fleet, seed=seed)
    r = simulate_online(arrivals, PROF, fleet, EDGE, policy="slack")
    assert r.violations == 0
    assert r.energy >= oracle_bound(arrivals, PROF, fleet, EDGE) * (1 - 1e-6)


# ---------------------------------------------------------------------------
# event-driven scheduler: parity with the seed flush-loop simulator
# ---------------------------------------------------------------------------

def _assert_same_result(a, b):
    assert a.energy == b.energy
    assert a.n_flushes == b.n_flushes
    assert a.batch_sizes == b.batch_sizes
    assert a.violations == b.violations
    assert a.flush_times == b.flush_times
    np.testing.assert_array_equal(a.per_user_energy, b.per_user_energy)


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("rate,seed", [(10.0, 0), (100.0, 0), (1000.0, 0),
                                       (100.0, 3), (2000.0, 7)])
def test_scheduler_bit_identical_to_reference(policy, rate, seed):
    """The event-driven scheduler reproduces the seed simulator bit for
    bit on the seed scenarios, for every policy."""
    fleet, arrivals = _setup(rate=rate, seed=seed)
    new = simulate_online(arrivals, PROF, fleet, EDGE, policy=policy,
                          window=0.02)
    ref = simulate_online_reference(arrivals, PROF, fleet, EDGE,
                                    policy=policy, window=0.02)
    _assert_same_result(new, ref)


def test_scheduler_bit_identical_simultaneous_bursts():
    """Equal arrival times (burst traffic) keep submission order and stay
    bit-identical to the reference's stable sort."""
    fleet, _ = _setup(M=8)
    arrivals = ([OnlineArrival(m, 0.0, float(fleet.deadline[m]))
                 for m in range(4)]
                + [OnlineArrival(m, 1e-4, float(fleet.deadline[m]))
                   for m in range(4, 8)])
    for policy in POLICIES:
        new = simulate_online(arrivals, PROF, fleet, EDGE, policy=policy,
                              window=0.02)
        ref = simulate_online_reference(arrivals, PROF, fleet, EDGE,
                                        policy=policy, window=0.02)
        _assert_same_result(new, ref)


def test_scheduler_incremental_submission_and_events():
    """The live-server regime: submit out of order, step event by event;
    flush events carry the planned schedule and book the GPU (Eq. 22)."""
    fleet, arrivals = _setup(M=8, rate=100.0)
    sched = OnlineScheduler(PROF, fleet, EDGE, policy="slack")
    for a in reversed(arrivals):            # out-of-order submission
        sched.submit(a)
    flushes, gpu_free_seen = [], []
    sched.on_flush = flushes.append
    sched.on_gpu_free = lambda ev: gpu_free_seen.append(ev.time)
    events = []
    while True:
        ev = sched.step()
        if ev is None:
            break
        events.append(ev)
    r = sched.result()
    _assert_same_result(r, simulate_online(arrivals, PROF, fleet, EDGE,
                                           policy="slack"))
    stepped = [ev for ev in events if isinstance(ev, FlushEvent)]
    assert all(a is b for a, b in zip(stepped, flushes))
    assert len(stepped) == len(flushes)
    assert len(flushes) == r.n_flushes
    for ev in flushes:
        assert ev.schedule.energy > 0
        assert ev.gpu_free >= ev.time       # booking never precedes flush
    # every offloading flush frees the GPU exactly once
    assert gpu_free_seen == sorted(ev.gpu_free for ev in flushes
                                   if ev.schedule.offload.any())
    # the clock is monotone over flush events
    assert r.flush_times == sorted(r.flush_times)


def test_bounded_flush_history_keeps_aggregates_complete():
    """history=N caps the rich FlushEvent list (live-server memory bound)
    while the OnlineResult aggregates still cover every flush."""
    fleet, arrivals = _setup(M=8, rate=10.0)     # sparse → many flushes
    ref = simulate_online(arrivals, PROF, fleet, EDGE, policy="immediate")
    assert ref.n_flushes > 2
    sched = OnlineScheduler(PROF, fleet, EDGE, policy="immediate",
                            history=2)
    sched.submit_many(arrivals)
    r = sched.run()
    assert len(sched.flushes) == 2               # capped
    _assert_same_result(r, ref)                  # aggregates complete


def test_submit_rejects_arrivals_behind_the_clock():
    """Once the clock has advanced, submitting an earlier arrival raises —
    the event heap must never rewind past flush decisions already taken."""
    fleet, arrivals = _setup(M=4, rate=50.0)
    sched = OnlineScheduler(PROF, fleet, EDGE, policy="immediate")
    sched.submit_many(arrivals)
    while sched.step() is not None:
        pass
    assert sched.now > 0
    with pytest.raises(ValueError, match="causal"):
        sched.submit(OnlineArrival(0, sched.now * 0.5,
                                   float(fleet.deadline[0])))
    # an arrival exactly AT the clock (and any later one) is fine
    sched.submit(OnlineArrival(0, sched.now, float(fleet.deadline[0])))
    sched.submit(OnlineArrival(1, sched.now + 1.0, float(fleet.deadline[1])))
    r = sched.run()
    assert r.n_flushes >= 3
    assert r.flush_times == sorted(r.flush_times)   # clock stayed monotone


def test_all_local_flush_reports_sane_gpu_free():
    """A flush that offloads nothing must not report a GPU-free time in
    the past (the booking horizon is untouched, but the event clamps to
    the flush time)."""
    fleet, _ = _setup(M=2)
    # deadline below l_min forces the all-local fallback plan
    tight = float(fleet.zeta[0] * PROF.v()[-1] / fleet.f_max[0]) * 0.5
    from repro.core import OnlineArrival
    sched = OnlineScheduler(PROF, fleet, EDGE, policy="immediate")
    sched.submit(OnlineArrival(0, 1.0, tight))
    r = sched.run()
    assert len(sched.flushes) == 1
    ev = sched.flushes[0]
    assert not ev.schedule.offload.any()
    assert ev.gpu_free >= ev.time
    assert r.violations == 1                    # past its point of no return


def test_scheduler_threads_gpu_occupancy_between_flushes():
    fleet, _ = _setup(M=8)
    arrivals = ([OnlineArrival(m, 0.0, float(fleet.deadline[m]))
                 for m in range(4)]
                + [OnlineArrival(m, 1e-4, float(fleet.deadline[m]))
                   for m in range(4, 8)])
    sched = OnlineScheduler(PROF, fleet, EDGE, policy="immediate")
    sched.submit_many(arrivals)
    r = sched.run()
    assert r.violations == 0
    offloading = [ev for ev in sched.flushes if ev.schedule.offload.any()]
    for prev, nxt in zip(offloading, offloading[1:]):
        # the later flush planned with the GPU busy until prev.gpu_free
        assert nxt.gpu_free >= prev.gpu_free


# ---------------------------------------------------------------------------
# property tests: violations and energy accounting
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(M=st.integers(2, 9), rate=st.floats(5.0, 2000.0),
       beta=st.floats(2.0, 40.0), seed=st.integers(0, 999),
       policy=st.sampled_from(["slack", "window", "immediate"]))
def test_property_zero_violations_with_budget_above_lmin(M, rate, beta,
                                                         seed, policy):
    """Whenever every arrival's remaining budget at its flush exceeds
    l_min, the policy reports zero violations.  β ≥ 2 keeps the slack
    policy's retained budget (keep_frac·T_m = 0.7(1+β)·l_min ≥ 2.1·l_min)
    and the window bound (Δ = 0 here) above the point of no return, so
    all three non-lastcall policies must be violation-free."""
    fleet = make_fleet(M, PROF, EDGE, beta=beta, seed=seed)
    arrivals = poisson_arrivals(M, rate, fleet, seed=seed)
    r = simulate_online(arrivals, PROF, fleet, EDGE, policy=policy,
                        window=0.0)
    assert r.violations == 0
    assert np.all(r.per_user_energy > 0)


@settings(max_examples=15, deadline=None)
@given(M=st.integers(2, 9), rate=st.floats(5.0, 2000.0),
       beta=st.floats(2.0, 40.0), seed=st.integers(0, 999),
       policy=st.sampled_from(["slack", "window", "immediate", "lastcall"]))
def test_property_per_user_energy_sums_to_total(M, rate, beta, seed, policy):
    """Per-user energies account for the whole reported total, and the
    total equals the sum of the flushed schedules' energies (device +
    uplink + edge, edge attributed evenly across each batch)."""
    fleet = make_fleet(M, PROF, EDGE, beta=beta, seed=seed)
    arrivals = poisson_arrivals(M, rate, fleet, seed=seed)
    sched = OnlineScheduler(PROF, fleet, EDGE, policy=policy, window=0.01)
    sched.submit_many(arrivals)
    r = sched.run()
    assert r.energy == float(r.per_user_energy.sum())
    total_from_flushes = sum(ev.schedule.energy for ev in sched.flushes)
    np.testing.assert_allclose(r.energy, total_from_flushes, rtol=1e-9)


@pytest.mark.parametrize("rate", [5.0, 282.0])
def test_per_user_energy_adds_up_to_float32_grid_totals(rate):
    """An offloaded flush's energy comes from the float32 planning grid;
    its per-user terms plus the edge share must still add up to it, not
    to the float64 re-sum of the float32 per-user terms (which differs by
    float32 rounding, ~2e-8 relative, on these fleets)."""
    fleet = make_fleet(2, PROF, EDGE, beta=3.0, seed=0)
    sched = OnlineScheduler(PROF, fleet, EDGE, policy="slack", window=0.01)
    sched.submit_many(poisson_arrivals(2, rate, fleet, seed=0))
    r = sched.run()
    assert any(ev.schedule.offload.any() for ev in sched.flushes)
    total_from_flushes = sum(ev.schedule.energy for ev in sched.flushes)
    np.testing.assert_allclose(r.energy, total_from_flushes, rtol=1e-9)


# ---------------------------------------------------------------------------
# offline bounds: subsetting fix
# ---------------------------------------------------------------------------

def test_oracle_bound_subsets_by_present_users():
    """Bounds over a partial trace use the present users' own device
    constants, not the first k rows of the fleet."""
    fleet, _ = _setup(M=8)
    present = [5, 2, 7]
    arrivals = [OnlineArrival(u, 0.01 * k, float(fleet.deadline[u]))
                for k, u in enumerate(present)]
    orc = oracle_bound(arrivals, PROF, fleet, EDGE)
    lc = all_local_energy(arrivals, PROF, fleet, EDGE)
    assert 0 < orc <= lc
    # independently computed on the explicit sub-fleet
    import dataclasses
    sub = fleet.subset(np.array(sorted(present)))
    sub = dataclasses.replace(sub, deadline=np.array(
        [fleet.deadline[u] for u in sorted(present)]))
    from repro.core import local_computing
    assert lc == local_computing(PROF, sub, EDGE).energy


def test_oracle_bound_rejects_duplicate_users():
    fleet, _ = _setup(M=4)
    arrivals = [OnlineArrival(1, 0.0, float(fleet.deadline[1])),
                OnlineArrival(1, 0.01, float(fleet.deadline[1]))]
    with pytest.raises(AssertionError, match="duplicate"):
        oracle_bound(arrivals, PROF, fleet, EDGE)
    with pytest.raises(AssertionError, match="duplicate"):
        all_local_energy(arrivals, PROF, fleet, EDGE)

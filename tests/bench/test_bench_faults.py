"""The harness run end to end on the CPU at small sizes, with the chip
check skipped: a sound run is correct, and each fault the cell can have,
planted in the timed path, makes ``correct`` come out false."""
import benchtest_util
import numpy as np
import pytest

from bench import run

SEED = 2 ** 35 + 17
ONLINE = dict(devices=200)


def _correct(name, seconds=0.5, devices=None, **over):
    spec, config, mix = benchtest_util.cell(name, devices=devices)
    if name.startswith("mnv2-10k"):
        mix.update(devices=200, rate_hz=2000.0, block=1024)
    if name.startswith("minitron"):
        mix.update(devices=4)
    out = run.run_cell(spec, name, SEED, seconds, False, require_tpu=False,
                       config=config, mix=mix)
    assert out["attempted"] > 0
    return out["correct"], out["compared"]


# ---- the served cells --------------------------------------------------------
def _state_unchanged(monkeypatch):
    import repro.serving.engine as engine
    monkeypatch.setattr(engine, "_layer_step",
                        lambda spec, ctx, stacked, r, h, vision: h)


def _half_the_batch_left_out(monkeypatch):
    import repro.serving.server as server
    orig = server.run_partitioned

    def half(executor, vocab, requests, sched):
        out = orig(executor, vocab, requests, sched)
        out[1::2] = 0.0
        return out
    monkeypatch.setattr(server, "run_partitioned", half)


def _logit_altered(monkeypatch):
    import repro.serving.server as server
    orig = server.run_partitioned

    def nudge(executor, vocab, requests, sched):
        out = orig(executor, vocab, requests, sched)
        out[:, 3, 7] += 1e-3 * np.abs(out).max()
        return out
    monkeypatch.setattr(server, "run_partitioned", nudge)


def test_served_sound_run_is_correct():
    ok, nums = _correct("minitron4b.busy")
    assert ok, nums


def test_served_layer_returning_its_state_unchanged(monkeypatch):
    _state_unchanged(monkeypatch)
    ok, nums = _correct("minitron4b.busy")
    assert not ok and nums["logit_err"]["value"] > 0.1


def test_served_half_the_batch_left_out(monkeypatch):
    _half_the_batch_left_out(monkeypatch)
    ok, nums = _correct("minitron4b.busy", seconds=1.0)
    assert not ok and nums["logit_err"]["value"] >= 0.5


def test_served_logit_altered_where_produced(monkeypatch):
    _logit_altered(monkeypatch)
    ok, nums = _correct("minitron4b.busy")
    assert not ok and nums["logit_err"]["value"] > 1e-4


@pytest.mark.parametrize("fault,least", [
    (None, None), (_state_unchanged, 0.1), (_half_the_batch_left_out, 0.5),
    (_logit_altered, 1e-4)])
def test_sparse_cell_is_correct_only_when_sound(fault, least, monkeypatch):
    """The small flushes of ``minitron4b.sparse``: the sound run is
    correct, and each fault of the served path fails it."""
    if fault is not None:
        fault(monkeypatch)
    ok, nums = _correct("minitron4b.sparse", seconds=2.0)
    if fault is None:
        assert ok, nums
    else:
        assert not ok and nums["logit_err"]["value"] >= least, nums


# ---- the planner cells -------------------------------------------------------
@pytest.fixture
def altered_plans(monkeypatch):
    """Every plan the planner produces runs its devices 0.5% slower."""
    from repro.core.jdob import BatchedPlanner
    orig = BatchedPlanner._reconstruct

    def slower(self, *a, **kw):
        s = orig(self, *a, **kw)
        s.f_device = s.f_device * 0.995
        return s
    monkeypatch.setattr(BatchedPlanner, "_reconstruct", slower)


def test_online_sound_run_is_correct():
    ok, nums = _correct("mnv2-10k.online")
    assert ok, nums


def test_online_plan_altered_where_produced(altered_plans):
    ok, nums = _correct("mnv2-10k.online")
    assert not ok and nums["deadline_excess"]["value"] > 1e-3


def test_online_half_the_batch_left_out(monkeypatch):
    from repro.core import OnlineScheduler
    orig = OnlineScheduler._flush

    def half(self, now):
        self._queue = self._queue[:max(1, len(self._queue) // 2)]
        return orig(self, now)
    monkeypatch.setattr(OnlineScheduler, "_flush", half)
    ok, nums = _correct("mnv2-10k.online")
    assert not ok and nums["flush_mismatch"]["value"] >= 1


def test_waves_sound_run_is_correct():
    ok, nums = _correct("mnv2-40.og")
    assert ok, nums


def test_waves_plan_altered_where_produced(altered_plans):
    ok, nums = _correct("mnv2-40.og")
    assert not ok and nums["deadline_excess"]["value"] > 1e-3


def test_waves_half_the_batch_left_out(monkeypatch):
    from repro.core import PlannerService
    orig = PlannerService.plan_fleet

    def half(self, fleet, *a, **kw):
        return orig(self, fleet.subset(np.arange(fleet.M // 2)), *a, **kw)
    monkeypatch.setattr(PlannerService, "plan_fleet", half)
    ok, nums = _correct("mnv2-40.og")
    assert not ok and nums["group_mismatch"]["value"] >= 20

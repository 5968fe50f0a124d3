"""The trace reduction on a small synthetic trace: busy union, idle share,
program time, and idle gaps attributed to the innermost host span."""
import benchtest_util  # noqa: F401
import pytest

from bench import trace as tr


def _summary():
    ev = tr.rows_to_events
    device = {"/device:TPU:0": {
        "ops": ev([("fusion.1", 1.0, 2.0), ("dot.2", 1.5, 3.0),
                   ("fusion.1", 5.0, 6.0), ("copy.3", 9.5, 11.0)]),
        "modules": ev([("jit__layer_step(7)", 1.0, 3.0),
                       ("jit__head_step(2)", 5.0, 6.0)])}}
    host = ev([("bench.window", 0.0, 10.0), ("bench.drain", 0.6, 8.0),
               ("bench.exec", 4.0, 7.0), ("bench.submit", 8.0, 9.9)])
    return tr.summarize(device, host)


def test_busy_union_and_idle_share():
    s = _summary()
    # [1, 3] ∪ [5, 6] ∪ [9.5, 10] (clipped to the window) = 3.5 s
    assert s.window_s == pytest.approx(10.0)
    assert s.busy_s == pytest.approx(3.5)
    assert s.devices == 1
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.65)


def test_op_and_program_times():
    s = _summary()
    assert s.op_s["fusion.1"] == pytest.approx(2.0)
    assert s.op_s["copy.3"] == pytest.approx(0.5)       # clipped at 10
    assert s.module_time("_layer_step") == pytest.approx(2.0)
    assert s.module_time("_head_step") == pytest.approx(1.0)
    ops = s.breakdown()["device_ops"]
    assert ops[0] == ["fusion.1", pytest.approx(2.0)]


def test_gaps_go_to_the_innermost_host_span():
    s = _summary()
    gaps = dict((round(v, 6), k) for k, v in s.gaps)
    # idle [0, 1]: its middle 0.5 is before any span; [3, 5]: the middle
    # 4.0 is in drain and in exec, which started later; [6, 9.5]: the
    # middle 7.75 is in drain only
    assert gaps[1.0] == "host.other"
    assert gaps[2.0] == "bench.exec"
    assert gaps[3.5] == "bench.drain"
    assert [k for k, _ in s.gaps][0] == "bench.drain"   # longest first
    assert sum(v for _, v in s.gaps) == pytest.approx(10.0 - 3.5)


def test_union_of_nested_and_touching_intervals():
    assert tr.union_length([(0, 4), (1, 2), (4, 5), (7, 8)]) == 6
    assert tr.idle_gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.summarize({}, tr.rows_to_events([("bench.exec", 0, 1)]))

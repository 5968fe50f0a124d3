"""The program-span reduction on small synthetic traces: flushes split by
the harness's flush spans, idle gaps given to the innermost span of
either kind, the ``bytes`` argument, and no reading without program
spans; and ``load`` on a real CPU capture."""
import benchtest_util  # noqa: F401
import pytest

from bench import spans as sp
from bench import trace as tr

MAIN = ("/host:CPU", 0)


def _spans(rows, thread=MAIN):
    """[(name, start, end[, args])] -> [Span] on one thread."""
    return [sp.Span(r[0], float(r[1]), float(r[2]), thread,
                    r[3] if len(r) > 3 else {}) for r in rows]


def _served():
    """Two served flushes in a 10 s window, one profiled flush before it."""
    busy = [(1.0, 2.0), (2.2, 2.6), (6.0, 6.5)]
    spans = _spans([
        ("bench.drain", -3.0, -1.0), ("repro.exec.to_host", -2.5, -2.0,
                                      {"bytes": 999}),
        ("bench.window", 0.0, 10.0),
        ("bench.drain", 0.5, 4.0),
        ("repro.loop.drain", 0.5, 0.8),
        ("repro.loop.flush", 0.8, 4.0),
        ("repro.plan.dispatch", 0.8, 0.9), ("repro.plan.fetch", 0.9, 1.0),
        ("repro.plan.reconstruct", 1.0, 1.1), ("repro.loop.book", 1.1, 1.2),
        ("bench.exec", 1.2, 4.0),
        ("repro.exec.prepare", 1.2, 1.3), ("repro.exec.blocks", 1.3, 1.5,
                                           {"lo": 0, "hi": 2}),
        ("repro.exec.head", 1.5, 1.6), ("repro.exec.wait", 1.6, 2.6),
        ("repro.exec.to_host", 2.6, 3.6, {"bytes": 4_000_000_000}),
        ("repro.exec.scatter", 3.6, 4.0),
        ("bench.submit", 4.0, 4.5),
        ("bench.drain", 4.5, 9.0),
        ("repro.loop.drain", 4.5, 4.7),
        ("repro.loop.flush", 4.7, 9.0),
        ("repro.plan.dispatch", 4.7, 4.8), ("repro.plan.fetch", 4.8, 5.0),
        ("repro.plan.reconstruct", 5.0, 5.2),
        ("repro.exec.wait", 5.5, 6.5),
        ("repro.exec.to_host", 6.5, 7.5, {"bytes": 2_000_000_000}),
        ("repro.exec.scatter", 7.5, 9.0),
    ])
    return busy, spans


def test_program_span_inside_harness_span_takes_the_gap():
    busy, spans = _served()
    view = sp.idle(busy, spans)
    names = dict((round(s, 6), n) for n, s in view["longest"])
    # [2.6, 6.0]: the middle 4.3 is in bench.submit only
    assert names[3.4] == "bench.submit"
    # [6.5, 10]: the middle 8.25 is in bench.drain, repro.loop.flush and
    # repro.exec.scatter, which started last
    assert names[3.5] == "repro.exec.scatter"
    # [0, 1]: bench.drain and repro.loop.drain both start at its middle
    # 0.5; the program span opened inside the harness span
    assert names[1.0] == "repro.loop.drain"
    assert sum(s for _, s in view["longest"]) == pytest.approx(
        10.0 - 1.9)
    # split at span edges, each moment of idle time to its innermost span
    by = view["idle_by_name"]
    assert by["repro.exec.to_host"] == pytest.approx(2.0)
    assert by["repro.exec.scatter"] == pytest.approx(1.9)
    assert by["repro.exec.wait"] == pytest.approx(0.7)
    assert by["host.other"] == pytest.approx(1.5)
    assert sum(by.values()) == pytest.approx(view["idle_s"])


def test_gaps_outside_program_spans_keep_their_harness_names():
    busy, spans = _served()
    harness = [s for s in spans if not s.name.startswith("repro.")]
    view = sp.idle(busy, harness)
    want = tr.summarize({"/device:TPU:0": {
        "ops": tr.rows_to_events([("op", s, e) for s, e in busy]),
        "modules": []}}, [tr.Event(s.name, s.start, s.end) for s in harness])
    assert sorted((n, round(s, 9)) for n, s in view["longest"]) == \
        sorted((n, round(s, 9)) for n, s in want.gaps)
    assert view["named_share"] == 0.0


def test_named_share_leaves_out_the_outer_spans():
    busy = [(1.0, 2.0), (3.0, 4.0)]
    spans = _spans([("bench.window", 0.0, 6.0), ("bench.plan", 0.0, 5.0),
                    ("repro.og.plan", 0.0, 5.0), ("repro.og.level", 0.0, 5.0),
                    ("repro.og.fold", 2.0, 3.0), ("repro.og.fold", 4.5, 5.0),
                    ("bench.submit", 5.0, 6.0)])
    view = sp.idle(busy, spans)
    # idle inside bench.plan: [0, 1], [2, 3], [4, 5]; of it the folds
    # hold [2, 3] and [4.5, 5], the rest lies in outer spans only; the
    # idle second in bench.submit counts for neither
    assert view["idle_s"] == pytest.approx(4.0)
    assert view["idle_in_harness_s"] == pytest.approx(3.0)
    assert view["named_share"] == pytest.approx(1.5 / 3.0)


def test_intersect_of_interval_lists():
    assert sp.intersect([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2), (3, 4)]
    assert sp.intersect([(0, 1)], [(1, 2)]) == []


def test_flush_totals_group_program_spans_by_harness_flush():
    _, spans = _served()
    fl = sp.flush_totals(spans)
    assert len(fl) == 2                       # the flush before the window
    assert fl[0]["repro.exec.wait"][0] == pytest.approx(1.0)
    assert fl[1]["repro.exec.scatter"][0] == pytest.approx(1.5)
    assert fl[0]["repro.plan.fetch"][2] == 1
    assert sum(c[2] for c in fl[0].values()) == 12
    out = sp.splits(fl)
    assert out["exec_wait_ms.p50"] == pytest.approx(1000.0)
    assert out["plan_wait_ms.p50"] == pytest.approx(150.0)
    assert out["plan_host_ms.p50"] == pytest.approx(
        0.5 * (200.0 + 300.0))
    assert out["loop_drain_ms.p50"] == pytest.approx(250.0)
    # host work of the first: prepare + blocks + head + scatter
    assert out["exec_host_ms.p50"] == pytest.approx(
        0.5 * (800.0 + 1500.0))
    assert out["og_host_ms.p50"] is None


def test_bytes_argument_is_read():
    _, spans = _served()
    fl = sp.flush_totals(spans)
    assert fl[0]["repro.exec.to_host"][1] == 4_000_000_000
    # 6 GB over 2 s of copies in the window (the 999 B before it left out)
    assert sp.splits(fl)["to_host_gbps"] == pytest.approx(3.0)


@pytest.mark.parametrize("name", sorted(sp.SPLITS) + ["to_host_gbps"])
def test_every_split_reads_none_without_program_spans(name):
    _, spans = _served()
    harness = [s for s in spans if not s.name.startswith("repro.")]
    assert sp.splits(sp.flush_totals(harness))[name] is None


def test_holders_pick_the_latest_start_across_threads():
    a = _spans([("bench.drain", 0.0, 10.0), ("repro.loop.flush", 1.0, 9.0)])
    b = _spans([("repro.plan.compile", 2.0, 3.0)], thread=("/host:CPU", 1))
    got = sp.holders(a + b, [0.5, 2.5, 5.0, 11.0])
    assert [g and g.name for g in got] == [
        "bench.drain", "repro.plan.compile", "repro.loop.flush", None]


def test_load_reads_program_spans_and_their_args(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("bench.window"):
            with TraceAnnotation("bench.drain"):
                with TraceAnnotation("repro.exec.to_host", bytes=1234):
                    pass
            with TraceAnnotation("unrelated"):
                pass
    busy, spans = sp.load(str(tmp_path))
    assert busy == []                         # no accelerator plane here
    got = {s.name: s for s in spans}
    assert set(got) == {"bench.window", "bench.drain", "repro.exec.to_host"}
    assert got["repro.exec.to_host"].args == {"bytes": 1234}
    fl = sp.flush_totals(spans)
    assert fl[0]["repro.exec.to_host"][1:] == [1234, 1]

"""The trace reduction on a small synthesized trace."""
import pytest

from bench import tracereduce as T


def trace():
    """A 10 s window on one device: ops busy 1-3 s (a sort from 1 to 2)
    and 2.5-4 s and 6-7 s; host spans cover the gaps 4-6 (materialize)
    and 7-10 (execute), and nothing covers 0-1."""
    ops = [T.Op("sort.1", 1.0, 2.0, "sort"), T.Op("fusion.2", 2.0, 3.0),
           T.Op("fusion.3", 2.5, 4.0), T.Op("fusion.2", 6.0, 7.0),
           T.Op("fusion.9", 11.0, 12.0)]   # after the window: ignored
    spans = [T.Span(T.WINDOW_SPAN, 0.0, 10.0),
             T.Span("bench.pass", 1.0, 10.0),
             T.Span("bench.execute:Q1", 1.0, 4.0),
             T.Span("bench.materialize:Q1", 4.0, 6.0),
             T.Span("bench.execute:Q3", 6.0, 10.0)]
    return ops, spans


def test_busy_union_counts_overlaps_once():
    ops, _ = trace()
    assert T.busy_seconds(ops, 0.0, 10.0) == pytest.approx(4.0)


def test_summary_idle_and_sort_share():
    s = T.summarize(*trace())
    assert s.window_s == pytest.approx(10.0)
    assert s.busy_s == pytest.approx(4.0)
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.6)   # idle share
    assert s.sort_s / s.busy_s == pytest.approx(0.25)        # sort share
    assert s.op_s["fusion.2"] == pytest.approx(2.0)


def test_idle_gaps_by_host_span():
    s = T.summarize(*trace())
    assert s.idle_by_host == pytest.approx(
        {"idle": 1.0, "bench.materialize:Q1": 2.0,
         "bench.execute:Q3": 3.0})
    assert s.breakdown()["idle_gaps"][0] == ["bench.execute:Q3",
                                              pytest.approx(3.0)]


def test_busy_averages_over_devices():
    ops = [T.Op("a", 0.0, 2.0, device=0), T.Op("a", 0.0, 1.0, device=1)]
    assert T.busy_seconds(ops, 0.0, 4.0) == pytest.approx(1.5)


def test_one_window_span_required():
    ops, spans = trace()
    with pytest.raises(ValueError):
        T.summarize(ops, spans[1:])

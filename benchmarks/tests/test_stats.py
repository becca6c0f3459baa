import pytest

import stats


def test_percentile_is_nearest_rank():
    values = [0.1 * i for i in range(1, 21)]          # 0.1 .. 2.0
    assert stats.percentile(values, 50) == pytest.approx(1.0)
    assert stats.percentile(values, 95) == pytest.approx(1.9)
    assert stats.percentile(values, 100) == pytest.approx(2.0)
    assert stats.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_window_counts_a_stall_between_collects():
    # ten collects of 1 s back to back, a 5 s stall, then one of 3 s
    spans = [(float(i), float(i) + 1.0) for i in range(10)]
    spans.append((15.0, 18.0))
    w = stats.window_summary(spans)
    assert w["collects"] == 11
    assert w["window_s"] == pytest.approx(18.0)
    # the rate is over all the time of the window, the stall with it
    assert w["query_s"] == pytest.approx(18.0 / 11)
    assert w["query_p50_s"] == pytest.approx(1.0)
    assert w["query_p95_s"] == pytest.approx(3.0)
    assert w["query_max_s"] == pytest.approx(3.0)


def test_window_with_no_collect_is_an_error():
    with pytest.raises(ValueError):
        stats.window_summary([])


def test_a_stalled_collect_gets_its_line():
    """Six collects of two queries, the fifth stalled: it is reported with
    the watch thread's late wake-up inside it, the others are not."""
    import types

    import run as R
    cell = types.SimpleNamespace(queries=["a", "b"])
    spans = [(0, 1), (1, 3), (3, 4), (4, 6), (6, 9.5), (9.5, 11.5)]
    cpu = [(0, .5), (.5, 1.2), (1.2, 1.7), (1.7, 2.4), (2.4, 3.0), (3.0, 3.7)]
    watch = R.Watch()
    watch.late = [(8.0, 1.9)]
    slow = R.slow_collects(cell, spans, ["a", "b"] * 3, cpu, watch)
    assert [(s["query"], s["collect"]) for s in slow] == [("a", 5)]
    assert slow[0]["seconds"] == pytest.approx(3.5)
    assert slow[0]["median_s"] == pytest.approx(1.0)
    assert slow[0]["watch_thread_late_s"] == pytest.approx(1.9)
    assert slow[0]["process_cpu_s"] == pytest.approx(0.6)
    assert watch.summary()["late_wakeups"] == 1

"""The arithmetic of the end-to-end and per-layer metric readers."""

from __future__ import annotations

import numpy as np
import pytest

from perfbench.loops import Answer, Update
from perfbench.run import Run, load_reader


def _counters(publish_ms, dispatches, query_dispatches):
    return {"publish_ms": publish_ms, "dispatches": dispatches,
            "query_dispatches": query_dispatches}


def _run_with_updates() -> Run:
    run = Run(t0=1.0, window_start=10.0, window_end=20.0)
    run.before = _counters(100.0, 50, 5)
    run.before["query_stats"] = {"batched": 10, "fallback": 2}
    published = [13.0, 16.0, 19.0, 22.5]  # the last one is the partial update
    for k, t in enumerate(published, start=1):
        u = Update("add", 24, published[k - 2] if k > 1 else 10.0, t, k, "done")
        u.counters = _counters(100.0 + 40.0 * k, 50 + 30 * k, 5 + 2 * k)
        run.updates.append(u)
    run.lookups_after = {"query_stats": {"batched": 40, "fallback": 12}}
    run.compiles = [(12.0, "compile"), (15.0, "cache_load")]
    return run


def test_update_ms_counts_the_share_of_the_update_in_flight_at_the_close():
    run = _run_with_updates()
    assert len(run.completed) == 3
    # the fourth update ran from 19.0 to 22.5: 1 s of its 3.5 s in the window
    share = (20.0 - 19.0) / 3.5
    assert load_reader("update_ms")(run) == pytest.approx(10.0 / (3 + share) * 1e3)


def test_update_ms_when_the_stream_ran_out_before_the_close():
    run = _run_with_updates()
    run.updates.pop()
    assert load_reader("update_ms")(run) == pytest.approx((19.0 - 10.0) / 3 * 1e3)


def test_update_ms_of_a_window_inside_one_update():
    run = Run(t0=0.0, window_start=10.0, window_end=20.0)
    run.updates = [Update("add", 24, 10.0, 30.0, 1, "done")]
    assert load_reader("update_ms")(run) == pytest.approx(20.0 * 1e3)


def test_counter_metrics_are_per_update_published_in_the_window():
    run = _run_with_updates()
    assert load_reader("publish_ms_per_update")(run) == pytest.approx(40.0)
    # 30 dispatches per update, 2 of them by lookups
    assert load_reader("dispatches_per_update")(run) == pytest.approx(28.0)
    assert load_reader("compiles_in_window")(run) == 2
    assert load_reader("query_fallback_share")(run) == pytest.approx(100 * 10 / 40)
    assert load_reader("setup_s")(run) == pytest.approx(9.0)


def test_lookup_latency_runs_from_the_due_time_over_all_lookups():
    run = Run(t0=0.0, window_start=0.0, window_end=200.0)
    # due every second; sent late by a second, answered 1..100 ms after due
    for i in range(100):
        due = float(i)
        run.answers.append(Answer(i, due, due + 0.5, due + (i + 1) / 1e3))
    run.answers.append(Answer(100, 150.0, 150.0, None))  # never answered
    lat_ms = np.arange(1, 101, dtype=np.float64)
    assert load_reader("query_p99_ms")(run) == pytest.approx(np.percentile(lat_ms, 99))
    assert load_reader("query_p99_ms")(run) == pytest.approx(99.01)


def test_trace_readers_read_nothing_without_a_trace():
    run = _run_with_updates()
    for name in ("device_ms_per_update", "device_idle_share"):
        assert load_reader(name)(run) is None


def test_a_metric_split_by_cell_is_read_by_its_base_reader():
    run = _run_with_updates()
    for part in ("lookups", "bulk", "a_cell_added_later"):
        assert load_reader(f"update_ms.{part}")(run) == load_reader("update_ms")(run)
        assert (load_reader(f"publish_ms_per_update.{part}")(run)
                == load_reader("publish_ms_per_update")(run))
    with pytest.raises(FileNotFoundError):
        load_reader("no_such_metric.bulk")

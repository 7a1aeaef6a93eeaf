"""Device time per phase of the store, from its spans and the launches."""

from __future__ import annotations

import copy
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench import phases, trace
from perfbench.run import Run, load_reader

MS = 1e6  # the profiler's clock is in nanoseconds
WORKER, READER = 1, 2


def _extract():
    """One second of window on one device: a delete published inside it
    and an add still running at its close, times in ms."""
    spans = [["bench.window", 0, 1000], ["bench.update", 0, 600],
             ["bench.update", 600, 500]]
    store = [["store.begin", 10, 10, WORKER], ["store.delete:prepare", 20, 20, WORKER],
             ["store.delete:seed", 40, 60, WORKER], ["store.delete:wave", 100, 200, WORKER],
             ["store.delete:rederive", 310, 90, WORKER],
             ["store.delete:forward", 400, 100, WORKER], ["store.barrier", 500, 10, WORKER],
             ["store.publish", 510, 50, WORKER], ["store.publish_host", 560, 30, WORKER],
             ["store.begin", 600, 10, WORKER], ["store.add:prepare", 610, 10, WORKER],
             ["store.add:forward", 620, 430, WORKER], ["store.query", 200, 60, READER]]
    launches = [["seed_tombs", 45, 1, WORKER], ["fwave", 105, 1, WORKER],
                ["_bgp_one", 205, 1, READER], ["rplan", 310, 1, WORKER],
                ["fforward", 410, 1, WORKER], ["_publish_snapshot", 515, 1, WORKER],
                ["fforward", 630, 1, WORKER], ["_publish_snapshot", 990, 1, WORKER],
                ["broadcast_in_dim", 620, 1, WORKER]]
    modules = [["jit_fforward(7)", 0, 5], ["jit_seed_tombs(1)", 50, 40],
               ["jit_fwave(2)", 110, 140], ["jit__bgp_one(3)", 255, 3],
               ["jit_rplan(4)", 320, 60], ["jit_fforward(7)", 420, 60],
               ["jit__publish_snapshot(5)", 520, 20], ["jit_fforward(7)", 640, 60]]
    ops = [[0, 5], [50, 40], [110, 90], [210, 40], [255, 3], [320, 60], [420, 60],
           [520, 20], [640, 60]]
    return {
        "devices": {"/device:TPU:0": {
            "ops": [[s * MS, d * MS] for s, d in ops],
            "modules": [[n, s * MS, d * MS] for n, s, d in modules]}},
        "spans": [[n, s * MS, d * MS, 0] for n, s, d in spans],
        "store_spans": [[n, s * MS, d * MS, t] for n, s, d, t in store],
        "launches": [[n, s * MS, d * MS, t] for n, s, d, t in launches],
    }


def test_device_time_goes_to_the_phase_that_launched_it():
    r = phases.reduce(_extract())
    assert r["updates_traced"] == 1
    # the fforward running at the trace's start was launched before it, and
    # the last publication's program ran after the trace: one of each left
    assert r["unmatched"] == {"fforward": 1, "_publish_snapshot": 1}
    assert r["misaligned"] == []
    # only programs launched inside the published delete count; the
    # lookup's matcher ran inside it too, from the reader's span
    assert r["phase_device_s_per_update"] == pytest.approx({
        "delete:seed": 0.040, "delete:wave": 0.130, "delete:rederive": 0.060,
        "delete:forward": 0.060, "publish": 0.020, "query": 0.003})
    assert r["program_device_s_per_update"]["delete:wave fwave"] == pytest.approx(0.130)


def test_idle_time_under_the_workers_spans_and_their_cover():
    r = phases.reduce(_extract())
    # worker spans cover [10, 300] and [310, 590] of the delete: 570 ms, of
    # which the device was busy 40 + 90 + 40 + 3 + 60 + 60 + 20 = 313 ms
    assert r["maint_idle_s_per_update"] == pytest.approx(0.257)
    # the add never reached its publication inside the trace
    assert r["updates_spanned"] == 1
    assert r["span_cover_min"] == pytest.approx(570 / 580)


def test_idle_gaps_are_labelled_by_the_store_spans_too():
    r = phases.reduce(_extract())
    # the longest gap is [700, 1000], under the add's forward phase
    assert r["idle_gaps"][0] == ["bench.update+store.add:forward", pytest.approx(0.3)]
    assert r["longest_launches"][0][0] in {"seed_tombs", "fwave", "_bgp_one"}


def test_more_than_one_unmatched_launch_reads_none():
    ex = _extract()
    ex["launches"].append(["_publish_snapshot", 995 * MS, 1 * MS, WORKER])
    r = phases.reduce(ex)
    assert r["unmatched"]["_publish_snapshot"] == 2
    assert r["phase_device_s_per_update"] is None
    assert r["program_device_s_per_update"] is None
    # what does not rest on the pairing is still read
    assert r["maint_idle_s_per_update"] == pytest.approx(0.257)


def test_an_execution_before_its_launch_reads_none():
    ex = _extract()
    for launch in ex["launches"]:
        if launch[0] == "seed_tombs":
            launch[1] = 50 * MS + 2 * phases.SKEW_NS
    r = phases.reduce(ex)
    assert r["misaligned"] == ["seed_tombs"]
    assert r["phase_device_s_per_update"] is None


def test_clock_skew_within_its_bound_is_accepted():
    ex = _extract()
    for launch in ex["launches"]:
        if launch[0] == "seed_tombs":
            launch[1] = 50 * MS + phases.SKEW_NS / 2
    r = phases.reduce(ex)
    assert r["misaligned"] == []
    assert r["phase_device_s_per_update"]["delete:seed"] == pytest.approx(0.040)


def test_nothing_to_read_without_store_spans_a_window_or_a_device():
    ex = _extract()
    assert phases.reduce({**ex, "store_spans": []}) is None
    assert phases.reduce({**ex, "devices": {}}) is None
    assert phases.reduce({**ex, "spans": ex["spans"][1:]}) is None


def _event(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def test_extract_keeps_the_outermost_event_of_each_call():
    host = SimpleNamespace(name="/host:CPU", lines=[
        SimpleNamespace(name="python3", events=[]),
        SimpleNamespace(name="python3", events=[
            _event("store.delete:wave", 0, 100),
            _event("PjitFunction(fwave)", 10, 50),
            _event("PjitFunction(fwave)", 11, 48),   # the same call
            _event("PjitFunction(add)", 20, 5),      # traced inside it
            _event("PjitFunction(fwave)", 70, 5),
            _event("bench.update", 0, 200),
        ]),
    ])
    device = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Modules", events=[_event("jit_fwave(1)", 12, 3)])])
    ex = phases.extract(SimpleNamespace(planes=[device, host]))
    assert ex["store_spans"] == [["store.delete:wave", 0, 100, 1]]
    assert ex["launches"] == [["fwave", 10, 50, 1], ["fwave", 70, 5, 1]]


RECORDED = Path(__file__).parent / "data" / "opencyc_x8_update.trace.json.gz"


def test_recorded_trace_reads_the_same_with_the_phase_keys_beside_it():
    """A trace of a program without ``store.*`` spans (the recorded one
    predates them) has no phases to read, and the keys that ``extract``
    adds leave every key of ``trace.reduce`` as it was."""
    ex = trace.load_extract(str(RECORDED))
    before = trace.reduce(copy.deepcopy(ex), trace.layer_map())
    ex.update({"store_spans": [], "launches": [["_unknown", 0.0, 1.0, 0]]})
    assert phases.reduce(ex) is None
    assert trace.reduce(ex, trace.layer_map()) == before


def _lookups_run(before: dict, after: dict) -> Run:
    run = Run(t0=0.0, window_start=1.0, window_end=2.0)
    run.before = {"query_stats": before}
    run.lookups_after = {"query_stats": after}
    return run


def test_query_device_wait_share():
    read = load_reader("query_device_wait_share")
    run = _lookups_run({"batched": 4, "device_wait_ms": 10.0, "wall_ms": 40.0},
                       {"batched": 9, "device_wait_ms": 70.0, "wall_ms": 120.0})
    assert read(run) == pytest.approx(100 * 60 / 80)
    # no lookup answered in the window
    assert read(_lookups_run({"wall_ms": 5.0, "device_wait_ms": 1.0},
                             {"wall_ms": 5.0, "device_wait_ms": 1.0})) is None


def test_query_device_wait_share_reads_nothing_where_there_is_nothing():
    read = load_reader("query_device_wait_share")
    assert read(Run(t0=0.0)) is None
    # a store that does not time its lookups
    assert read(_lookups_run({"batched": 1}, {"batched": 5})) is None

"""The reduction from a device trace to metrics."""

from __future__ import annotations

from pathlib import Path

import pytest

from perfbench import trace

LAYERS = {"query": ["_bgp_one"], "serving": ["_publish_snapshot"],
          "unnamed": ["_unknown"]}


def _extract():
    """A window of 100 ns on one device, in nanoseconds."""
    return {
        "devices": {"/device:TPU:0": {
            "ops": [[5, 10], [10, 20], [40, 10], [70, 5], [95, 20]],
            "modules": [["jit__unknown(1)", 5, 25], ["jit__publish_snapshot(2)", 40, 10],
                        ["jit__bgp_one(3)", 70, 5], ["jit_fused_forward_rounds", 95, 20]],
        }},
        "spans": [
            ["bench.window", 0, 100, "main"],
            ["bench.update", 0, 60, "main"], ["bench.wait_publish", 1, 59, "main"],
            ["bench.update", 60, 50, "main"], ["bench.wait_publish", 61, 49, "main"],
            ["bench.lookup_answer", 50, 25, "reader"],
        ],
    }


def test_busy_idle_and_program_time_inside_the_window():
    r = trace.reduce(_extract(), LAYERS)
    # ops union inside [0, 100]: [5, 30], [40, 50], [70, 75], [95, 100]
    assert r["busy_s"] == pytest.approx(45e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["idle_share"] == pytest.approx(0.55)
    # fused_forward_rounds is listed under no layer, so it is "other"
    assert r["layer_device_s"] == pytest.approx(
        {"unnamed": 25e-9, "other": 5e-9, "serving": 10e-9, "query": 5e-9})
    assert r["device_ops"][0] == ["_unknown [unnamed]", pytest.approx(25e-9)]


def test_maintenance_time_is_per_update_that_ended_in_the_window():
    """Device busy time inside each update, whatever program ran."""
    r = trace.reduce(_extract(), LAYERS)
    # only the first update, [0, 60], ended inside the window; the device
    # was busy in [5, 30] and [40, 50] of it
    assert r["updates_traced"] == 1
    assert r["device_s_per_update"] == pytest.approx(35e-9)


def test_idle_gaps_are_labelled_by_the_spans_open_across_them():
    r = trace.reduce(_extract(), LAYERS)
    # gaps [50, 70], [75, 95], [30, 40], [0, 5], longest first
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx([20e-9, 20e-9, 10e-9, 5e-9])
    assert [g[0] for g in r["idle_gaps"]] == [
        "bench.lookup_answer+bench.update+bench.wait_publish",
        "bench.update+bench.wait_publish",
        "bench.update+bench.wait_publish",
        "bench.update+bench.wait_publish",
    ]


def test_nothing_to_read_without_a_window_or_a_device():
    ex = _extract()
    assert trace.reduce({"devices": {}, "spans": ex["spans"]}, LAYERS) is None
    ex["spans"] = [s for s in ex["spans"] if s[0] != "bench.window"]
    assert trace.reduce(ex, LAYERS) is None


def test_program_names_and_layers():
    assert trace.program_name("jit_fused_forward_rounds(17)") == "fused_forward_rounds"
    assert trace.layer_of("_unknown", trace.layer_map()) == "unnamed"
    assert trace.layer_of("_bgp_one", trace.layer_map()) == "query"
    assert trace.layer_of("_publish_snapshot", trace.layer_map()) == "serving"
    assert trace.layer_of("something_else", LAYERS) == "other"
    # names match whole: a new program that contains a listed name is other
    assert trace.layer_of("_bgp_one_wide", trace.layer_map()) == "other"
    assert trace.layer_of("_unknown_plan", trace.layer_map()) == "other"


RECORDED = Path(__file__).parent / "data" / "opencyc_x8_update.trace.json.gz"


def test_recorded_chip_trace_of_one_update():
    """2.8 s of a traced window of ``opencyc_x8.lookups_under_updates`` on
    one TPU v5e, around one 24-row add, as ``trace.extract`` read it."""
    r = trace.reduce(trace.load_extract(str(RECORDED)), trace.layer_map())
    assert r["window_s"] == pytest.approx(2.820901179)
    assert r["busy_s"] == pytest.approx(2.761403724)
    assert r["idle_share"] == pytest.approx(1 - 2.761403724 / 2.820901179)
    layers = r["layer_device_s"]
    assert layers["unnamed"] == pytest.approx(2.709915832)
    assert layers["serving"] == pytest.approx(0.048119676)
    assert layers["query"] == pytest.approx(0.003391499)
    assert layers["eager"] == pytest.approx(4.396e-06)
    assert "other" not in layers
    # a program's span holds its operations and the few microseconds
    # between them, so the programs' time covers the busy time and a little
    assert r["busy_s"] <= sum(layers.values()) <= r["busy_s"] + 1e-4
    assert r["updates_traced"] == 1
    # the update's span also holds the publish and a lookup's matcher
    assert r["device_s_per_update"] == pytest.approx(2.194680692)
    assert r["device_s_per_update"] <= r["busy_s"]
    assert r["device_ops"][0][0] == "_unknown [unnamed]"
    assert r["idle_gaps"][0] == ["bench.update+bench.wait_publish",
                                 pytest.approx(0.012448769)]
    assert len(r["idle_gaps"]) == 10

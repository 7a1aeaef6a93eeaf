"""Every configuration and traffic file loads and drives the store, on the
CPU at a tiny scale, through the same loops as a run on the chip; a new
cell is added as data only."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

from bench_helpers import tiny, tiny_run
from perfbench import cell, run

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_every_config_and_traffic_file_loads():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        conf = cell.load_json(ROOT / c["file"])
        assert conf["name"] == c["name"]
        assert set(c["reduced"]) <= set(conf["generator"]) & set(conf["reduced"])
        assert conf["guarantees"]["consistency"].startswith("epoch-snapshot")
    for w in BENCH["workloads"]:
        spec = cell.load(w["name"])
        assert w["config"] in configs and spec.chips == w["chips"]
        assert spec.traffic["updates"]["loop"] == "closed"
        warm = [op for op, _ in spec.traffic["updates"]["warmup"]]
        # an add that puts a delete's rows back runs the programs the
        # delete's own rederivation ran, so a restoring mix warms no add
        assert "delete" in warm
        assert "add" in warm or spec.traffic["updates"].get("restore")
    for path in (ROOT / "perfbench" / "traffic").glob("*.json"):
        assert "updates" in cell.load_json(path)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_drives_a_tiny_store_and_prints_the_result_line(workload):
    traced = "queries" not in cell.load(workload).traffic
    res = tiny_run(workload, traced=traced)
    assert res["correct"], res["checks"]
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    want = {m["name"] for m in run.metric_entries(workload, traced)}
    assert set(res["metrics"]) <= want
    if traced:  # no device plane on the CPU: the trace's metrics stay out
        names = {n.split(".")[0]: n for n in res["metrics"]}
        assert "device_idle_share" not in names
        assert "device_ms_per_update" not in names
        assert res["metrics"][names["compiles_in_window"]]["unit"] == "count"
    else:
        assert set(res["metrics"]) == want
    assert all(v["value"] <= v["limit"] for v in res["checks"].values())
    json.dumps(res)


def test_a_new_traffic_file_is_found_with_no_code_edit(tmp_path):
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    mix = {"why": "a test mix", "updates": {
        "loop": "closed", "clients": 1, "batch": 4, "p_delete": 0.5,
        "p_merge_add": 0.5, "op_order": ["delete", "add"], "events": 8,
        "warmup": [["add", 4], ["delete", 4]]}}
    (tmp_path / "perfbench" / "traffic" / "few_updates.json").write_text(json.dumps(mix))
    bench = dict(BENCH, workloads=BENCH["workloads"] + [{
        "name": "opencyc_x8.few_updates", "config": "opencyc_x8",
        "traffic": "few_updates", "chips": 1, "why": "test"}],
        end_to_end=BENCH["end_to_end"] + [{
            "name": "update_ms.few_updates", "unit": "ms", "better": "lower",
            "bound": 0.1, "source": "host_clock",
            "workloads": ["opencyc_x8.few_updates"]}])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = cell.load("opencyc_x8.few_updates", root=tmp_path)
    assert spec.traffic == mix
    res = tiny_run("opencyc_x8.few_updates", spec=tiny(spec), root=tmp_path)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"update_ms.few_updates", "setup_s"}

"""The command's contract: no TPU, no result; inputs made from the seed."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np

from perfbench import cell

from bench_helpers import tiny

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _run_command(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_exits_non_zero_without_a_tpu_and_prints_no_result():
    proc = _run_command(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_exits_non_zero_with_only_the_benchmark_files(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_command(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def _signature(inputs: cell.Inputs):
    return ([(op, rows.shape[0]) for op, rows in inputs.warmup + inputs.events],
            len(inputs.lookups), sorted(q.kind for q in inputs.lookups),
            inputs.graph.facts.shape)


def test_inputs_come_from_the_seed_and_every_seed_gets_the_same_work():
    spec = tiny(cell.load(CELLS[0]))
    big = 2**31 + 12345
    a, b = cell.build(spec, big, 4.0), cell.build(spec, big, 4.0)
    assert np.array_equal(a.graph.facts, b.graph.facts)
    assert a.lookups == b.lookups and np.array_equal(a.due, b.due)
    assert all(np.array_equal(x[1], y[1]) for x, y in zip(a.events, b.events))
    c = cell.build(spec, 7, 4.0)
    assert not np.array_equal(a.graph.facts, c.graph.facts)
    # the same ops, batch sizes of deletes, number and mix of lookups
    sa, sc = _signature(a), _signature(c)
    assert [op for op, _ in sa[0]] == [op for op, _ in sc[0]]
    assert sa[1:] == sc[1:]
    assert a.due.min() >= 0 and a.due.max() < 4.0


def test_restore_puts_back_each_delete_so_the_work_does_not_drift():
    spec = tiny(cell.load("dbpedia_x2.bulk_updates"))
    assert spec.traffic["updates"]["restore"]
    inputs = cell.build(spec, 2**31 + 99, 4.0)
    stream = inputs.warmup + inputs.events
    base = {tuple(r) for r in inputs.graph.facts}
    current = set(base)
    for (op, rows), (nxt, back) in zip(stream, stream[1:]):
        if op == "delete":
            assert {tuple(r) for r in rows} <= current == base
            assert nxt == "add" and np.array_equal(rows, back)
        current ^= {tuple(r) for r in rows}

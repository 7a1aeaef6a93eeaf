"""A cell: one configuration under one traffic mix, found by name.

``BENCHMARK.json`` names the cell's configuration and traffic; each lives in
a file of its own, ``perfbench/configs/<config>.json`` and
``perfbench/traffic/<traffic>.json`` under the same root.  ``build`` turns
them and a seed into the inputs of one run: the graph, the update stream
and the timed lookups with their due times.  Nothing here touches JAX.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import data

ROOT = Path(__file__).resolve().parents[1]


@dataclass
class Spec:
    name: str
    chips: int
    config: dict
    traffic: dict


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load(workload: str, root: Path = ROOT) -> Spec:
    """The cell ``workload`` of ``root/BENCHMARK.json``, with its files."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    here = root / "perfbench"
    return Spec(
        name=workload,
        chips=int(w["chips"]),
        config=load_json(here / "configs" / f"{w['config']}.json"),
        traffic=load_json(here / "traffic" / f"{w['traffic']}.json"),
    )


@dataclass
class Inputs:
    graph: data.Graph
    warmup: list            # [(op, rows)] applied in set-up
    events: list            # [(op, rows)] the window's closed loop draws from
    lookups: list = field(default_factory=list)      # timed lookups
    due: np.ndarray = field(default_factory=lambda: np.zeros(0))  # s after start
    warm_lookups: list = field(default_factory=list)  # [[Lookup]] batches


def seeds_of(seed: int) -> list[int]:
    """Independent seeds for the graph, the stream and the lookups."""
    return [int(s) for s in np.random.SeedSequence(seed).generate_state(4)]


def build(spec: Spec, seed: int, seconds: float) -> Inputs:
    """The inputs of one run of ``spec``, made from ``seed``.

    The window's lookups are ``rate_per_s * seconds`` in number for every
    seed, with kinds in equal shares in a seeded order, due at sorted
    uniform times over the window: Poisson arrivals at that rate,
    conditioned on their count.
    """
    s_graph, s_stream, s_lookup, s_due = seeds_of(seed)
    graph = data.generate(**spec.config["generator"], seed=s_graph)
    up = spec.traffic["updates"]
    warmup = [tuple(w) for w in up["warmup"]]
    n_events = len(warmup) + up["events"]
    n_base = len(graph.names)
    stream = data.sample_update_stream(
        graph.facts, graph.names, n_events=n_events, batch=up["batch"],
        p_delete=up["p_delete"], p_merge_add=up["p_merge_add"],
        op_order=up.get("op_order"), plan=warmup,
        restore=up.get("restore", False), seed=s_stream,
    )
    # the most names such a stream can intern whatever the seed: an event
    # interns at most one fresh value per row when adds merge, and two
    # names to restart an emptied store
    per_row = 1 if up["p_merge_add"] > 0 else 0
    graph.names.pad_to(n_base + sum(b * per_row + 2 for _, b in warmup)
                       + up["events"] * (up["batch"] * per_row + 2))
    inputs = Inputs(graph, stream[:len(warmup)], stream[len(warmup):])
    q = spec.traffic.get("queries")
    if q:
        inputs.lookups, inputs.due, inputs.warm_lookups = make_lookups(
            q, graph, s_lookup, s_due, seconds)
    return inputs


def make_lookups(q: dict, graph: data.Graph, s_lookup: int, s_due: int,
                 seconds: float) -> tuple[list, np.ndarray, list]:
    """The window's lookups over ``graph``, their due times, and the
    warm-up batches, as the traffic's ``queries`` entry ``q`` says."""
    kinds = list(q["kinds"])
    n = int(round(q["rate_per_s"] * seconds))
    order = np.random.default_rng(s_due).permutation(
        np.resize(np.arange(len(kinds)), n))
    warm_kinds = [k for k in kinds for b in q["warm_batches"] for _ in range(b)]
    pool = data.point_queries(
        graph.facts, graph.names, [kinds[i] for i in order] + warm_kinds,
        seed=s_lookup, max_answers=q["max_answers"],
    )
    due = np.sort(np.random.default_rng(s_due + 1).uniform(0, seconds, n))
    warm = iter(pool[n:])
    batches = [[next(warm) for _ in range(b)] for _k in kinds for b in q["warm_batches"]]
    return pool[:n], due, batches

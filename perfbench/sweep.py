#!/usr/bin/env python3
"""Sweep of the lookup rate of a cell, to find the highest it sustains.

    python3 perfbench/sweep.py --workload <cell> --seed <n> --seconds 45 --rates 40,80,160

One process, one set-up: the cell's store is built and warmed as in a run,
then for each rate, in rising order, one window of ``--seconds`` drives the
closed-loop ingest client beside open-loop lookups at that rate (fresh
lookups over the same graph for each step).  Per step it prints one JSON line: the offered and
the achieved rate, the latency percentiles from the due time, how late the
reader sent lookups in each third of the window, as the mean and the 95th
percentile (a backlog that grows shows as the later thirds outgrowing the
first), the lookups still unanswered when the window closed, and the
updates published.  A step should hold many updates, since each one holds
the device for seconds and lookups that reach the device wait behind it.
The cell's traffic file keeps the rate chosen from it; the sweep is not
part of a run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from perfbench import cell, loops, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    spec = cell.load(args.workload)
    run.configure_jax()
    dev, why = run.find_device(spec.chips, True)
    if dev is None:
        run.log(f"sweep: {why}")
        return 2
    from perfbench import system

    inputs = cell.build(spec, args.seed, args.seconds)
    store = system.make_store(inputs.graph, spec.config.get("engine", {}))
    ingest = loops.Ingest(store, system.snapshot_view, system.counters)
    for op, rows in inputs.warmup:
        ingest.apply(op, rows, span="bench.warm_update")
    loops.warm_lookups(store, [[system.to_query(q) for q in b]
                               for b in inputs.warm_lookups])
    events = iter(inputs.events)
    try:
        for k, rate in enumerate(rates):
            q = dict(spec.traffic["queries"], rate_per_s=rate)
            _, _, s_lookup, s_due = cell.seeds_of(args.seed + k + 1)
            lookups, due, _ = cell.make_lookups(q, inputs.graph, s_lookup,
                                                s_due, args.seconds)
            print(json.dumps(_step(store, ingest, events, lookups, due, rate,
                                   args.seconds, system)), flush=True)
    finally:
        store.close()
    return 0


def _step(store, ingest, events, lookups, due, rate, seconds, system) -> dict:
    queries = [system.to_query(q) for q in lookups]
    ingest.updates.clear()
    ingest.acked_epoch = store.epoch
    start = time.perf_counter()
    reader = loops.Lookups(store, queries, due, start, ingest)
    reader.start()
    ingest.run(events, start + seconds)
    closed = start + seconds
    reader.join(seconds + run.ANSWER_WAIT_S)
    ans = reader.answers
    done = [a for a in ans if a.answered is not None and a.answered <= closed]
    lat = [a.answered - a.due for a in ans if a.answered is not None]
    late = np.asarray([a.submitted - a.due for a in ans]) * 1e3
    thirds = np.array_split(late, 3)
    pub = [u for u in ingest.updates if u.published and u.published <= closed]
    return {
        "offered_per_s": rate,
        "achieved_per_s": len(done) / seconds,
        "p50_ms": loops.percentile(lat, 50) * 1e3 if lat else None,
        "p95_ms": loops.percentile(lat, 95) * 1e3 if lat else None,
        "p99_ms": loops.percentile(lat, 99) * 1e3 if lat else None,
        "late_mean_ms_by_third": [float(t.mean()) for t in thirds],
        "late_p95_ms_by_third": [loops.percentile(t, 95) for t in thirds],
        "unanswered_at_close": len(ans) - len(done),
        "updates_published": len(pub),
        "update_ms": (pub[-1].published - start) / len(pub) * 1e3 if pub else None,
    }


if __name__ == "__main__":
    sys.exit(main())

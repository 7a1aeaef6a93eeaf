"""Serving-tier latency/throughput: idle, busy, closed-loop, batched drain.

The serving contract (docs/serving.md) answers every query from the published
epoch snapshot, so reads never block on — or observe — an in-flight
maintenance operation.  This bench quantifies four things per profile:

  * **idle** — per-query latency with no update in flight;
  * **busy** — queries admitted between maintenance phases while add/delete
    epochs run against the same store (cooperative scheduler, so the
    interleaving is exact).  The headline ratio ``busy_over_idle`` ~= 1:
    snapshots are published eagerly at the epoch barrier (device-resident
    buffer swap + incremental rho refresh + host mirror), so a busy read
    costs exactly what an idle read costs — the build is charged to
    ``snapshot_build_ms`` (its own column), never to the first unlucky
    query.  The ratio is the median over per-query PAIRED ratios (each
    busy sample vs the same query idle at the same published snapshot) —
    see the attribution-discipline comment in ``run_one``;
  * **closed_loop** — a paced open workload against a ``threaded=True``
    store: queries issued at ``target_qps`` from the bench thread while the
    maintenance worker churns through update epochs concurrently; reports
    achieved qps and p50/p95/p99 latency under real concurrent update
    pressure;
  * **batched_speedup** — throughput of draining a shape-heavy *point-lookup*
    query list through the vmapped batched executor (one compiled dispatch
    per BGP shape group, :mod:`repro.sparql.batched`) over the scalar
    one-query-at-a-time host drain on the SAME snapshot.  Point lookups
    (bound subject and/or object, small answer bags) are the queries a
    serving tier batches in practice, and the regime where matching — not
    answer materialisation — is the cost: the scalar matcher scans O(N)
    triples per atom while the batched matcher binary-searches the sorted
    snapshot keys, so the gap widens with store size.  The latency sections
    above keep the generator's §5-hazard mix (scans, joins, clique
    multiplicities) — those answers are bag-materialisation-bound, which is
    shared verbatim by both matchers (``_finish``) and therefore says
    nothing about either.

Epoch-consistency *correctness* is enforced by
tests/test_serve_triple_store.py (batched == scalar == from-scratch oracle);
here the store's epoch accounting is only sanity-checked so the numbers stay
honest.  ``main(out_json=...)`` (or ``benchmarks/run.py serve``) writes the
rows to BENCH_serve.json; ``benchmarks/run.py --check`` gates the committed
rows via :func:`benchmarks.run.compare_serve`.
"""

from __future__ import annotations

import gc
import json
import time

import jax
import numpy as np

from repro.data.generator import generate, sample_update_stream
from repro.serve.triple_store import TripleStore
from repro.sparql.executor import evaluate_at

# Serving-scale stand-ins for the paper's dataset regimes (smaller than the
# materialisation PROFILES: every epoch also pays a from-scratch-sized jit
# warm-up on first occurrence, and the bench runs several profiles).
SERVE_PROFILES: dict[str, dict] = {
    # chain/join-rule heavy (DBpedia-style property chains).  n_plain keeps
    # per-query work well above the container's timer/cache-noise floor —
    # sub-100us queries make any latency *ratio* a coin flip
    "chain_like": dict(
        n_groups=20, group_size=3, n_spokes_per=2, n_plain=2000,
        hierarchy_depth=2, chain_rules=True,
    ),
    # equality-dense: many/large cliques (OpenCyc-style)
    "clique_like": dict(
        n_groups=40, group_size=6, n_spokes_per=2, n_plain=1000,
        hierarchy_depth=2,
    ),
    # plain-payload heavy with chains (DBpedia-style volume) — the
    # shape-heavy profile the batched-drain gate pins (most triples per
    # predicate, so scalar per-query joins are at their most expensive)
    "dbpedia_like": dict(
        n_groups=12, group_size=3, n_spokes_per=2, n_plain=10000,
        hierarchy_depth=2, chain_rules=True,
    ),
}


def _point_queries(facts: np.ndarray, dic, n: int, seed: int) -> list:
    """A serving-realistic point-lookup mix sampled from the explicit facts.

    Three selective single-atom shapes (three compiled shape groups): a
    subject+predicate lookup, a subject scan (out-degree-sized answer) and
    a reverse (predicate, object) lookup.  Constants are drawn from real
    triples whose subject out-degree / (p, o) fan-in is point-lookup sized
    — a hub subject or a type-like (p, o) pair has a scan-sized bag, which
    is a different workload (measured by the latency sections, not here).
    """
    from repro.sparql.algebra import Query

    rng = np.random.default_rng(seed)
    key_po = facts[:, 1].astype(np.int64) << 32 | facts[:, 2].astype(np.int64)
    _, inv, cnt = np.unique(key_po, return_inverse=True, return_counts=True)
    _, inv_s, cnt_s = np.unique(facts[:, 0], return_inverse=True,
                                return_counts=True)
    sel_po = np.flatnonzero(cnt[inv] <= 32)
    sel_s = np.flatnonzero(cnt_s[inv_s] <= 32)
    out = []
    for _ in range(n):
        kind = int(rng.integers(3))
        pool = sel_po if kind == 2 else sel_s
        if pool.shape[0] == 0:
            pool = np.arange(facts.shape[0])
        s, p, o = (int(t) for t in facts[pool[rng.integers(pool.shape[0])]])
        if kind == 0:
            q = Query([(s, p, -1)], [], [-1], False)
        elif kind == 1:
            q = Query([(s, -1, -2)], [], [-1, -2], False)
        else:
            q = Query([(-1, p, o)], [], [-1], False)
        out.append(q)
    return out


def _ms(xs: list[float]) -> dict:
    a = np.asarray(xs, dtype=np.float64) * 1e3
    if a.size == 0:
        return {"mean": 0.0, "p50": 0.0, "p95": 0.0, "p99": 0.0}
    return {
        "mean": round(float(a.mean()), 4),
        "p50": round(float(np.percentile(a, 50)), 4),
        "p95": round(float(np.percentile(a, 95)), 4),
        "p99": round(float(np.percentile(a, 99)), 4),
    }


def _closed_loop(
    facts, program, dic, queries, updates, target_qps: float, n_cl: int,
) -> dict:
    """Paced queries from this thread vs the maintenance worker thread.

    A fresh ``threaded=True`` store (the cooperative store's interleaving
    is hand-scheduled; this one races for real).  Updates are fed in evenly
    across the query window so the worker stays busy under the pacing.
    """
    store = TripleStore(facts, program, dic, threaded=True)
    try:
        for q in queries:  # warm the compiled matchers off the clock
            store.submit_query(q)
        store.drain()
        period = 1.0 / target_qps
        every = max(n_cl // max(len(updates), 1), 1)
        lat: list[float] = []
        tickets = []
        next_t = t_start = time.perf_counter()
        ui = 0
        for i in range(n_cl):
            if i % every == 0 and ui < len(updates):
                op, delta = updates[ui]
                tickets.append(store.submit_update(op, delta))
                ui += 1
            now = time.perf_counter()
            if now < next_t:
                time.sleep(next_t - now)
            next_t += period
            lat.append(store.query_now(queries[i % len(queries)]).wall_s)
        dur = time.perf_counter() - t_start
        busy_n = sum(1 for x in lat if x is not None)
        store.drain()
        assert all(t.status == "done" for t in tickets)
        return {
            "target_qps": round(target_qps, 1),
            "achieved_qps": round(busy_n / max(dur, 1e-9), 1),
            "n_queries": n_cl,
            "updates_submitted": len(updates),
            "epochs_completed": store.epoch,
            "latency_ms": _ms(lat),
        }
    finally:
        store.close()


def run_one(
    name: str, kw: dict, n_updates: int = 4, batch: int = 16,
    n_queries: int = 24, seed: int = 0, target_qps: float = 150.0,
    closed_loop_queries: int | None = None, drain_list_len: int = 128,
) -> dict:
    facts, program, dic = generate(**kw, seed=seed)
    updates = sample_update_stream(
        facts, dic, n_events=n_updates, batch=batch, seed=seed
    )
    queries = [
        payload
        for _op, payload in sample_update_stream(
            facts, dic, n_events=n_queries, batch=batch, p_query=1.0,
            seed=seed + 1,
        )
    ]

    t0 = time.perf_counter()
    store = TripleStore(facts, program, dic)
    base_s = time.perf_counter() - t0

    # warm the query paths (scalar + every batched shape group) so the
    # latency sections below measure steady-state dispatch, not compiles
    for q in queries:
        store.submit_query(q)
    store.drain()

    # -- idle: no maintenance in flight --------------------------------------
    idle_s = [store.query_now(q).wall_s for q in queries]

    # -- busy: queries admitted between the phases of running epochs ---------
    # The attribution discipline, each piece of which changes the answer:
    #   * paired baseline — per-query cost spans orders of magnitude across
    #     the hazard mix AND grows as add epochs grow the store, so each
    #     busy sample is compared against the SAME query measured idle at
    #     the SAME published snapshot (the pre-update baseline pass), never
    #     against the mix-wide mean;
    #   * device sync — step() returns at XLA *dispatch*; the async device
    #     tail is maintenance cost and is drained (and billed to maint_s)
    #     before the query clock starts;
    #   * gc in the maintenance window — a deferred collection otherwise
    #     lands on whichever query allocates next (observed: a ~60ms pause
    #     billed to a 1ms read);
    #   * a short burst per phase — a serving tier answers streams between
    #     phases; the first read after device work pays the cold-cache
    #     toll, the burst is what a client actually sees;
    #   * median of paired ratios — robust to container timer jitter, which
    #     dominates any sum at sub-millisecond latencies.
    busy_s: list[float] = []
    idle_extra: list[float] = []
    ratios: list[float] = []
    maint_s = 0.0
    phases = 0
    qi = 0
    for op, delta in updates:
        idle_now = [store.query_now(q).wall_s for q in queries]
        idle_extra.extend(idle_now)
        t = store.submit_update(op, delta)
        while t.status != "done":
            s0 = time.perf_counter()
            store.step()  # one maintenance phase (query queue is empty here)
            st = store.state
            jax.block_until_ready(
                [st.spo, st.epoch, st.marked, st.tomb, st.n_used,
                 st.rep, st.sort_perm, st.sorted_keys]
            )
            gc.collect()
            maint_s += time.perf_counter() - s0
            phases += 1
            for _ in range(4):
                qt = store.query_now(queries[qi % len(queries)])
                busy_s.append(qt.wall_s)
                ratios.append(
                    qt.wall_s / max(idle_now[qi % len(queries)], 1e-9)
                )
                qi += 1
        assert t.epoch == store.epoch  # barrier accounting stays honest
    assert store.epoch == len(updates)

    # -- batched vs scalar drain throughput at the final epoch ---------------
    snap = store.snapshot
    qlist = _point_queries(facts, dic, drain_list_len, seed + 2)
    bx = store._batched
    bx.run(qlist, snap, dic)  # warm any residual compile at this batch shape
    tb, ts = [], []
    for _ in range(3):  # medians: one drain is jitter-prone at these sizes
        s0 = time.perf_counter()
        bx.run(qlist, snap, dic)
        tb.append(time.perf_counter() - s0)
        s0 = time.perf_counter()
        for q in qlist:
            evaluate_at(q, snap, dic)
        ts.append(time.perf_counter() - s0)
    t_batched = sorted(tb)[1]
    t_scalar = sorted(ts)[1]
    batched_speedup = t_scalar / max(t_batched, 1e-9)

    # -- closed-loop load against a threaded store ---------------------------
    cl_updates = sample_update_stream(
        facts, dic, n_events=n_updates, batch=batch, seed=seed + 2
    )
    closed = _closed_loop(
        facts, program, dic, queries, cl_updates, target_qps,
        closed_loop_queries or max(4 * n_queries, 96),
    )

    audit_problems = store.audit()
    idle, busy = _ms(idle_s + idle_extra), _ms(busy_s)
    return {
        "dataset": name,
        "facts": int(facts.shape[0]),
        "triples_served": int(store.snapshot.triples.shape[0]),
        "base_s": round(base_s, 3),
        "epochs": store.epoch,
        "maintenance_phases": phases,
        "maint_s_per_epoch": round(maint_s / max(store.epoch, 1), 4),
        "idle_query_ms": idle,
        "busy_query_ms": busy,
        "busy_over_idle": round(float(np.median(ratios)), 2) if ratios
        else None,
        # the publication cost, as its own column: construction first, then
        # one entry per epoch barrier (the attribution fix — reads above
        # never pay this)
        "snapshot_build_ms": _ms([x / 1e3 for x in store.publish_ms]),
        "batched_speedup": round(batched_speedup, 2),
        "batched_drain_qps": round(len(qlist) / max(t_batched, 1e-9), 1),
        "scalar_drain_qps": round(len(qlist) / max(t_scalar, 1e-9), 1),
        "batched_stats": dict(bx.stats),
        "closed_loop": closed,
        "audit_problems": audit_problems,
        "n_queries_idle": len(idle_s) + len(idle_extra),
        "n_queries_busy": len(busy_s),
        "ops": [op for op, _ in updates],
    }


def main(
    profiles: dict | None = None,
    out_json: str | None = None,
    n_updates: int = 4,
    batch: int = 16,
    n_queries: int = 24,
    seed: int = 0,
    target_qps: float = 150.0,
) -> list[dict]:
    rows = []
    print(
        "dataset        facts  served  ep  idle q ms  busy q ms  busy/idle"
        "  batchx  cl p95 ms"
    )
    for name, kw in (profiles or SERVE_PROFILES).items():
        r = run_one(
            name, kw, n_updates=n_updates, batch=batch,
            n_queries=n_queries, seed=seed, target_qps=target_qps,
        )
        print(
            f"{r['dataset']:14s} {r['facts']:6d} {r['triples_served']:7d}"
            f" {r['epochs']:3d} {r['idle_query_ms']['mean']:10.3f}"
            f" {r['busy_query_ms']['mean']:10.3f}"
            f"  x{r['busy_over_idle']:<7} x{r['batched_speedup']:<5}"
            f" {r['closed_loop']['latency_ms']['p95']:9.3f}"
        )
        rows.append(r)
    if out_json:
        doc = {
            "caveat": (
                "queries are answered from device-resident double-buffered "
                "epoch snapshots published eagerly at each maintenance "
                "barrier; busy ~= idle because readers never touch the live "
                "arena and never pay the snapshot build (snapshot_build_ms "
                "is its own column).  closed_loop paces queries from the "
                "bench thread at target_qps against a threaded store whose "
                "maintenance worker runs concurrent epochs; batched_speedup "
                "is the vmapped shape-grouped drain vs the scalar host "
                "drain on the same snapshot.  Maintenance wall-clock "
                "inherits the XLA-CPU sort caveat of BENCH_incremental.json"
            ),
            "rows": rows,
        }
        with open(out_json, "w") as fh:
            json.dump(doc, fh, indent=2)
        print(f"[bench_serve_updates] wrote {out_json}")
    return rows


if __name__ == "__main__":
    from repro.compat import use_compilation_cache

    use_compilation_cache()
    main(out_json="BENCH_serve.json")

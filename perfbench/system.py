"""The system under test, and the only module of the benchmark that imports it.

The window drives ``repro.serve.triple_store.TripleStore`` through its public
surface (``submit_update``, ``submit_query``, ``query_now``, ``snapshot``,
``epoch``, ``drain``, ``close``) with ``threaded=True``: maintenance runs on
the store's worker thread while the benchmark's reader answers lookups from
published snapshots.  The counters the per-layer metrics read are the
store's own: ``publish_ms``, ``dispatch_counts`` and ``query_stats``.
"""

from __future__ import annotations

import numpy as np

from .data import Graph, Lookup


def make_store(graph: Graph, engine_kw: dict):
    """A threaded store over ``graph``; its construction materialises the
    base store (epoch 0)."""
    from repro.core.rules import parse_program
    from repro.core.terms import Dictionary
    from repro.serve.triple_store import TripleStore

    dic = Dictionary()
    for name in graph.names.names[len(dic):]:
        dic.intern(name)
    program = parse_program([r.text for r in graph.rules], dic)
    if dic.n_resources != len(graph.names):
        raise AssertionError("the rules name a resource the graph lacks")
    return TripleStore(graph.facts, program, dic, threaded=True, **engine_kw)


def to_query(q: Lookup):
    from repro.sparql.algebra import Query

    return Query([tuple(q.atom)], [], list(q.select), False)


def counters(store) -> dict:
    """The store's counters, to be differenced around the window."""
    d = store.dispatch_counts
    return {
        "publishes": len(store.publish_ms),
        "publish_ms": float(np.sum(store.publish_ms)),
        "dispatches": d["total"],
        "query_dispatches": sum(
            n for k, n in d["by_phase"].items() if k.startswith("query/")),
        "engine_compiles": sum(d["compiles_by_family"].values()),
        "query_stats": dict(store.query_stats),
        "capacity_retries": int(store.state.stats.capacity_retries),
    }


def snapshot_view(store):
    """(epoch, host rows, rho) of the published snapshot."""
    snap = store.snapshot
    return snap.epoch, snap.triples, np.asarray(snap.rho.rep)

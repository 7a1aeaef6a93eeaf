"""The store's phase spans, program names and timers.

Every program the engine compiles is named after its fn-cache family, and
every phase of an update runs inside a ``store.<phase>`` profiler span
(``DispatchCounter.in_phase``), so a device trace can give each program's
device time to the phase that launched it.  The timers of the serving
tier run from admission.
"""

import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.core.engine_jax import JaxEngine, _CountedFn
from repro.core.stats import DispatchCounter
from repro.data.generator import generate
from repro.serve.triple_store import TripleStore
from repro.sparql import Query

# the functions the engine jits as ``functools.partial`` objects: a program
# still named after one of them escaped the family names
UNNAMED = {
    "_unknown", "eval_plan", "eval_plan_rederive", "process_candidates",
    "_squeeze_stream", "fused_forward_rounds", "fused_delete_waves",
    "_seed_tombs", "_od_step", "_finalize_tombs", "_extract_tombed",
    "_member", "_occupancy", "_rebuild_index",
}
DELETE_ORDER = [
    "store.begin", "store.delete:prepare", "store.delete:seed",
    "store.delete:wave", "store.delete:finalize", "store.delete:rederive",
    "store.delete:forward", "store.barrier", "store.publish",
    "store.publish_host",
]


def _store(threaded: bool, **engine_kw):
    facts, prog, dic = generate(
        n_groups=1, group_size=4, n_spokes_per=3, n_plain=0,
        hierarchy_depth=0, seed=0,
    )
    engine = JaxEngine(dic.n_resources, capacity=1 << 11, bind_cap=1 << 11,
                       out_cap=1 << 11, rewrite_cap=1 << 11, **engine_kw)
    store = TripleStore(facts, prog, dic, engine=engine, threaded=threaded)
    # an inverse-functional edge: deleting it splits the clique
    edge = facts[np.flatnonzero(facts[:, 1] == dic.id_of(":idProp"))[:1]]
    spoke = Query([(-1, dic.id_of(":spoke"), -2)], [], [-1], False)
    return store, edge, spoke


def _profile(log_dir):
    """The store's spans and the launches, as ``perfbench.phases`` reads
    them from the profile in ``log_dir``."""
    from jax.profiler import ProfileData

    from perfbench import phases

    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)[0]
    return phases.extract(ProfileData.from_file(path))


def test_profile_of_a_split_an_add_and_a_drain(tmp_path):
    store, edge, spoke = _store(threaded=True)
    with store:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            store.submit_update("delete", edge)
            store.drain()
            store.submit_update("add", edge)
            store.drain()
            store.submit_query(spoke)
            store.query_now(spoke)
        finally:
            jax.profiler.stop_trace()
    ex = _profile(str(tmp_path))
    worker = {t for n, _, _, t in ex["store_spans"] if n == "store.begin"}
    assert len(worker) == 1
    spans = sorted((s, s + d, n) for n, s, d, t in ex["store_spans"] if t in worker)
    launches = [(p, s) for p, s, _, t in ex["launches"] if t in worker]
    names = {p for p, _ in launches}
    assert not names & UNNAMED, names & UNNAMED
    assert {"seed_tombs", "fwave", "finalize_tombs", "fforward",
            "_publish_snapshot"} <= names, names
    for program, t in launches:
        assert any(s <= t < e for s, e, _ in spans), (program, t)

    # the delete's spans in order, then the add's
    begins = [s for s, _, n in spans if n == "store.begin"]
    assert len(begins) == 2
    order = []
    for s, _, n in spans:
        if s < begins[1] and n not in order:
            order.append(n)
    assert order == DELETE_ORDER
    adds = [n for s, _, n in spans if s >= begins[1]]
    assert adds == ["store.begin", "store.add:prepare", "store.add:forward",
                    "store.barrier", "store.publish", "store.publish_host"]

    # the drain's matcher runs on the reader's thread, inside its span
    q_spans = [(s, s + d, t) for n, s, d, t in ex["store_spans"] if n == "store.query"]
    assert {t for _, _, t in q_spans}.isdisjoint(worker)
    bgp = [(s, t) for p, s, _, t in ex["launches"] if p == "_bgp_one"]
    assert bgp and all(any(a <= s < b and t == tq for a, b, tq in q_spans)
                       for s, t in bgp)
    assert store.query_stats["batched"] == 2


@pytest.mark.parametrize("fuse_rounds, families", [
    (True, {"fforward", "fwave"}),
    (False, {"plan", "process", "od"}),
])
def test_every_engine_program_is_named_after_its_family(fuse_rounds, families):
    store, edge, spoke = _store(threaded=False, fuse_rounds=fuse_rounds)
    store.submit_update("delete", edge)
    store.submit_update("add", edge)
    store.submit_query(spoke)
    store.query_now(spoke)
    store.drain()
    own = {"snapshot": "_publish_snapshot", "bgp": "_bgp_one"}
    # ("padbuf", ...) entries are device buffers, not programs
    fns = [f for f in store.engine._fns.values() if isinstance(f, _CountedFn)]
    assert {f.family for f in fns} >= families | {
        "rplan", "seed_tombs", "finalize_tombs", "extract_od", "member",
        "occupancy", "snapshot", "bgp"}
    for fn in fns:
        assert fn.fn.__name__ == own.get(fn.family, fn.family)


def test_in_phase_nests_restores_and_stays_on_its_thread():
    c = DispatchCounter()
    seen = []

    def reader():
        seen.append(c.phase)
        with c.in_phase("query"):
            c.record("bgp")
            seen.append(c.phase)
        seen.append(c.phase)

    with c.in_phase("delete:wave"):
        with c.in_phase("publish"):
            c.record("snapshot")
            assert c.phase == "publish"
        assert c.phase == "delete:wave"
        t = threading.Thread(target=reader)
        t.start()
        t.join()
        assert c.phase == "delete:wave"
    assert c.phase is None
    assert seen == [None, "query", None]
    assert dict(c.by_phase) == {("publish", "snapshot"): 1, ("query", "bgp"): 1}

    with pytest.raises(ValueError):
        with c.in_phase("add:forward"):
            raise ValueError
    assert c.phase is None


def test_in_phase_stays_open_across_a_generators_yield():
    c = DispatchCounter()

    def phases():
        with c.in_phase("delete:seed"):
            yield "seeded"
        with c.in_phase("delete:wave"):
            yield "wave"

    gen = phases()
    assert next(gen) == "seeded" and c.phase == "delete:seed"
    c.record("seed_tombs")
    assert next(gen) == "wave" and c.phase == "delete:wave"
    gen.close()
    assert c.phase is None
    assert dict(c.by_phase) == {("delete:seed", "seed_tombs"): 1}


def test_lookup_tickets_are_timed_each_from_its_own_admission():
    store, _edge, spoke = _store(threaded=False)
    early = store.submit_query(spoke)
    time.sleep(0.05)
    late = store.submit_query(spoke)
    last = store.query_now(spoke)
    assert early.wall_s >= late.wall_s + 0.05 > late.wall_s >= last.wall_s > 0
    s = store.query_stats
    assert s["batched"] == 3
    assert s["wall_ms"] == pytest.approx(
        1e3 * (early.wall_s + late.wall_s + last.wall_s))
    assert 0 < s["device_wait_ms"] <= s["wall_ms"]
    # a direct call of the executor times its wait but counts no lookup
    assert len(store._batched.run([spoke, spoke], store.snapshot, store.dic)) == 2
    assert store._batched.last_wait_ms > 0
    assert {k: store.query_stats[k] for k in s if k.endswith("_ms")} == {
        k: v for k, v in s.items() if k.endswith("_ms")}


def test_update_tickets_are_timed_from_admission():
    store, edge, _spoke = _store(threaded=False)
    t = store.submit_update("delete", edge)
    time.sleep(0.05)
    store.drain()
    assert t.status == "done"
    assert t.queued_s >= 0.05
    assert t.wall_s > t.queued_s
    assert t.wall_s * 1e3 > t.publish_ms

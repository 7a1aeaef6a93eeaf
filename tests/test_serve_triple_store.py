"""Differential consistency of the live SPARQL triple store.

The serving oracle (docs/serving.md): every answer the service produces at
epoch ``e`` must equal evaluating the same query over the *from-scratch* REW
materialisation of the explicit fact set as of epoch ``e`` — no matter how
queries interleave with the phases of running maintenance operations.  The
scheduler is deterministic, so randomized interleavings (including queries
admitted between an overdelete wave and its rederivation) are constructed
exactly and replayed against the oracle.

Fuzz tiers follow the PR 2 harness pattern: seeded fallback combos always
run; with hypothesis installed a quick budget runs in tier-1 and a larger
``slow``-marked budget nightly.
"""

import numpy as np
import pytest

from repro.core.engine_jax import JaxEngine
from repro.core.materialise import materialise_rew
from repro.core.triples import apply_op, pack
from repro.data.datasets import single_clique
from repro.data.generator import generate, sample_update_stream
from repro.serve.triple_store import TripleStore
from repro.sparql import Query, evaluate


def _engine(dic, cap=1 << 11):
    return JaxEngine(
        dic.n_resources, capacity=cap, bind_cap=cap, out_cap=cap,
        rewrite_cap=cap,
    )


def _packset(spo):
    return set(pack(np.asarray(spo, np.int32).reshape(-1, 3)).tolist())


class _Oracle:
    """Explicit-set bookkeeping + from-scratch answers per completed epoch."""

    def __init__(self, facts, program, dic):
        self.program, self.dic = program, dic
        self.explicit_at = {0: np.asarray(facts, np.int32)}
        self._mat = {}

    def apply(self, ticket):
        """Record a completed update ticket (call in epoch order)."""
        prev = self.explicit_at[ticket.epoch - 1]
        self.explicit_at[ticket.epoch] = apply_op(prev, ticket.op, ticket.delta)

    def mat(self, epoch):
        if epoch not in self._mat:
            self._mat[epoch] = materialise_rew(
                self.explicit_at[epoch], self.program, self.dic.n_resources
            )
        return self._mat[epoch]

    def answer(self, q, epoch):
        ref = self.mat(epoch)
        return evaluate(q, ref.triples(), ref.rep, self.dic)


def _run_trace(gen_kw, seed, n_events, batch, ticks_seed, cap=1 << 11):
    """Feed a mixed trace through the scheduler under a randomized tick
    pattern, then hold every answer to the oracle at its reported epoch."""
    facts, prog, dic = generate(**gen_kw, seed=seed)
    trace = sample_update_stream(
        facts, dic, n_events=n_events, batch=batch, p_query=0.5, seed=seed
    )
    if not any(op == "query" for op, _ in trace):
        trace.append(
            sample_update_stream(
                facts, dic, n_events=1, batch=1, p_query=1.0, seed=seed + 1
            )[0]
        )
    store = TripleStore(facts, prog, dic, engine=_engine(dic, cap))
    rng = np.random.default_rng(ticks_seed)
    updates, queries = [], []
    for op, payload in trace:
        if op == "query":
            queries.append(store.submit_query(payload))
        else:
            updates.append(store.submit_update(op, payload))
        # 0 ticks lets work pile up; >0 races reads against update phases
        for _ in range(int(rng.integers(0, 3))):
            store.step()
    store.drain()

    assert all(t.status == "done" for t in updates + queries)
    assert store.epoch == len(updates)  # one epoch per admitted update
    oracle = _Oracle(facts, prog, dic)
    for t in sorted(updates, key=lambda t: t.epoch):
        oracle.apply(t)
    # the published snapshot is the newest epoch's fixpoint
    ref = oracle.mat(store.epoch)
    assert _packset(store.snapshot.triples) == _packset(ref.triples())
    assert (store.snapshot.rho.rep[: ref.rep.shape[0]] == ref.rep).all()
    for t in queries:
        assert t.answer == oracle.answer(t.query, t.epoch), (
            f"query {t.uid} diverged from the epoch-{t.epoch} oracle"
        )
    return store, queries


# ---------------------------------------------------------------------------
# differential consistency across workload profiles
# ---------------------------------------------------------------------------

_TRACE_PROFILES = [
    ("chain_like", dict(n_groups=2, group_size=3, n_spokes_per=1, n_plain=20,
                        hierarchy_depth=1, chain_rules=True), 3),
    ("clique_like", dict(n_groups=2, group_size=5, n_spokes_per=2, n_plain=10,
                         hierarchy_depth=1), 5),
    ("dbpedia_like", dict(n_groups=2, group_size=3, n_spokes_per=2, n_plain=60,
                          hierarchy_depth=2, chain_rules=True), 7),
]


@pytest.mark.parametrize(
    "gen_kw, seed", [(kw, s) for _n, kw, s in _TRACE_PROFILES],
    ids=[n for n, _kw, _s in _TRACE_PROFILES],
)
def test_differential_consistency(gen_kw, seed):
    _run_trace(gen_kw, seed=seed, n_events=6, batch=8, ticks_seed=seed)


# ---------------------------------------------------------------------------
# scheduled edge cases on the snapshot API
# ---------------------------------------------------------------------------

def test_query_admitted_between_overdelete_and_rederive():
    """A query admitted after the overdelete wave finalises (the live arena
    hides tombstoned-but-not-yet-rederived rows) must be answered at the
    previous epoch's fixpoint — evaluating the live mid-round store instead
    would lose answers."""
    facts, prog, dic = generate(
        n_groups=1, group_size=4, n_spokes_per=3, n_plain=0,
        hierarchy_depth=0, seed=0,
    )
    store = TripleStore(facts, prog, dic, engine=_engine(dic))
    spoke = dic.id_of(":spoke")
    q = Query([(-1, spoke, -2)], [], [-1], False)
    baseline = store.query_now(q)
    assert baseline.epoch == 0 and sum(baseline.answer.values()) > 0

    idp = dic.id_of(":idProp")
    edge = facts[np.flatnonzero(facts[:, 1] == idp)[:1]]
    t = store.submit_update("delete", edge)
    ticks = 0
    while store.inflight_phase != "overdeleted":
        store.step()
        ticks += 1
        assert ticks < 50, "never reached the mid-overdelete phase"
    assert t.status == "running"

    # the live arena is mid-round: rows the rederive pass will restore are
    # hidden, so reading it directly WOULD be wrong...
    live = store.engine.state_triples(store.state)
    assert _packset(live) < _packset(store.snapshot.triples)
    assert evaluate(q, live, store.engine.state_rep(store.state), dic) \
        != baseline.answer

    # ...but the admitted query reads the published epoch-0 snapshot
    mid = store.submit_query(q)
    store.step()
    assert mid.status == "done" and mid.epoch == 0
    assert mid.answer == baseline.answer

    store.drain()
    after = store.query_now(q)
    assert after.epoch == 1
    ref = materialise_rew(apply_op(facts, "delete", edge), prog, dic.n_resources)
    assert after.answer == evaluate(q, ref.triples(), ref.rep, dic)


def test_query_admitted_at_rederive_phase_reads_published_snapshot():
    """The targeted-rederivation phase ("rederive": head-bound joins done,
    forward fixpoint still pending) is a scheduler yield point like any
    other — a query admitted there must be served at the previous epoch's
    fixpoint, not the live mid-operation arena."""
    facts, prog, dic = generate(
        n_groups=1, group_size=4, n_spokes_per=3, n_plain=0,
        hierarchy_depth=0, seed=0,
    )
    store = TripleStore(facts, prog, dic, engine=_engine(dic))
    spoke = dic.id_of(":spoke")
    q = Query([(-1, spoke, -2)], [], [-1], False)
    baseline = store.query_now(q)

    idp = dic.id_of(":idProp")
    edge = facts[np.flatnonzero(facts[:, 1] == idp)[:1]]
    store.submit_update("delete", edge)
    ticks = 0
    while store.inflight_phase != "rederive":
        store.step()
        ticks += 1
        assert ticks < 100, "never reached the rederive phase"
    mid = store.submit_query(q)
    store.step()
    assert mid.status == "done" and mid.epoch == 0
    assert mid.answer == baseline.answer

    store.drain()
    after = store.query_now(q)
    assert after.epoch == 1
    ref = materialise_rew(apply_op(facts, "delete", edge), prog, dic.n_resources)
    assert after.answer == evaluate(q, ref.triples(), ref.rep, dic)


def test_split_then_query_old_representative_expands_post_split():
    """Clique split followed immediately by a query over the old
    representative: the answer must expand through the POST-split rho."""
    facts, prog, dic = single_clique(6)
    store = TripleStore(facts, prog, dic, engine=_engine(dic, cap=256))
    sa = dic.id_of("owl:sameAs")
    a = [dic.id_of(f":a{i}") for i in range(6)]
    q_old_rep = Query([(-1, sa, a[0])], [], [-1], False)
    pre = store.query_now(q_old_rep)
    assert pre.answer == {(f":a{i}",): 1 for i in range(6)}

    store.submit_update("delete", facts[2:3])  # a2 ~ a3 -> {a0,a1,a2}|{a3,a4,a5}
    store.drain()
    post = store.query_now(q_old_rep)
    assert post.epoch == 1
    assert post.answer == {(":a0",): 1, (":a1",): 1, (":a2",): 1}
    # the old representative no longer speaks for the severed half
    q_new_rep = Query([(-1, sa, a[4])], [], [-1], False)
    assert store.query_now(q_new_rep).answer == {
        (":a3",): 1, (":a4",): 1, (":a5",): 1,
    }


def test_snapshot_isolated_from_maintenance_and_noop_epochs():
    """Published snapshots are immutable across later maintenance; no-op
    updates still cross an epoch barrier (their fixpoint is the unchanged
    store), so readers' epochs stay monotone and attributable."""
    facts, prog, dic = single_clique(5)
    store = TripleStore(facts, prog, dic, engine=_engine(dic, cap=256))
    snap0 = store.snapshot
    before = _packset(snap0.triples)
    rho0 = snap0.rho.rep.copy()

    store.submit_update("delete", facts[1:2])
    store.drain()
    assert store.epoch == 1 and store.snapshot is not snap0
    # the old view is untouched by the epoch that ran after it
    assert _packset(snap0.triples) == before
    assert (snap0.rho.rep == rho0).all()
    assert not snap0.rho.rep.flags.writeable

    # no-op update: delete of a non-explicit row
    t = store.submit_update("delete", np.asarray([[9, 9, 9]], np.int32))
    store.drain()
    assert t.status == "done" and t.epoch == 2 and store.epoch == 2
    assert _packset(store.snapshot.triples) == _packset(
        store.engine.state_triples(store.state)
    )


def test_query_constant_unseen_at_serving_epoch():
    """A query constant interned AFTER the published snapshot's rho was
    frozen (e.g. a resource a concurrent add is about to introduce) must be
    treated as a singleton — an empty match, never an IndexError killing
    the scheduler — and must resolve normally once its epoch completes."""
    facts, prog, dic = single_clique(4)
    store = TripleStore(facts, prog, dic, engine=_engine(dic, cap=256))
    sa = dic.id_of("owl:sameAs")
    fresh = dic.intern(":arrives-later")
    assert fresh >= store.snapshot.n_res
    q = Query([(-1, sa, fresh)], [], [-1], False)

    # race the query against the add that introduces the fresh resource
    store.submit_update(
        "add", np.asarray([[fresh, sa, dic.id_of(":a0")]], np.int32)
    )
    early = store.submit_query(q)
    store.step()
    assert early.status == "done" and early.epoch == 0
    assert early.answer == {}  # unseen singleton: no match, no crash
    store.drain()
    late = store.query_now(q)
    assert late.epoch == 1
    # fresh ~ a0 merged the clique: the constant now expands to all members
    assert late.answer == {
        (":a0",): 1, (":a1",): 1, (":a2",): 1, (":a3",): 1,
        (":arrives-later",): 1,
    }


def test_mixed_trace_generator_shapes():
    """p_query=0 keeps the update-only contract; p_query=1 yields queries."""
    facts, _prog, dic = single_clique(4)
    upd = sample_update_stream(facts, dic, n_events=4, batch=4, seed=0)
    assert all(op in ("add", "delete") for op, _ in upd)
    qs = sample_update_stream(
        facts, dic, n_events=4, batch=4, p_query=1.0, seed=0
    )
    assert all(op == "query" for op, _ in qs)
    for _op, q in qs:
        assert isinstance(q, Query) and q.select
        assert all(len(atom) == 3 for atom in q.patterns)


# ---------------------------------------------------------------------------
# fuzz of interleaved query/update schedules (PR 2 harness pattern)
# ---------------------------------------------------------------------------

_FUZZ_COMBOS = [
    (dict(n_groups=2, group_size=3, n_spokes_per=1, n_plain=15,
          hierarchy_depth=1), 19, 5, 6, 23),
    (dict(n_groups=1, group_size=4, n_spokes_per=2, n_plain=5,
          hierarchy_depth=0), 29, 6, 5, 31),
]


@pytest.mark.parametrize(
    "gen_kw, seed, n_events, batch, ticks_seed", _FUZZ_COMBOS,
    ids=["serve_basic", "serve_dense"],
)
def test_fuzz_fallback_schedules(gen_kw, seed, n_events, batch, ticks_seed):
    """Seeded interleaving fuzz that runs without hypothesis installed."""
    _run_trace(gen_kw, seed, n_events, batch, ticks_seed)


try:
    from hypothesis import HealthCheck, given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # container without the test extra: fallback fuzz only
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    _sched_params = given(
        seed=st.integers(0, 2**16),
        ticks_seed=st.integers(0, 2**16),
        n_events=st.integers(2, 6),
        batch=st.integers(2, 8),
        n_groups=st.integers(1, 2),
        group_size=st.integers(2, 4),
        n_plain=st.integers(0, 15),
    )
    _fuzz_settings = dict(
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
    )

    def _fuzz_test(max_examples):
        """A fresh test function per budget: hypothesis applies ``settings``
        once per function object."""

        def fuzz(seed, ticks_seed, n_events, batch, n_groups, group_size,
                 n_plain):
            gen_kw = dict(
                n_groups=n_groups, group_size=group_size, n_spokes_per=1,
                n_plain=n_plain, hierarchy_depth=1,
            )
            _run_trace(gen_kw, seed, n_events, batch, ticks_seed)

        return _sched_params(
            settings(max_examples=max_examples, **_fuzz_settings)(fuzz)
        )

    # quick budget for tier-1; hypothesis shrinks failures to a minimal
    # schedule (fewest events, smallest graph, simplest tick pattern)
    test_fuzz_interleaved_schedules = _fuzz_test(5)

    # nightly tier: larger example budget, deselectable via -m "not slow"
    test_fuzz_interleaved_schedules_nightly = pytest.mark.slow(_fuzz_test(50))


# ---------------------------------------------------------------------------
# bench smoke: the tiny profile must run end-to-end (keeps the bench alive)
# ---------------------------------------------------------------------------

def test_bench_serve_smoke(tmp_path):
    from benchmarks.bench_serve_updates import main

    out = tmp_path / "BENCH_serve.json"
    rows = main(
        profiles={"smoke": dict(
            n_groups=2, group_size=3, n_spokes_per=1, n_plain=20,
            hierarchy_depth=1,
        )},
        out_json=str(out),
        n_updates=2, batch=6, n_queries=4,
    )
    assert out.exists()
    (row,) = rows
    assert row["epochs"] == 2 and row["n_queries_busy"] > 0
    # the acceptance contract: latency recorded with AND without concurrent
    # maintenance epochs
    assert row["idle_query_ms"]["mean"] >= 0
    assert row["busy_query_ms"]["mean"] > 0
    assert row["idle_query_ms"]["p99"] >= row["idle_query_ms"]["p50"]
    # snapshot build cost is its own column (never inside query latency):
    # construction + one entry per epoch barrier
    assert row["snapshot_build_ms"]["mean"] > 0
    assert row["batched_speedup"] > 0 and row["audit_problems"] == []
    cl = row["closed_loop"]
    assert cl["epochs_completed"] == cl["updates_submitted"] == 2
    assert cl["achieved_qps"] > 0
    assert cl["latency_ms"]["p99"] >= cl["latency_ms"]["p50"] >= 0
    import json

    doc = json.loads(out.read_text())
    assert doc["rows"][0]["dataset"] == "smoke"

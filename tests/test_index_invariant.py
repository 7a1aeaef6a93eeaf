"""Persistent sorted arena index: invariant fuzz + the sort-op budget.

The tentpole contract (ISSUE 4): ``EngineState.sorted_keys`` always equals
``sort(pack3(live rows))`` per shard — maintained by merge-on-insert and
stable-partition removal, NEVER by re-sorting the arena — and the arena is
argsorted at most once per *mutation epoch* (capacity re-layout), asserted
two ways below: a jaxpr trace proving the compiled round fns contain no
arena-length sort primitive, and the ``stats.index_rebuilds`` counter.

Traces cover chain/clique/dbpedia-style workloads after every add/delete
phase, capacity-retry restarts, and epoch barriers, on 1 device in-process
plus 1/2/4 virtual devices in a subprocess (``slow``).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.engine_jax import JaxEngine, index_invariant_report
from repro.core.incremental_spmd import spmd_add_phases, spmd_delete_phases
from repro.core.materialise import materialise_rew
from repro.core.triples import apply_op, pack
from repro.data.datasets import clique_with_spokes, pex, single_clique
from repro.data.generator import generate, sample_update_stream


def _engine(dic, cap=1 << 10, **kw):
    return JaxEngine(
        dic.n_resources, capacity=cap, bind_cap=cap, out_cap=cap,
        rewrite_cap=cap, **kw,
    )


def _assert_clean(eng, state, where=""):
    probs = index_invariant_report(state, eng.n_shards)
    assert probs == [], (where, probs)


# ---------------------------------------------------------------------------
# invariant after every phase / operation (chain, clique, dbpedia-style)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "ds",
    [
        lambda: single_clique(8),                      # chain of sameAs
        lambda: clique_with_spokes(6, 4),              # clique + payload
        lambda: generate(n_groups=2, group_size=3, n_spokes_per=2,
                         n_plain=40, hierarchy_depth=2, chain_rules=True,
                         seed=5),                      # dbpedia-style rules
    ],
    ids=["chain", "clique", "dbpedia_like"],
)
def test_index_invariant_after_every_phase(ds):
    from repro.compat import enable_x64

    facts, prog, dic = ds()
    eng = _engine(dic)
    state = eng.materialise_state(facts, prog)
    _assert_clean(eng, state, "base")
    events = sample_update_stream(facts, dic, n_events=4, batch=6, seed=1)
    explicit = facts
    for i, (op, delta) in enumerate(events):
        explicit = apply_op(explicit, op, delta)
        gen = (spmd_add_phases if op == "add" else spmd_delete_phases)(
            eng, state, delta, 10_000
        )
        eng._set_update_buffers(True)
        with enable_x64():
            for phase in gen:
                _assert_clean(eng, state, f"event {i} phase {phase}")
        eng._barrier(state)
        _assert_clean(eng, state, f"event {i} barrier")
        ref = materialise_rew(explicit, prog, dic.n_resources)
        got = set(pack(eng.state_triples(state)).tolist())
        assert got == set(pack(ref.triples()).tolist()), (i, op)
    assert state.stats.index_rebuilds == 0  # no growth -> no full argsort


def test_index_invariant_across_capacity_retry_restart():
    """A mid-update CapacityError rolls back, re-lays-out the arena, and
    rebuilds the index exactly once (the per-epoch argsort budget)."""
    facts, prog, dic = clique_with_spokes(7, 4)
    base = _engine(dic)
    used = int(np.asarray(base.materialise_state(facts, prog).n_used).sum())
    eng = JaxEngine(dic.n_resources, capacity=used + 2, bind_cap=1 << 10,
                    out_cap=1 << 10, rewrite_cap=1 << 10)
    state = eng.materialise_state(facts, prog)
    _assert_clean(eng, state, "snug base")
    rebuilds0 = state.stats.index_rebuilds
    eng.delete_facts(state, facts[2:4])  # forces arena growth + restart
    assert eng.capacity > used + 2
    _assert_clean(eng, state, "after growth")
    assert not state.index_dirty
    assert state.stats.index_rebuilds - rebuilds0 == 1
    remaining = np.concatenate([facts[:2], facts[4:]], axis=0)
    ref = materialise_rew(remaining, prog, dic.n_resources)
    got = set(pack(eng.state_triples(state)).tolist())
    assert got == set(pack(ref.triples()).tolist())


def test_index_invariant_at_serving_epoch_barriers():
    """The serving scheduler's tick loop keeps the invariant at every tick,
    and snapshots read through the index match the mask-scan extraction."""
    from repro.serve.triple_store import TripleStore

    facts, prog, dic = generate(
        n_groups=2, group_size=3, n_spokes_per=1, n_plain=25,
        hierarchy_depth=1, seed=2,
    )
    store = TripleStore(facts, prog, dic)
    _assert_clean(store.engine, store.state, "epoch 0")
    events = sample_update_stream(facts, dic, n_events=3, batch=5, seed=2)
    for op, delta in events:
        store.submit_update(op, delta)
    ticks = 0
    while store.pending():
        store.step()
        ticks += 1
        assert ticks < 10_000
        if store.inflight is None:  # epoch barrier
            _assert_clean(store.engine, store.state, f"tick {ticks}")
    snap = store.snapshot
    live = (np.asarray(store.state.epoch) >= 0) & ~np.asarray(store.state.marked)
    want = np.asarray(store.state.spo)[live]
    assert set(pack(snap.triples).tolist()) == set(pack(want).tolist())
    # index extraction publishes packed-key-sorted triples per shard
    assert (np.diff(pack(snap.triples)) > 0).all()


# ---------------------------------------------------------------------------
# the sort-op budget: no arena-length sort primitive inside the round fns
# (the recursive jaxpr walker lives in repro.analysis — shared with the
# lint CLI, which audits the same inventory through the engine registry)
# ---------------------------------------------------------------------------

from repro.analysis import count_sorts_at_least as _sorts_at_least


def test_no_arena_sort_in_round_fns():
    """Trace test for the acceptance budget: neither the process step nor
    any plan evaluation contains a sort over arena-length operands — only
    the (cap-sized) candidate stream / binding sorts remain, and the single
    allowed arena argsort lives in the explicit rebuild fn."""
    import jax

    from repro.compat import enable_x64
    from repro.data.datasets import pex

    facts, prog, dic = pex()
    # arena strictly larger than every other buffer so arena-length sorts
    # are unambiguous in the traces
    eng = JaxEngine(dic.n_resources, capacity=4096, bind_cap=256, out_cap=256,
                    rewrite_cap=256)
    state = eng.materialise_state(facts, prog)
    arena_rows = int(state.spo.shape[0])
    assert arena_rows > 4 * max(eng.bind_cap, eng.out_cap, eng.rewrite_cap)

    with enable_x64():
        import jax.numpy as jnp

        from repro.core.engine_jax import I32, eval_plan, process_candidates
        from functools import partial

        cands = jnp.zeros((eng.out_cap, 3), I32)
        cv = jnp.zeros((eng.out_cap,), bool)
        proc = partial(
            process_candidates, rewrite_cap=eng.rewrite_cap, axis=None,
            n_shards=1, route_cap=None, pair_cap=eng.pair_cap,
        )
        jx = jax.make_jaxpr(proc)(
            state.spo, state.epoch, state.marked, state.n_used, state.rep,
            state.sort_perm, state.sorted_keys, cands, cv, jnp.asarray(1, I32),
        )
        assert _sorts_at_least(jx.jaxpr, arena_rows) == 0

        from repro.core.engine_jax import build_plans

        for rule in prog.rules:
            for full in (False, True):
                for plan in build_plans(rule, full=full):
                    consts = jnp.zeros((len(rule.body), 3), I32)
                    hc = jnp.zeros((3,), I32)
                    slots = tuple(
                        t if isinstance(t, int) and t < 0 else None
                        for t in rule.head
                    )
                    fn = partial(
                        eval_plan, plan=tuple(plan), head_var_slots=slots,
                        bind_cap=eng.bind_cap, out_cap=eng.out_cap, axis=None,
                    )
                    jx = jax.make_jaxpr(fn)(
                        state.spo, state.epoch, state.marked, state.tomb,
                        state.sorted_keys, state.sort_perm,
                        jnp.asarray(1, I32), consts, hc,
                    )
                    assert _sorts_at_least(jx.jaxpr, arena_rows) == 0, rule

        # the targeted rederive joins obey the same budget: seed table and
        # binding sorts are cap-sized, the arena is only range-probed
        from repro.core.engine_jax import build_rederive_plan, eval_plan_rederive

        for rule in prog.rules:
            plan, seed_vars = build_rederive_plan(rule)
            if not seed_vars:
                continue  # variable-free head: whole-rule fallback instead
            consts = jnp.zeros((len(rule.body), 3), I32)
            hc = jnp.zeros((3,), I32)
            slots = tuple(
                t if isinstance(t, int) and t < 0 else None
                for t in rule.head
            )
            seeds = jnp.zeros((64, len(seed_vars)), I32)
            sv = jnp.zeros((64,), bool)
            fn = partial(
                eval_plan_rederive, plan=tuple(plan), head_var_slots=slots,
                seed_vars=seed_vars, bind_cap=eng.bind_cap,
                out_cap=eng.out_cap, axis=None,
            )
            jx = jax.make_jaxpr(fn)(
                state.spo, state.epoch, state.marked, state.tomb,
                state.sorted_keys, state.sort_perm, consts, hc, seeds, sv,
            )
            assert _sorts_at_least(jx.jaxpr, arena_rows) == 0, rule


def test_rebuild_counter_budget_over_stream():
    """<= one full argsort per mutation epoch across a whole update stream:
    rebuilds only ever accompany capacity growth."""
    facts, prog, dic = generate(
        n_groups=2, group_size=3, n_spokes_per=1, n_plain=30,
        hierarchy_depth=1, seed=4,
    )
    eng = _engine(dic, cap=1 << 11)
    state = eng.materialise_state(facts, prog)
    events = sample_update_stream(facts, dic, n_events=5, batch=8, seed=4)
    epochs = 0
    for op, delta in events:
        (eng.add_facts if op == "add" else eng.delete_facts)(state, delta)
        epochs += 1
        assert state.stats.index_rebuilds <= epochs
    assert state.stats.index_rebuilds == 0  # ample caps: zero full sorts


# ---------------------------------------------------------------------------
# hypothesis fuzz (nightly) + device-count invariance (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_index_invariant_hypothesis_fuzz():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    facts0, prog0, dic0 = clique_with_spokes(5, 3)

    @settings(max_examples=25, deadline=None)
    @given(
        st.lists(
            st.tuples(
                st.sampled_from(["add", "delete"]),
                st.lists(st.integers(0, facts0.shape[0] - 1), min_size=1,
                         max_size=4),
            ),
            min_size=1, max_size=4,
        )
    )
    def run(script):
        eng = _engine(dic0, cap=512)
        state = eng.materialise_state(facts0, prog0)
        explicit = facts0
        for op, idxs in script:
            delta = facts0[np.asarray(sorted(set(idxs)))]
            explicit = apply_op(explicit, op, delta)
            (eng.add_facts if op == "add" else eng.delete_facts)(state, delta)
            _assert_clean(eng, state, (op, idxs))
            ref = materialise_rew(explicit, prog0, dic0.n_resources)
            got = set(pack(eng.state_triples(state)).tolist())
            assert got == set(pack(ref.triples()).tolist())

    run()


_MESH_SCRIPT = textwrap.dedent(
    """
    import numpy as np, jax
    from repro.core.engine_jax import JaxEngine, index_invariant_report
    from repro.core.materialise import materialise_rew
    from repro.core.triples import apply_op, pack
    from repro.data.generator import generate, sample_update_stream
    from repro.launch.mesh import make_engine_mesh

    assert len(jax.devices()) == 4, jax.devices()
    facts, prog, dic = generate(n_groups=2, group_size=3, n_spokes_per=1,
                                n_plain=15, hierarchy_depth=1, seed=3)
    events = sample_update_stream(facts, dic, n_events=3, batch=6, seed=3)
    for n_dev, route in ((1, None), (2, None), (4, None), (4, 256)):
        eng = JaxEngine(dic.n_resources, capacity=1 << 10, bind_cap=1 << 10,
                        out_cap=1 << 10, rewrite_cap=1 << 10,
                        mesh=make_engine_mesh(n_dev), route_cap=route,
                        seed_chunk=128)
        state = eng.materialise_state(facts, prog)
        assert index_invariant_report(state, eng.n_shards) == [], ("base", n_dev)
        explicit = facts
        for op, delta in events:
            explicit = apply_op(explicit, op, delta)
            (eng.add_facts if op == "add" else eng.delete_facts)(state, delta)
            probs = index_invariant_report(state, eng.n_shards)
            assert probs == [], (n_dev, route, op, probs)
            ref = materialise_rew(explicit, prog, dic.n_resources)
            got = set(pack(eng.state_triples(state)).tolist())
            assert got == set(pack(ref.triples()).tolist()), (n_dev, op)
    print("INDEX-INVARIANT-OK")
    """
)


@pytest.mark.slow
def test_index_invariant_device_count_invariant():
    """The per-shard invariant holds on 1/2/4 virtual devices, gather and
    owner-routed exchange alike."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=900,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "INDEX-INVARIANT-OK" in out.stdout

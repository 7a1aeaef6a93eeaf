"""The fused on-device fixpoint vs the host round loop and the oracle.

Three fronts:

* **Differential** — fused (default), host-loop (``fuse_rounds=False``) and
  the from-scratch REW materialisation agree after every event of an update
  stream, over the four profile shapes of tests/test_incremental_spmd.py
  (the 1/2/4-device matrix lives there, in the mesh subprocess script's
  ``*_nofuse`` cells).
* **Trace shape** — the registered ``fforward`` trace contains exactly ONE
  top-level while_loop (the fixpoint) and zero arena-length sorts; the
  dispatch count of a fused maintenance stream stays under the host loop's.
* **Attribution bugfixes riding along** — capacity-retry dispatches land in
  a distinct ``"retry"`` phase, an empty admitted batch presizes to the
  minimum delta width without booking ``wide_growth_restarts``, and the
  sticky wide-buffer fallback's narrow probe is keyed off epoch barriers
  (fallback exits after load drops even though the fused loop advances
  rounds on device).
"""

import numpy as np
import pytest

from repro.analysis import build_probe, count_sorts_at_least
from repro.core.engine_jax import CapacityError, JaxEngine
from repro.core.materialise import materialise_rew
from repro.core.triples import apply_op as _apply, pack
from repro.data.generator import generate, sample_update_stream


def _packset(spo):
    return set(pack(np.asarray(spo, np.int32).reshape(-1, 3)).tolist())


def _engine(dic, cap=1 << 11, **kw):
    return JaxEngine(
        dic.n_resources, capacity=cap, bind_cap=cap, out_cap=cap,
        rewrite_cap=cap, **kw,
    )


# same profile shapes as tests/test_incremental_spmd.py's _MODE_COMBOS
_COMBOS = [
    (dict(n_groups=1, group_size=5, n_spokes_per=2, n_plain=8,
          hierarchy_depth=0), 3, "clique_ish"),
    (dict(n_groups=2, group_size=3, n_spokes_per=1, n_plain=25,
          hierarchy_depth=3), 5, "chain_ish"),
    (dict(n_groups=2, group_size=3, n_spokes_per=1, n_plain=30,
          hierarchy_depth=1, chain_rules=True), 7, "dbpedia_ish"),
    (dict(n_groups=2, group_size=3, n_spokes_per=1, n_plain=15,
          hierarchy_depth=1, hometown_groups=1, hometown_size=5), 9,
     "uobm_ish"),
]


# ---------------------------------------------------------------------------
# differential: fused == host loop == from-scratch, per event
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "gen_kw, seed, _id", _COMBOS, ids=[c[-1] for c in _COMBOS]
)
def test_fused_vs_host_vs_scratch(gen_kw, seed, _id):
    facts, prog, dic = generate(**gen_kw, seed=seed)
    events = sample_update_stream(facts, dic, n_events=4, batch=8, seed=seed)
    engines = {
        "fused": _engine(dic, fuse_rounds=True),
        "host": _engine(dic, fuse_rounds=False),
    }
    states = {m: e.materialise_state(facts, prog) for m, e in engines.items()}
    explicit = facts
    for i, (op, delta) in enumerate(events):
        explicit = _apply(explicit, op, delta)
        ref = materialise_rew(explicit, prog, dic.n_resources)
        want = _packset(ref.triples())
        for m, e in engines.items():
            (e.add_facts if op == "add" else e.delete_facts)(states[m], delta)
            assert _packset(e.state_triples(states[m])) == want, (i, m, op)
            rep = e.state_rep(states[m])
            assert (rep[: ref.rep.shape[0]] == ref.rep).all(), (i, m, op)
    # the fused engine genuinely orchestrated on device: fewer dispatches
    # for the same work (the point of the subsystem)
    assert (
        engines["fused"].dispatches.total < engines["host"].dispatches.total
    ), (engines["fused"].dispatches.total, engines["host"].dispatches.total)


def _body_order_plans(rule, full, tombstone=False):
    """:func:`build_plans` with every plan in body order (each atom keeps
    the predicate it has there): the join order before plans started at
    their delta atom."""
    from repro.core import engine_jax as ej

    plans = []
    for i in [0] if full else range(len(rule.body)):
        specs, bound = [], set()
        for j, atom in enumerate(rule.body):
            cm, eq, b, f = ej._atom_static(atom, bound)
            if full:
                pred = ej.PRED_ALL
            else:
                pred = ej.PRED_OLD if j < i else (
                    ej.PRED_DELTA if j == i else ej.PRED_ALL
                )
            if tombstone:
                pred = ej.PRED_TDELTA if pred == ej.PRED_DELTA else ej.PRED_TSTORE
            count = not tombstone and (pred == ej.PRED_DELTA or (full and j == 0))
            specs.append(ej._AtomSpec(j, cm, eq, b, f, pred, count))
            bound |= {v for v, _ in b} | {v for v, _ in f}
        plans.append(specs)
    return plans


@pytest.mark.parametrize(
    "gen_kw, seed, _id", _COMBOS, ids=[c[-1] for c in _COMBOS]
)
def test_join_order_changes_no_match_and_no_count(gen_kw, seed, _id, monkeypatch):
    """Delta-first plans, fused and host-looped, and body-order plans give
    the same store, rho, derivations and rule applications after every
    event; the base run's counts equal the numpy reference's; and no join
    step of the delta-first engines probes the arena."""
    from repro.core import engine_jax, fused
    from repro.core.materialise import materialise

    facts, prog, dic = generate(**gen_kw, seed=seed)
    events = sample_update_stream(facts, dic, n_events=4, batch=8, seed=seed)
    engines = {
        "fused": _engine(dic, fuse_rounds=True),
        "host": _engine(dic, fuse_rounds=False),
        "body_order": _engine(dic, fuse_rounds=True),
    }

    def call(m, fn, *args):
        with monkeypatch.context() as mp:
            if m == "body_order":
                mp.setattr(engine_jax, "build_plans", _body_order_plans)
                mp.setattr(fused, "build_plans", _body_order_plans)
            return fn(*args)

    ref = materialise(facts, prog, dic.n_resources, mode="REW")
    states = {}
    for m, e in engines.items():
        states[m] = call(m, e.materialise_state, facts, prog)
        st = states[m].stats
        assert (st.derivations, st.rule_applications) == (
            ref.stats.derivations, ref.stats.rule_applications
        ), m
    for i, (op, delta) in enumerate(events):
        seen = {}
        for m, e in engines.items():
            call(m, e.add_facts if op == "add" else e.delete_facts, states[m], delta)
            st = states[m].stats
            seen[m] = (
                _packset(e.state_triples(states[m])),
                tuple(e.state_rep(states[m])),
                st.derivations, st.rule_applications,
            )
        assert seen["fused"] == seen["host"] == seen["body_order"], (i, op)
    for m in ("fused", "host"):
        st = states[m].stats
        assert st.arena_probe_joins == 0 and st.index_joins > 0, (m, st)


def test_fused_with_dedup_kernel_matches_scratch():
    """use_kernel=True swaps the in-loop argsorts for the counting-rank
    kernel; the fused fixpoint must be bit-equal to the oracle with it."""
    gen_kw, seed, _ = _COMBOS[0]
    facts, prog, dic = generate(**gen_kw, seed=seed)
    events = sample_update_stream(facts, dic, n_events=3, batch=6, seed=seed)
    eng = _engine(dic, cap=256, fuse_rounds=True, use_kernel=True)
    state = eng.materialise_state(facts, prog)
    explicit = facts
    for op, delta in events:
        explicit = _apply(explicit, op, delta)
        (eng.add_facts if op == "add" else eng.delete_facts)(state, delta)
        ref = materialise_rew(explicit, prog, dic.n_resources)
        assert _packset(eng.state_triples(state)) == _packset(ref.triples())


# ---------------------------------------------------------------------------
# trace shape: one while_loop, no arena sorts
# ---------------------------------------------------------------------------

def _traced(engine, state, name):
    from repro.compat import enable_x64

    from repro.core import incremental_spmd  # noqa: F401 (registers fns)
    from repro.core.engine_jax import AUDIT_REGISTRY

    with enable_x64():
        return dict(AUDIT_REGISTRY[name].builder(engine, state))


@pytest.mark.parametrize("name", ["fforward", "fwave"])
def test_fused_trace_is_one_while_loop(name):
    """The fused fn IS the fixpoint: exactly one while_loop at the top
    level (merge_pairs_jax nests its own pointer-jumping loops INSIDE the
    body — only the top level counts) and zero arena-length sorts anywhere
    (the index is maintained incrementally; rebuild stays outside)."""
    engine, state, _prog = build_probe("pex")
    jx = _traced(engine, state, name)[name]
    top_whiles = [e for e in jx.jaxpr.eqns if e.primitive.name == "while"]
    assert len(top_whiles) == 1, [e.primitive.name for e in jx.jaxpr.eqns]
    arena_rows = int(state.spo.shape[0])
    assert count_sorts_at_least(jx, arena_rows) == 0


# ---------------------------------------------------------------------------
# dispatch attribution across capacity retries
# ---------------------------------------------------------------------------

def test_retry_dispatches_get_their_own_phase():
    """_recover_capacity re-tags the counter before touching the state, so
    recovery dispatches never masquerade as work of the phase that
    overflowed — and the crosscheck admits the "retry" phase.  The tag
    that was live comes back once the recovery is over."""
    from repro.analysis import dispatch_crosscheck

    gen_kw, seed, _ = _COMBOS[0]
    facts, prog, dic = generate(**gen_kw, seed=seed)
    eng = _engine(dic, cap=256)
    state = eng.materialise_state(facts, prog)

    snap = eng._snapshot(state)
    seen = []
    restore = eng._restore
    eng._restore = lambda st, sn: (seen.append(eng.dispatches.phase),
                                   restore(st, sn))
    eng.dispatches.phase = "delete:wave"  # stale tag at overflow time
    eng._recover_capacity(state, snap, CapacityError("bind"))
    assert seen == ["retry"]
    assert eng.dispatches.phase == "delete:wave"
    assert state.stats.capacity_retries == 1
    eng.dispatches.phase = None

    assert dispatch_crosscheck(eng.dispatches, prog) == []


def test_forced_overflow_stream_reconciles():
    """An update stream that genuinely trips the capacity retry leaves a
    counter the static profile fully admits (retry phase included)."""
    from repro.analysis import dispatch_crosscheck

    facts, prog, dic = generate(
        n_groups=2, group_size=4, n_spokes_per=2, n_plain=60,
        hierarchy_depth=2, seed=11,
    )
    # wide caps large enough to converge, delta caps squeezed so the
    # maintenance stream must discover its width by overflow at least once
    eng = JaxEngine(
        dic.n_resources, capacity=1 << 11, bind_cap=1 << 11, out_cap=1 << 11,
        rewrite_cap=1 << 11, delta_out_cap=2,
    )
    state = eng.materialise_state(facts, prog)
    events = sample_update_stream(facts, dic, n_events=3, batch=16, seed=11)
    explicit = facts
    for op, delta in events:
        explicit = _apply(explicit, op, delta)
        (eng.add_facts if op == "add" else eng.delete_facts)(state, delta)
    ref = materialise_rew(explicit, prog, dic.n_resources)
    assert _packset(eng.state_triples(state)) == _packset(ref.triples())
    assert dispatch_crosscheck(eng.dispatches, prog) == []


# ---------------------------------------------------------------------------
# _presize_delta on an empty admitted batch
# ---------------------------------------------------------------------------

def test_empty_batch_presize_books_no_wide_growth():
    """A no-op epoch presizes from cardinality 0: the clamp keeps the delta
    width at its minimum instead of a 0-row presize the next phase would
    repair with a width-discovery restart booked on an idle epoch."""
    gen_kw, seed, _ = _COMBOS[1]
    facts, prog, dic = generate(**gen_kw, seed=seed)
    eng = _engine(dic, cap=512)
    state = eng.materialise_state(facts, prog)

    eng._presize_delta(0)
    assert eng.delta_out >= 1  # minimum pow2 width, not degenerate 0

    before = (
        state.stats.wide_growth_restarts, state.stats.capacity_retries,
        eng.delta_out, eng.delta_bind, eng.delta_rewrite,
    )
    eng.add_facts(state, np.zeros((0, 3), np.int32))
    eng.delete_facts(state, np.zeros((0, 3), np.int32))
    after = (
        state.stats.wide_growth_restarts, state.stats.capacity_retries,
        eng.delta_out, eng.delta_bind, eng.delta_rewrite,
    )
    assert before == after, (before, after)


# ---------------------------------------------------------------------------
# sticky fallback's narrow probe is epoch-keyed
# ---------------------------------------------------------------------------

def test_fallback_narrow_probe_keyed_off_epochs():
    """Once in the wide-buffer fallback, 4 epoch barriers after entry the
    next operation retries the narrow buffers — counted in operations, not
    rounds (the fused loop advances rounds on device, so any round-based
    schedule would stall at one tick per fixpoint)."""
    gen_kw, seed, _ = _COMBOS[0]
    facts, prog, dic = generate(**gen_kw, seed=seed)
    eng = _engine(dic, cap=512)
    state = eng.materialise_state(facts, prog)

    eng._delta_fallback = True  # as left by a delta-width overflow storm
    eng._fallback_since = None
    row = facts[:1]
    epochs_in_fallback = 0
    for _ in range(6):
        if not eng._delta_fallback:
            break
        epochs_in_fallback += 1
        eng.delete_facts(state, row)
        eng.add_facts(state, row)
    # load dropped (tiny updates): the probe fired after 4 epoch barriers
    # and fallback exited — it must not stay sticky forever
    assert not eng._delta_fallback
    assert epochs_in_fallback >= 2  # stayed wide through the window...
    assert eng._fallback_since is None  # ...and the clock reset on exit
    ref = materialise_rew(facts, prog, dic.n_resources)
    assert _packset(eng.state_triples(state)) == _packset(ref.triples())


# ---------------------------------------------------------------------------
# one rho change books rule_rewrites exactly once (fused exit re-run dedupe)
# ---------------------------------------------------------------------------

def test_remerge_booked_once_across_fused_exit():
    """A rho re-merge that rewrites a rule constant books ``rule_rewrites``
    exactly once (and ``rules_requeued`` once per changed rule), identically
    across the fused engine — whose rewrite-due exit round is nullified on
    device and re-run by the host, the historical double-booking hazard —
    the host round loop, and the numpy oracle.  All booking flows through
    the single ``_rewrite_program`` site, so the counters cannot diverge."""
    from repro.core.rules import parse_program
    from repro.core.terms import Dictionary

    dic = Dictionary()
    b, a = dic.intern(":b"), dic.intern(":a")  # b first: merge rep is b
    prog = parse_program(["(?x, :anchored, :a) <- (?x, :q, :a)"], dic)
    q = dic.id_of(":q")
    u = dic.intern(":u")
    for i in range(20):
        dic.intern(f":pad{i}")
    facts = np.asarray([[u, q, b]], np.int32)
    delta = np.asarray([[a, 1, b]], np.int32)  # owl:sameAs merge a -> b

    ref = materialise_rew(
        np.concatenate([facts, delta]), prog, dic.n_resources
    )
    want = _packset(ref.triples())

    booked = {}
    for label, fuse in (("fused", True), ("host", False)):
        eng = _engine(dic, cap=256, fuse_rounds=fuse)
        st = eng.materialise_state(facts, prog)
        before = (st.stats.rule_rewrites, st.stats.rules_requeued)
        eng.add_facts(st, delta)
        booked[label] = (st.stats.rule_rewrites - before[0],
                         st.stats.rules_requeued - before[1])
        assert _packset(eng.state_triples(st)) == want, label
        # ... and the re-merge was evaluated anchored, not whole-rule
        assert st.stats.remerge_targeted >= 1, label
        assert st.stats.full_plan_evals == 0, label
    assert booked["fused"] == booked["host"] == (
        ref.stats.rule_rewrites, ref.stats.rules_requeued
    ) == (1, 1)

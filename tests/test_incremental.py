"""Incremental materialisation maintenance vs the from-scratch oracle.

The oracle is Theorem-1 style: after any sequence of add/delete updates, the
incremental state must equal the from-scratch REW materialisation of the
updated explicit fact set — same rho (min-ID representatives are
order-independent, so reps must match exactly), same normal-form store, and
therefore the same expansion T^rho.
"""

import numpy as np
import pytest

from repro.core.incremental import (
    add_facts,
    delete_facts,
    materialise_incremental,
    normal_forms,
)
from repro.core.materialise import expand, materialise_rew
from repro.core.triples import pack
from repro.data.datasets import pex, single_clique
from repro.data.generator import generate, sample_update_stream


def _packset(spo):
    return set(pack(np.asarray(spo, np.int32).reshape(-1, 3)).tolist())


def _explicit_apply(explicit, op, delta):
    """Oracle-side explicit-set bookkeeping (same semantics as the state)."""
    from repro.core.triples import apply_op

    return apply_op(explicit, op, delta)


def assert_matches_scratch(state, explicit, program, n_resources, expand_check=False):
    ref = materialise_rew(explicit, program, n_resources)
    assert _packset(state.triples()) == _packset(ref.triples())
    assert (state.rep[: ref.rep.shape[0]] == ref.rep).all()
    # the incremental rep may be longer (grown by adds); the tail is identity
    tail = state.rep[ref.rep.shape[0] :]
    assert (tail == np.arange(ref.rep.shape[0], state.rep.shape[0])).all()
    if expand_check:
        lhs = expand(state.triples(), state.rep)
        rhs = expand(ref.triples(), ref.rep)
        assert lhs == rhs


# ---------------------------------------------------------------------------
# additions
# ---------------------------------------------------------------------------

def test_add_matches_scratch_pex():
    facts, prog, dic = pex()
    base, extra = facts[:1], facts[1:]
    state = materialise_incremental(base, prog, dic.n_resources)
    add_facts(state, extra)
    assert_matches_scratch(state, facts, prog, dic.n_resources, expand_check=True)


def test_add_new_resources_grows_rep():
    facts, prog, dic = pex()
    state = materialise_incremental(facts, prog, dic.n_resources)
    new_id = dic.n_resources + 5
    delta = np.asarray([[new_id, facts[0, 1], facts[0, 2]]], np.int32)
    add_facts(state, delta)
    all_facts = np.concatenate([facts, delta], axis=0)
    assert_matches_scratch(state, all_facts, prog, new_id + 1)


def test_add_empty_delta_is_noop():
    facts, prog, dic = pex()
    state = materialise_incremental(facts, prog, dic.n_resources)
    before = _packset(state.triples())
    add_facts(state, np.zeros((0, 3), np.int32))
    add_facts(state, facts)  # re-adding explicit facts is also a no-op
    assert _packset(state.triples()) == before
    assert_matches_scratch(state, facts, prog, dic.n_resources)


# ---------------------------------------------------------------------------
# deletions and clique splitting
# ---------------------------------------------------------------------------

def test_delete_sameas_edge_splits_clique():
    facts, prog, dic = single_clique(6)  # a0~a1~...~a5, one clique
    state = materialise_incremental(facts, prog, dic.n_resources)
    mid = facts[2:3]  # a2 ~ a3: splits into {a0,a1,a2} and {a3,a4,a5}
    delete_facts(state, mid)
    remaining = np.concatenate([facts[:2], facts[3:]], axis=0)
    assert_matches_scratch(
        state, remaining, prog, dic.n_resources, expand_check=True
    )
    # the split is observable: two cliques instead of one
    reps = np.unique(state.rep[np.unique(facts[:, [0, 2]])])
    assert reps.shape[0] == 2


def test_delete_derived_sameas_support():
    """Deleting one :idProp edge must split the rule-derived clique."""
    facts, prog, dic = generate(
        n_groups=3, group_size=4, n_spokes_per=2, n_plain=30, hierarchy_depth=2
    )
    state = materialise_incremental(facts, prog, dic.n_resources)
    idp = dic.id_of(":idProp")
    id_rows = np.flatnonzero(facts[:, 1] == idp)
    delta = facts[id_rows[:2]]
    delete_facts(state, delta)
    remaining = facts[~np.isin(pack(facts), pack(delta))]
    assert_matches_scratch(state, remaining, prog, dic.n_resources)


def test_delete_empty_and_unknown_delta_is_noop():
    facts, prog, dic = pex()
    state = materialise_incremental(facts, prog, dic.n_resources)
    before = _packset(state.triples())
    delete_facts(state, np.zeros((0, 3), np.int32))
    delete_facts(state, np.asarray([[9, 9, 9]], np.int32))  # not explicit
    assert _packset(state.triples()) == before
    assert_matches_scratch(state, facts, prog, dic.n_resources)


def test_delete_everything():
    for ds in (lambda: pex(), lambda: single_clique(5)):
        facts, prog, dic = ds()
        state = materialise_incremental(facts, prog, dic.n_resources)
        delete_facts(state, facts)
        assert state.triples().shape[0] == 0
        assert (state.rep == np.arange(dic.n_resources)).all()
        assert_matches_scratch(
            state, np.zeros((0, 3), np.int32), prog, dic.n_resources
        )


def test_clique_split_property():
    """Property-style: deleting ANY random subset of sameAs edges (plus the
    empty and full subsets) and re-materialising equals the incremental
    result — including payload triples hanging off the clique."""
    from repro.data.datasets import clique_with_spokes

    facts, prog, dic = clique_with_spokes(7, 4)
    sa_rows = np.flatnonzero(facts[:, 1] == dic.id_of("owl:sameAs"))
    rng = np.random.default_rng(42)
    subsets = [np.zeros(0, np.int64), sa_rows]  # edge cases first
    for _ in range(6):
        m = int(rng.integers(1, sa_rows.shape[0] + 1))
        subsets.append(rng.choice(sa_rows, size=m, replace=False))
    for sub in subsets:
        state = materialise_incremental(facts, prog, dic.n_resources)
        delta = facts[np.asarray(sub, dtype=np.int64)]
        delete_facts(state, delta)
        remaining = (
            facts[~np.isin(pack(facts), pack(delta))] if delta.shape[0] else facts
        )
        assert_matches_scratch(state, remaining, prog, dic.n_resources)


def test_add_then_delete_roundtrip():
    """add(D); delete(D) returns to the original materialisation."""
    facts, prog, dic = generate(
        n_groups=2, group_size=3, n_spokes_per=1, n_plain=20, hierarchy_depth=1
    )
    state = materialise_incremental(facts, prog, dic.n_resources)
    before = _packset(state.triples())
    rep_before = state.rep.copy()
    idp = dic.id_of(":idProp")
    # a bridge edge that merges two previously-distinct cliques
    g0 = facts[facts[:, 1] == idp][0, 0]
    g1 = facts[facts[:, 1] == idp][-1, 0]
    vid = dic.intern(":bridge")
    bridge = np.asarray(
        [[g0, idp, vid], [g1, idp, vid]], np.int32
    )
    add_facts(state, bridge)
    assert _packset(state.triples()) != before  # the merge happened
    delete_facts(state, bridge)
    assert _packset(state.triples()) == before
    assert (state.rep[: rep_before.shape[0]] == rep_before).all()


# ---------------------------------------------------------------------------
# generated update streams (the acceptance-criteria oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "gen_kw, seed",
    [
        (dict(n_groups=3, group_size=3, n_spokes_per=2, n_plain=40,
              hierarchy_depth=2), 0),
        (dict(n_groups=2, group_size=4, n_spokes_per=1, n_plain=30,
              hierarchy_depth=1, chain_rules=True), 1),
        (dict(n_groups=4, group_size=3, n_spokes_per=2, n_plain=25,
              hierarchy_depth=2, hometown_groups=1, hometown_size=5), 2),
    ],
    ids=["claros_ish", "chains_ish", "uobm_ish"],
)
def test_update_streams_match_scratch(gen_kw, seed):
    facts, prog, dic = generate(**gen_kw, seed=seed)
    events = sample_update_stream(
        facts, dic, n_events=5, batch=10, seed=seed
    )
    state = materialise_incremental(facts, prog, dic.n_resources)
    explicit = facts
    for op, delta in events:
        explicit = _explicit_apply(explicit, op, delta)
        if op == "add":
            add_facts(state, delta)
        else:
            delete_facts(state, delta)
        assert_matches_scratch(state, explicit, prog, dic.n_resources)


def test_delete_then_readd_in_one_stream():
    """delete(D); add(D) inside one stream restores store and rho exactly."""
    facts, prog, dic = generate(
        n_groups=2, group_size=3, n_spokes_per=1, n_plain=20, hierarchy_depth=1
    )
    state = materialise_incremental(facts, prog, dic.n_resources)
    before = _packset(state.triples())
    rep_before = state.rep.copy()
    idp = dic.id_of(":idProp")
    delta = facts[np.flatnonzero(facts[:, 1] == idp)[:3]]
    delete_facts(state, delta)
    assert _packset(state.triples()) != before  # the split happened
    add_facts(state, delta)
    assert _packset(state.triples()) == before
    assert (state.rep == rep_before).all()
    assert_matches_scratch(state, facts, prog, dic.n_resources)


# ---------------------------------------------------------------------------
# differential fuzz harness: sharded incremental vs from-scratch oracle
# ---------------------------------------------------------------------------

def _run_differential_stream(gen_kw, seed, n_events, batch, engine=True):
    """Apply a sampled update stream and assert oracle equality per batch.

    ``engine=True`` drives the sharded device path
    (:meth:`JaxEngine.add_facts` / ``delete_facts``); ``engine=False`` the
    host reference subsystem.  Either way the result after EVERY batch must
    equal from-scratch ``materialise_rew`` on the updated explicit set.
    """
    facts, prog, dic = generate(**gen_kw, seed=seed)
    events = sample_update_stream(
        facts, dic, n_events=n_events, batch=batch, seed=seed
    )
    stream_desc = [(op, delta.shape[0]) for op, delta in events]
    explicit = facts
    if engine:
        from repro.core.engine_jax import JaxEngine

        eng = JaxEngine(
            dic.n_resources, capacity=1 << 11, bind_cap=1 << 11,
            out_cap=1 << 11, rewrite_cap=1 << 11,
        )
        state = eng.materialise_state(facts, prog)
        for i, (op, delta) in enumerate(events):
            explicit = _explicit_apply(explicit, op, delta)
            (eng.add_facts if op == "add" else eng.delete_facts)(state, delta)
            ref = materialise_rew(explicit, prog, dic.n_resources)
            got, want = _packset(eng.state_triples(state)), _packset(ref.triples())
            assert got == want, (
                f"store diverged after event {i} of {stream_desc}: "
                f"+{len(got - want)}/-{len(want - got)} triples"
            )
            rep = eng.state_rep(state)
            assert (rep[: ref.rep.shape[0]] == ref.rep).all(), (
                f"rho diverged after event {i} of {stream_desc}"
            )
            tail = rep[ref.rep.shape[0]:]
            assert (tail == np.arange(ref.rep.shape[0], rep.shape[0])).all()
    else:
        state = materialise_incremental(facts, prog, dic.n_resources)
        for i, (op, delta) in enumerate(events):
            explicit = _explicit_apply(explicit, op, delta)
            (add_facts if op == "add" else delete_facts)(state, delta)
            assert_matches_scratch(state, explicit, prog, dic.n_resources)


_FUZZ_COMBOS = [
    (dict(n_groups=2, group_size=3, n_spokes_per=1, n_plain=15,
          hierarchy_depth=1), 7, 4, 8, True),
    (dict(n_groups=1, group_size=4, n_spokes_per=2, n_plain=5,
          hierarchy_depth=0), 11, 5, 6, True),
    (dict(n_groups=3, group_size=2, n_spokes_per=1, n_plain=25,
          hierarchy_depth=2, chain_rules=True), 13, 4, 10, False),
    (dict(n_groups=2, group_size=3, n_spokes_per=2, n_plain=20,
          hierarchy_depth=1, hometown_groups=1, hometown_size=4), 17, 5, 8,
     False),
]


@pytest.mark.parametrize(
    "gen_kw, seed, n_events, batch, engine", _FUZZ_COMBOS,
    ids=["eng_basic", "eng_dense", "host_chains", "host_hometown"],
)
def test_fuzz_fallback_streams(gen_kw, seed, n_events, batch, engine):
    """Seeded differential fuzz that runs without hypothesis installed."""
    _run_differential_stream(gen_kw, seed, n_events, batch, engine=engine)


try:
    from hypothesis import HealthCheck, given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # container without the test extra: fallback fuzz only
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    _stream_params = given(
        seed=st.integers(0, 2**16),
        n_events=st.integers(1, 5),
        batch=st.integers(2, 12),
        n_groups=st.integers(1, 3),
        group_size=st.integers(2, 4),
        n_plain=st.integers(0, 25),
        hierarchy_depth=st.integers(0, 2),
    )
    _fuzz_settings = dict(
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
    )

    def _fuzz_test(max_examples):
        """A fresh test function per budget: hypothesis applies ``settings``
        once per function object."""

        def fuzz(seed, n_events, batch, n_groups, group_size, n_plain,
                 hierarchy_depth):
            gen_kw = dict(
                n_groups=n_groups, group_size=group_size, n_spokes_per=1,
                n_plain=n_plain, hierarchy_depth=hierarchy_depth,
            )
            _run_differential_stream(gen_kw, seed, n_events, batch, engine=True)

        return _stream_params(
            settings(max_examples=max_examples, **_fuzz_settings)(fuzz)
        )

    # quick budget for tier-1; hypothesis shrinks a failing case to a
    # minimal stream (fewest events, smallest batches, tiniest graph)
    test_fuzz_update_stream_differential = _fuzz_test(10)

    # nightly tier: larger example budget, deselectable via -m "not slow"
    test_fuzz_update_stream_differential_nightly = pytest.mark.slow(
        _fuzz_test(100)
    )


# ---------------------------------------------------------------------------
# kernel-batched normal forms + engine integration
# ---------------------------------------------------------------------------

def test_normal_forms_kernel_parity():
    rng = np.random.default_rng(0)
    rep = np.arange(300, dtype=np.int32)
    rep[rng.integers(0, 300, size=60)] = rng.integers(0, 50, size=60)
    from repro.core.uf import compress_np

    rep = compress_np(rep)
    spo = rng.integers(0, 300, size=(200, 3)).astype(np.int32)
    np_out = normal_forms(spo, rep, use_kernel=False)
    k_out = normal_forms(spo, rep, use_kernel=True)
    assert (np_out == k_out).all()


def test_rewrite_owner_kernel_parity():
    """Fused (normal form, owner shard) matches the numpy route keys."""
    from repro.core.uf import compress_np
    from repro.kernels.ops import rewrite_owner

    rng = np.random.default_rng(1)
    rep = np.arange(300, dtype=np.int32)
    rep[rng.integers(0, 300, size=60)] = rng.integers(0, 50, size=60)
    rep = compress_np(rep)
    spo = rng.integers(0, 300, size=(200, 3)).astype(np.int32)
    for n_shards in (1, 4):
        nf, owner = rewrite_owner(spo, rep, n_shards)
        assert (np.asarray(nf) == rep[spo]).all()
        assert (np.asarray(owner) == rep[spo][:, 0] % n_shards).all()


def test_delete_with_kernel_normal_forms():
    facts, prog, dic = single_clique(5)
    state = materialise_incremental(
        facts, prog, dic.n_resources, use_kernel=True
    )
    delete_facts(state, facts[1:2])
    remaining = np.concatenate([facts[:1], facts[2:]], axis=0)
    assert_matches_scratch(state, remaining, prog, dic.n_resources)


def test_engine_materialise_incremental():
    from repro.core.engine_jax import JaxEngine

    facts, prog, dic = pex()
    updates = [
        ("add", np.asarray([[facts[0, 0], facts[0, 1], facts[2, 2]]], np.int32)),
        ("delete", facts[1:2]),
    ]
    eng = JaxEngine(
        dic.n_resources, capacity=256, bind_cap=256, out_cap=256, rewrite_cap=256
    )
    spo, rep, stats = eng.materialise_incremental(facts, prog, updates)

    explicit = facts
    for op, delta in updates:
        explicit = _explicit_apply(explicit, op, delta)
    ref = materialise_rew(explicit, prog, dic.n_resources)
    assert _packset(spo) == _packset(ref.triples())
    assert (rep == ref.rep).all()

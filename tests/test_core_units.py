"""Unit tests for the core substrate: dictionary, packing, arena, union-find."""

import numpy as np
import pytest

from repro.core import terms
from repro.core.rules import Program, Rule, parse_program, parse_rule
from repro.core.terms import Dictionary, SAME_AS, var
from repro.core.triples import TripleArena, pack, unpack
from repro.core.uf import (
    FrozenRho,
    clique_members,
    clique_sizes,
    compress_np,
    merge_pairs_jax,
    merge_pairs_np,
    split_cliques,
)


def test_dictionary_roundtrip():
    d = Dictionary()
    a = d.intern(":a")
    b = d.intern(":b")
    assert d.intern(":a") == a != b
    assert d.lookup(a) == ":a"
    assert d.id_of("owl:sameAs") == SAME_AS
    assert ":a" in d and ":zzz" not in d


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    spo = rng.integers(0, terms.MAX_ID, size=(1000, 3)).astype(np.int32)
    assert (unpack(pack(spo)) == spo).all()
    # packing is order-preserving lexicographically
    keys = pack(spo)
    order = np.argsort(keys)
    rows = spo[order]
    as_tuples = [tuple(r) for r in rows]
    assert as_tuples == sorted(as_tuples)


def test_arena_add_dedup_and_mark():
    a = TripleArena(capacity=2)
    added = a.add_batch(np.array([[1, 2, 3], [1, 2, 3], [4, 5, 6]], np.int32))
    assert added.shape[0] == 2
    assert a.total == 2 and a.unmarked == 2
    # re-adding is a no-op
    assert a.add_batch(np.array([[4, 5, 6]], np.int32)).shape[0] == 0
    # marking hides from matching but keeps the row (paper: mark, don't delete)
    a.mark_rows(np.array([0]))
    assert a.total == 2 and a.unmarked == 1
    assert not a.contains(np.array([[1, 2, 3]]))[0]
    assert a.contains(np.array([[4, 5, 6]]))[0]
    # growth across capacity boundary
    big = np.stack([np.arange(7, 107), np.full(100, 2), np.arange(7, 107)], axis=1)
    assert a.add_batch(big.astype(np.int32)).shape[0] == 100
    assert a.unmarked == 101


def test_rewrite_sweep_marks_and_returns():
    a = TripleArena()
    a.add_batch(np.array([[5, 2, 5], [7, 2, 8]], np.int32))
    rep = np.arange(10, dtype=np.int32)
    rep[7] = 3  # 7 merged into 3
    rw = a.rewrite_sweep(rep)
    assert rw.tolist() == [[3, 2, 8]]
    assert a.unmarked == 1  # <5,2,5> untouched, <7,2,8> marked
    assert a.total == 2


def test_union_find_min_hooking_deterministic():
    rep = np.arange(10, dtype=np.int32)
    pairs = np.array([[3, 7], [7, 9], [2, 9], [5, 4]], np.int32)
    rep1, n1 = merge_pairs_np(rep.copy(), pairs)
    # same pairs in any order give the same result
    rep2, n2 = merge_pairs_np(rep.copy(), pairs[::-1])
    assert (rep1 == rep2).all() and n1 == n2 == 4
    # clique {2,3,7,9} -> rep 2; {4,5} -> 4
    assert rep1[3] == rep1[7] == rep1[9] == rep1[2] == 2
    assert rep1[5] == rep1[4] == 4
    sizes = clique_sizes(rep1)
    assert sizes[2] == 4 and sizes[4] == 2 and sizes[0] == 1
    mem = clique_members(rep1)
    assert mem[2].tolist() == [2, 3, 7, 9]


def test_union_find_chain_and_cycle():
    rep = np.arange(6, dtype=np.int32)
    # chain 0-1, 1-2, 2-3, 3-0 (cycle) must not loop forever
    pairs = np.array([[0, 1], [1, 2], [2, 3], [3, 0]], np.int32)
    rep, n = merge_pairs_np(rep, pairs)
    assert (rep[:4] == 0).all() and n == 3


def test_union_find_jax_matches_np():
    rng = np.random.default_rng(1)
    n = 200
    for trial in range(5):
        pairs = rng.integers(0, n, size=(50, 2)).astype(np.int32)
        rep_np, _ = merge_pairs_np(np.arange(n, dtype=np.int32), pairs)
        valid = np.ones(pairs.shape[0], dtype=bool)
        # pad with garbage to exercise the mask
        pad = rng.integers(0, n, size=(13, 2)).astype(np.int32)
        pairs_j = np.concatenate([pairs, pad])
        valid_j = np.concatenate([valid, np.zeros(13, bool)])
        rep_j = np.asarray(
            merge_pairs_jax(
                np.arange(n, dtype=np.int32), pairs_j.astype(np.int32), valid_j
            )
        )
        assert (compress_np(rep_j) == rep_np).all(), trial


def test_split_cliques_semantics_pinned():
    """Regression pin for the serving refactor: suspect representatives (and
    only representatives) revert their whole clique to singletons; everything
    else — non-roots, singletons, empty suspect sets — is a no-op."""
    rep = compress_np(np.array([0, 0, 0, 3, 3, 5], np.int32))
    out = split_cliques(rep, np.array([0]))
    assert out.tolist() == [0, 1, 2, 3, 3, 5]
    assert rep.tolist() == [0, 0, 0, 3, 3, 5]  # input untouched (copy)
    # a non-representative member names no clique: no-op
    assert split_cliques(rep, np.array([1])).tolist() == rep.tolist()
    # a singleton representative: no-op
    assert split_cliques(rep, np.array([5])).tolist() == rep.tolist()
    # empty suspect set: no-op (identity object semantics not required)
    assert split_cliques(rep, np.zeros(0, np.int64)).tolist() == rep.tolist()
    # splitting every clique yields the identity map
    assert split_cliques(rep, np.array([0, 3])).tolist() == list(range(6))


def test_epoch_ok_tombstone_visibility_pinned():
    """Regression pin for the serving refactor: the tombstone predicates
    match the PRE-deletion store (tombstoned rows stay join candidates, like
    DRed matching deleted facts against T), while the forward predicates
    ignore ``tomb`` entirely and see only live epochs."""
    import jax.numpy as jnp

    from repro.core.engine_jax import (
        PRED_ALL,
        PRED_DELTA,
        PRED_OLD,
        PRED_TDELTA,
        PRED_TSTORE,
        _epoch_ok,
    )

    # rows: free, old live, marked, tombstoned wave 1, fresh live
    epoch = jnp.asarray([-1, 0, 1, 2, 2])
    marked = jnp.asarray([False, False, True, False, False])
    tomb = jnp.asarray([-1, 0, 1, 1, -1])
    r = 2

    def ok(pred):
        return np.asarray(_epoch_ok(epoch, marked, tomb, r, pred)).tolist()

    # pre-deletion store: every unmarked, allocated row — INCLUDING rows
    # already tombstoned this pass
    assert ok(PRED_TSTORE) == [False, True, False, True, True]
    # wave delta: tombstoned exactly in wave r-1
    assert ok(PRED_TDELTA) == [False, False, False, True, False]
    # forward discipline is blind to tombstones (the tomb==-1 invariant is
    # restored before any forward round runs)
    assert ok(PRED_OLD) == [False, True, False, False, False]
    assert ok(PRED_DELTA) == [False, False, False, False, False]  # row 2 marked
    assert ok(PRED_ALL) == [False, True, False, False, False]


def test_frozen_rho_view_matches_uf_helpers():
    raw = np.array([0, 0, 1, 3, 3, 5], np.int32)  # 2 -> 1 -> 0 chain
    fr = FrozenRho(raw)
    ref = compress_np(raw)
    assert (fr.rep == ref).all()
    assert not fr.rep.flags.writeable
    assert (fr.sizes == clique_sizes(ref)).all()
    want_members = clique_members(ref)
    assert set(fr.members) == set(want_members)
    for k, v in want_members.items():
        assert fr.members[k].tolist() == v.tolist()
    # the expansion tables are cached, not recomputed per query
    assert fr.members is fr.members and fr.sizes is fr.sizes
    assert fr.normalise(np.array([2, 4])).tolist() == [0, 3]
    assert len(fr) == 6


def test_rule_parse_and_rewrite():
    d = Dictionary()
    r = parse_rule("(?x, owl:sameAs, :USA) <- (:Obama, :presidentOf, ?x)", d)
    assert r.head[0] == var(1) and r.head[1] == SAME_AS
    rep = np.arange(len(d), dtype=np.int32)
    rep[d.id_of(":USA")] = d.id_of(":Obama")
    rr = r.rewrite(rep)
    assert rr.head[2] == d.id_of(":Obama")
    assert rr.body == r.body  # body had no :USA
    prog, changed = Program([r]).rewrite(rep)
    assert changed == [0]
    prog2, changed2 = prog.rewrite(rep)
    assert changed2 == []


def test_unsafe_rule_rejected():
    with pytest.raises(ValueError):
        Rule((var(1), SAME_AS, var(2)), ((var(1), 5, 6),))


def test_probe_boundary_key_no_sentinel_alias():
    """Satellite bugfix pin: an INVALID probe slot must never hit a store
    row, even when the garbage in the slot packs to KEY_MAX - 1 — the key
    of <2^21-1, 2^21-1, 2^21-2>, which raw (non-dictionary) engine inputs
    can legitimately contain.  The old code parked invalid probes at the
    KEY_MAX - 1 sentinel, so such a row was spuriously matched (and e.g.
    tombstoned by _seed_tombs)."""
    import jax.numpy as jnp

    from repro.compat import enable_x64
    from repro.core.engine_jax import I32, KEY_MAX
    from repro.core.incremental_spmd import _probe_index

    m = (1 << 21) - 1
    boundary = np.asarray([m, m, m - 1], np.int32)  # packs to KEY_MAX - 1
    with enable_x64():
        spo = jnp.asarray(np.stack([[1, 2, 3], boundary]), I32)
        keys = np.array([pack(np.asarray([[1, 2, 3]], np.int64))[0],
                         np.int64((1 << 63) - 2)])
        order = np.argsort(keys)
        sorted_keys = jnp.asarray(keys[order])
        sort_perm = jnp.asarray(order.astype(np.int32))
        select = jnp.asarray([True, True])
        # one valid query for the boundary row, one INVALID slot holding the
        # exact same garbage triple
        queries = jnp.asarray(np.stack([boundary, boundary]), I32)
        qvalid = jnp.asarray([True, False])
        rows, hit = _probe_index(sorted_keys, sort_perm, select, queries, qvalid)
        assert np.asarray(hit).tolist() == [True, False]
        assert int(np.asarray(rows)[0]) == 1
        assert int(np.asarray(sorted_keys)[1]) == (1 << 63) - 2  # KEY_MAX - 1 real


def test_probe_respects_select_mask():
    """A probe hit on a live row excluded by ``select`` (e.g. already
    tombstoned) reports no hit."""
    import jax.numpy as jnp

    from repro.compat import enable_x64
    from repro.core.engine_jax import I32, KEY_MAX
    from repro.core.incremental_spmd import _probe_index

    with enable_x64():
        rows_np = np.asarray([[1, 2, 3], [4, 5, 6]], np.int32)
        keys = np.sort(pack(rows_np.astype(np.int64)))
        sorted_keys = jnp.asarray(keys)
        sort_perm = jnp.asarray(np.argsort(pack(rows_np.astype(np.int64))).astype(np.int32))
        queries = jnp.asarray(rows_np, I32)
        qvalid = jnp.asarray([True, True])
        rows, hit = _probe_index(
            sorted_keys, sort_perm, jnp.asarray([False, True]), queries, qvalid
        )
        assert np.asarray(hit).tolist() == [False, True]


def test_compact_is_stable_partition():
    """_compact packs valid rows front, stably, without sorting; overflow
    flags valid rows beyond cap; tail rows stay masked."""
    import jax.numpy as jnp

    from repro.compat import enable_x64
    from repro.core.engine_jax import _compact

    with enable_x64():
        col = jnp.asarray(np.arange(10, dtype=np.int32))
        valid = jnp.asarray([False, True, True, False, True, False, True, True, False, True])
        out, ov_valid, overflow = _compact({"c": col}, valid, 8)
        assert np.asarray(out["c"])[np.asarray(ov_valid)].tolist() == [1, 2, 4, 6, 7, 9]
        assert not bool(overflow)
        out, ov_valid, overflow = _compact({"c": col}, valid, 4)
        assert np.asarray(out["c"])[np.asarray(ov_valid)].tolist() == [1, 2, 4, 6]
        assert bool(overflow)


def test_index_invariant_report_catches_corruption():
    """The invariant checker itself must flag a broken index."""
    from repro.core.engine_jax import JaxEngine, index_invariant_report
    from repro.data.datasets import pex

    facts, prog, dic = pex()
    eng = JaxEngine(dic.n_resources, capacity=64, bind_cap=64, out_cap=64,
                    rewrite_cap=64)
    state = eng.materialise_state(facts, prog)
    assert index_invariant_report(state) == []
    import jax.numpy as jnp

    from repro.compat import enable_x64

    with enable_x64():
        state.sorted_keys = state.sorted_keys.at[0].set(jnp.int64(12345))
    assert index_invariant_report(state) != []


def test_delta_growth_clamped_and_eviction_scoped():
    """Review pins: (1) delta caps never double past their wide caps (the
    periodic narrow probe must not re-grow + recompile forever on
    store-scale workloads); (2) eviction after growth is family-precise
    for tagged keys but still value-matches derived-width keys (padbuf /
    process / squeeze), which would otherwise leak executables."""
    from repro.core.engine_jax import JaxEngine

    eng = JaxEngine(10, capacity=64, bind_cap=1 << 13, out_cap=1 << 13,
                    rewrite_cap=1 << 13)
    assert eng.delta_bind == 1 << 13  # floor == wide here
    eng._grow_for("delta_bind")
    assert eng.delta_bind == 1 << 13  # clamped at bind_cap
    assert eng._delta_fallback
    eng._grow_for("bind")  # wide grows (fallback active -> x4)
    assert eng.bind_cap == 1 << 15
    eng._grow_for("delta_bind")  # now below wide again: doubles
    assert eng.delta_bind == 1 << 14

    # eviction scoping
    eng._fns = {
        ("plan", 0, 0, "delta", (), (), ("bind", 1 << 14), ("out", 1 << 13)): 1,
        ("plan", 0, 0, "full", (), (), ("bind", 1 << 15), ("out", 1 << 13)): 2,
        ("padbuf", 1 << 14): 3,
        ("process", 1 << 14, ("rewrite", 4096), ("route", None),
         ("out", 1 << 13), ("pair", 4096)): 4,
        ("squeeze", 123, ("out", 1 << 13)): 5,
    }
    eng._grow_for("delta_bind")  # 1<<14 -> 1<<15, records ("bind", 1<<14)
    assert eng.delta_bind == 1 << 15
    keys = set(eng._fns.values())
    # the delta-bind plan fn and the derived-width padbuf/process entries
    # at the outgrown value are gone; the wide plan fn and unrelated
    # squeeze survive (no ("out", ...) growth happened)
    assert keys == {2, 5}


def test_member_rejects_key_max_padding_match():
    """The all-max-ID triple packs to KEY_MAX (the padding sentinel, reserved);
    _member must not report it present by matching index padding."""
    import jax.numpy as jnp

    from repro.compat import enable_x64
    from repro.core.engine_jax import I32
    from repro.core.incremental_spmd import _member

    m = (1 << 21) - 1
    with enable_x64():
        sorted_keys = jnp.asarray(
            np.array([pack(np.asarray([[1, 2, 3]], np.int64))[0],
                      np.int64((1 << 63) - 1)])  # one live key + padding
        )
        q = jnp.asarray(np.stack([[1, 2, 3], [m, m, m]]), I32)
        qv = jnp.asarray([True, True])
        hit = _member(sorted_keys, q, qv, axis=None)
        assert np.asarray(hit).tolist() == [True, False]


def test_head_may_rederive_pre_post_split_mapping():
    """The ISSUE 5 satellite-2 corner, pinned at the unit level: overdelete
    masks (and the extracted tombstone rows) hold PRE-split normal forms,
    while the rule is rewritten under the POST-split rho — a head constant
    that is a non-representative member of a split clique must be collapsed
    through the pre-deletion rho before matching.  The naive post-split
    check would skip the rule and lose a restorable fact."""
    from repro.core.incremental_spmd import _head_bindings, _head_may_rederive

    # pre-split clique {1, 2, 3} with representative 1; resources 4 and 5
    # are singletons.  Post-split, constant 3 reverts to itself.
    rep_old = np.asarray([0, 1, 1, 1, 4, 5], np.int32)
    # the overdeleted instance, normal under the PRE-split rho: its object
    # slot holds the old representative 1, not the member 3
    od = np.asarray([[5, 4, 1]], np.int32)
    od_mask = np.zeros((3, 6), bool)
    for pos in range(3):
        od_mask[pos][od[:, pos]] = True
    rule = Rule((-1, 4, 3), ((-1, 5, -2),))  # head (?x, :p4, :c3) post-split

    assert _head_may_rederive(rule, od_mask, rep_old)
    assert not od_mask[2][3]  # the naive post-split lookup would say False

    # the exact row-wise filter agrees and extracts the ?x binding
    bind = _head_bindings(rule, od, rep_old)
    assert bind.tolist() == [[5]]


def test_head_bindings_eq_vars_dedup_and_const_head():
    """_head_bindings semantics: repeated head variables filter row-wise,
    bindings deduplicate, mismatching constants drop rows, and a
    variable-free head returns None (the whole-rule fallback signal)."""
    from repro.core.incremental_spmd import _head_bindings

    rep = np.arange(12, dtype=np.int32)
    od = np.asarray(
        [[7, 4, 7], [7, 4, 8], [9, 4, 9], [7, 4, 7], [7, 5, 7]], np.int32
    )
    # head (?x, :p4, ?x): only rows with s == o and p == 4, deduplicated
    rule_eq = Rule((-1, 4, -1), ((-1, 5, -2),))
    assert _head_bindings(rule_eq, od, rep).tolist() == [[7], [9]]
    # head (?x, :p4, ?y): two-column bindings, deduplicated
    rule_xy = Rule((-1, 4, -2), ((-1, 5, -2),))
    assert _head_bindings(rule_xy, od, rep).tolist() == [
        [7, 7], [7, 8], [9, 9],
    ]
    # no overdeleted row matches p = 6: empty binding table
    rule_p6 = Rule((-1, 6, -2), ((-1, 5, -2),))
    assert _head_bindings(rule_p6, od, rep).shape == (0, 2)
    # variable-free head: no instance constraint exists
    rule_const = Rule((7, 4, 7), ((-1, 5, -2),))
    assert _head_bindings(rule_const, od, rep) is None


def test_build_rederive_plan_orders_bound_atoms_first():
    """The head-bound plan chains backward: atoms sharing a variable with
    the bound set come first (so their fixed positions form index-prefix
    range probes), and every atom matches the surviving store
    (PRED_TSTORE)."""
    from repro.core.engine_jax import PRED_TSTORE, build_rederive_plan

    # head (?x, 4, ?z) <- (?y, 5, ?z) & (?x, 5, ?y): written delta-first
    # order starts at an atom NOT sharing ?x; the rederive plan must pick
    # the ?z-sharing atom anyway (both share a head var here), then chain
    rule = Rule((-1, 4, -3), ((-2, 5, -3), (-1, 5, -2)))
    plan, head_vars = build_rederive_plan(rule)
    assert head_vars == (-1, -3)
    assert [s.pred for s in plan] == [PRED_TSTORE, PRED_TSTORE]
    # first picked atom binds a head var; the second is fully chained
    first, second = plan
    assert any(v in (-1, -3) for v, _ in first.bound_items)
    assert {v for v, _ in second.bound_items} >= {-2}

    # a body atom with NO head-var overlap anywhere still gets a plan
    rule2 = Rule((-1, 4, -1), ((-2, 5, -3), (-1, 6, -1)))
    plan2, hv2 = build_rederive_plan(rule2)
    assert hv2 == (-1,)
    # the ?x atom is evaluated first despite being written second
    assert plan2[0].index == 1

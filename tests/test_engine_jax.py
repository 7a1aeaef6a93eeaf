"""JAX fixed-capacity engine vs numpy reference engine equivalence."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.engine_jax import JaxEngine
from repro.core.materialise import Contradiction, materialise
from repro.core.rules import Program, Rule
from repro.core.terms import DIFFERENT_FROM, SAME_AS
from repro.core.triples import pack
from repro.data.datasets import clique_with_spokes, pex, pex_rule_rewrite, single_clique


def _sets_equal(a: np.ndarray, b: np.ndarray) -> bool:
    return set(pack(a).tolist()) == set(pack(b).tolist())


@pytest.mark.parametrize(
    "ds", [pex, pex_rule_rewrite, lambda: single_clique(4)], ids=["pex", "pex_rr", "clique4"]
)
def test_jax_engine_matches_reference(ds):
    facts, prog, dic = ds()
    ref = materialise(facts, prog, dic.n_resources, mode="REW")
    eng = JaxEngine(dic.n_resources, capacity=256, bind_cap=256, out_cap=256, rewrite_cap=256)
    spo, rep, stats = eng.materialise(facts, prog)
    assert _sets_equal(ref.triples(), spo)
    assert (rep == ref.rep).all()
    assert stats.derivations == ref.stats.derivations
    assert stats.rule_applications == ref.stats.rule_applications
    assert stats.merged_resources == ref.stats.merged_resources
    assert stats.reflexive_added == ref.stats.reflexive_added


def test_capacity_growth_retry():
    """Tiny initial capacities must transparently grow, not fail."""
    # 14 explicit facts whose REW store (27 rows) cannot fit a 4-row arena
    facts, prog, dic = clique_with_spokes(3, 12)
    eng = JaxEngine(dic.n_resources, capacity=4, bind_cap=4, out_cap=4, rewrite_cap=4)
    spo, rep, stats = eng.materialise(facts, prog)
    ref = materialise(facts, prog, dic.n_resources, mode="REW")
    assert spo.shape[0] > 4
    assert _sets_equal(ref.triples(), spo)
    assert (rep == ref.rep).all()
    assert eng.capacity > 4  # the arena grew


def test_contradiction_raised():
    eng = JaxEngine(10, capacity=64, bind_cap=64, out_cap=64, rewrite_cap=64)
    E = np.array([[5, DIFFERENT_FROM, 6], [5, SAME_AS, 6]], np.int32)
    with pytest.raises(Contradiction):
        eng.materialise(E, Program([]))


N_RES = 9
CONSTS = list(range(3, N_RES))
PREDS = CONSTS + [SAME_AS]
VARS = [-1, -2]

fact = st.tuples(st.sampled_from(CONSTS), st.sampled_from(PREDS), st.sampled_from(CONSTS))


@st.composite
def rule(draw):
    body = tuple(
        draw(
            st.lists(
                st.tuples(
                    st.sampled_from(CONSTS + VARS),
                    st.sampled_from(PREDS),
                    st.sampled_from(CONSTS + VARS),
                ),
                min_size=1,
                max_size=2,
            )
        )
    )
    body_vars = [t for a in body for t in a if t < 0]
    head_so = st.sampled_from(CONSTS + body_vars) if body_vars else st.sampled_from(CONSTS)
    head = (draw(head_so), draw(st.sampled_from(PREDS)), draw(head_so))
    return Rule(head, body)


@settings(max_examples=15, deadline=None)
@given(
    facts=st.lists(fact, min_size=1, max_size=6),
    rules=st.lists(rule(), min_size=0, max_size=2),
)
def test_jax_engine_random_equivalence(facts, rules):
    E = np.asarray(facts, np.int32).reshape(-1, 3)
    P = Program(rules)
    ref = materialise(E, P, N_RES, mode="REW")
    eng = JaxEngine(N_RES, capacity=512, bind_cap=512, out_cap=512, rewrite_cap=512)
    spo, rep, stats = eng.materialise(E, P)
    assert _sets_equal(ref.triples(), spo)
    assert (rep == ref.rep).all()
    assert stats.derivations == ref.stats.derivations


# ---------------------------------------------------------------------------
# delta-first plans joined through the two persistent indexes
# ---------------------------------------------------------------------------

from repro.core.engine_jax import (  # noqa: E402
    PRED_ALL,
    PRED_DELTA,
    PRED_OLD,
    PRED_TDELTA,
    PRED_TSTORE,
    _index_prefix,
    build_plans,
    plan_join_counts,
)
from repro.data.generator import PROFILES, generate  # noqa: E402


@pytest.mark.parametrize("profile", sorted(PROFILES))
def test_delta_plans_start_at_delta_and_join_through_an_index(profile):
    """Every delta and tombstone plan of a generator profile scans its delta
    atom first, keeps each other atom's semi-naive predicate, and joins each
    later atom by range scans on the (s, p, o) or (p, o, s) index — none
    probes the arena."""
    kw = dict(PROFILES[profile], n_groups=4, n_plain=40)
    _facts, prog, _dic = generate(**kw, seed=1)
    for rule in prog.rules:
        for tomb in (False, True):
            plans = build_plans(rule, full=False, tombstone=tomb)
            assert len(plans) == len(rule.body)
            for i, plan in enumerate(plans):
                assert sorted(s.index for s in plan) == list(range(len(rule.body)))
                first = plan[0]
                assert first.index == i and not first.bound_items
                assert first.pred == (PRED_TDELTA if tomb else PRED_DELTA)
                assert first.count_appl == (not tomb)
                for spec in plan[1:]:
                    want = PRED_TSTORE if tomb else (
                        PRED_OLD if spec.index < i else PRED_ALL
                    )
                    assert spec.pred == want and not spec.count_appl
                    assert _index_prefix(spec)[0] is not None, (rule, i, spec)
                assert plan_join_counts(plan) == (len(plan) - 1, 0)

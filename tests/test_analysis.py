"""repro.analysis: walker, passes, planted fixtures, dispatch auditor, CLI.

Positive direction: the registered engine/maintenance inventory lints clean
at the probe geometry on every dataset family, and a driven update stream's
runtime dispatches reconcile against the static per-phase profile.

Negative direction (the half a linter test suite usually forgets): each
planted-violation fixture must keep tripping exactly its pass, with a
location precise enough to act on — a pass that stops seeing its fixture
has gone blind, whatever the inventory audit says.
"""

import json
import os
import subprocess
import sys

import pytest

from repro.analysis import (
    ALL_PASSES,
    DtypeSafety,
    NoArenaScatter,
    NoArenaSort,
    NoHostCallback,
    audit_engine,
    audited_fn_labels,
    build_probe,
    count_sorts_at_least,
    dispatch_crosscheck,
    jaxpr_walk,
)
from repro.analysis.fixtures import (
    ARENA,
    EXPECTED_PASS,
    FIXTURES,
    trace_fixture,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_passes(label, jx, arena_rows):
    vs = []
    for p in ALL_PASSES:
        vs += p.run(label, jx, arena_rows)
    return vs


# ---------------------------------------------------------------------------
# jaxpr_walk: the generic traversal the passes (and the budget tests) share
# ---------------------------------------------------------------------------

def test_jaxpr_walk_reaches_cond_branches_with_path():
    """The planted sort inside a cond branch is reachable, and its path
    names the nesting trail (the historical helper only looked at
    top-level param values, so tuple-of-branches sub-jaxprs need explicit
    coverage)."""
    _label, jx, _rows = trace_fixture("nested_cond_sort")
    sort_paths = [
        path for eqn, path in jaxpr_walk(jx) if eqn.primitive.name == "sort"
    ]
    assert sort_paths, "walker never reached the branch body"
    assert any("cond[branches" in "/".join(p) for p in sort_paths), sort_paths


def test_count_sorts_at_least_thresholds():
    """Arena-length sorts count; cap-width sorts do not (the discrimination
    the probe geometry exists to make unambiguous)."""
    _l, jx, rows = trace_fixture("arena_sort")
    assert count_sorts_at_least(jx, rows) == 1
    assert count_sorts_at_least(jx, rows + 1) == 0  # strictly longer: none


# ---------------------------------------------------------------------------
# planted fixtures: every pass must catch its bug class, with a location
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", FIXTURES)
def test_fixture_trips_expected_pass(name):
    label, jx, rows = trace_fixture(name)
    vs = _run_passes(label, jx, rows)
    hits = [v for v in vs if v.pass_name == EXPECTED_PASS[name]]
    assert hits, (name, [str(v) for v in vs])
    v = hits[0]
    # the report must be actionable: pass, fn, primitive, and a path
    assert v.fn == f"fixture:{name}"
    assert v.primitive
    assert v.path
    assert str(v).startswith(f"[{EXPECTED_PASS[name]}] fixture:{name}:")
    d = v.as_dict()
    assert set(d) >= {"pass_name", "fn", "primitive", "path", "detail"}


def test_nested_fixture_reports_nested_path():
    """The cond-branch plant's location names the branch, not ``<top>``."""
    label, jx, rows = trace_fixture("nested_cond_sort")
    vs = [v for v in NoArenaSort().run(label, jx, rows)]
    assert vs and vs[0].path != "<top>", [str(v) for v in vs]
    assert "cond[branches" in vs[0].path


def test_fixtures_do_not_cross_fire():
    """Each fixture trips only its own pass family — a scatter plant must
    not look like a sort violation and vice versa (pass independence)."""
    others = {
        "arena_sort": NoArenaScatter(),
        "arena_scatter": NoArenaSort(),
        "int32_key": NoHostCallback(),
        "host_callback": DtypeSafety(),
    }
    for name, p in others.items():
        label, jx, rows = trace_fixture(name)
        assert p.run(label, jx, rows) == [], (name, p.name)


def test_dtype_safety_allows_widening_and_untainted_casts():
    """Only narrowing casts of packed-key-tainted values violate: widening
    a key further, or narrowing a value that never saw a pack, is fine."""
    import jax
    import jax.numpy as jnp
    from repro.compat import enable_x64

    def benign(s, x):
        key = (s.astype(jnp.int64) << jnp.int64(21)) | s.astype(jnp.int64)
        return key + jnp.int64(1), x.astype(jnp.int32)  # untainted narrow

    with enable_x64():
        jx = jax.make_jaxpr(benign)(
            jnp.zeros(8, jnp.int32), jnp.zeros(8, jnp.int64)
        )
    assert DtypeSafety().run("benign", jx, ARENA) == []


# ---------------------------------------------------------------------------
# positive direction: the registered inventory lints clean on every family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dataset", ["chain", "clique", "dbpedia_like"])
def test_inventory_lints_clean(dataset):
    engine, state, program = build_probe(dataset)
    vs = audit_engine(engine, state)
    assert vs == [], [str(v) for v in vs]
    labels = audited_fn_labels(engine, state)
    # the inventory covers the whole maintenance surface, not one fn
    fams = {lbl.split(":")[0] for lbl in labels}
    assert fams >= {
        "process", "squeeze", "rebuild_index", "seed_tombs",
        "od", "finalize_tombs", "extract_od", "member", "occupancy",
        "fforward", "fwave",
    }, fams
    if program.rules:  # pure-sameAs profiles have no rule plans to trace
        assert {"plan", "rplan"} <= fams, fams


def test_driven_stream_dispatches_reconcile():
    """Real add+delete events through the engine leave a dispatch counter
    the static phase profile fully admits (the runtime half of the
    DispatchAuditor), and the phase tags reset after each operation."""
    engine, state, program = build_probe("clique")
    explicit = state.explicit
    engine.delete_facts(state, explicit[:2])
    engine.add_facts(state, explicit[:2])
    assert engine.dispatches.phase is None  # generators reset their tag
    assert engine.dispatches.total > 0
    tagged = [ph for (ph, _f) in engine.dispatches.by_phase if ph is not None]
    assert tagged, "no phase-tagged dispatches recorded"
    assert dispatch_crosscheck(engine.dispatches, program) == []


# ---------------------------------------------------------------------------
# dispatch cross-check semantics (pure, no tracing)
# ---------------------------------------------------------------------------

def test_dispatch_crosscheck_flags_unknowns():
    from repro.core.stats import DispatchCounter

    c = DispatchCounter()
    c.phase = "add:forward"
    c.record("process")          # admitted
    c.phase = "add:mystery"
    c.record("process")          # unknown phase
    c.phase = "delete:wave"
    c.record("rogue")            # unregistered family in a known phase
    c.phase = "retry"
    c.record("rebuild_index")    # capacity-retry recovery: admitted
    c.phase = None
    c.record("anything")         # untagged: never checked
    probs = dispatch_crosscheck(c)
    assert len(probs) == 2, probs
    assert any("unknown phase 'add:mystery'" in p for p in probs)
    assert any(
        "delete:wave" in p and "'rogue'" in p and "static profile allows" in p
        for p in probs
    )


def test_dispatch_counter_snapshot_and_reset():
    from repro.core.stats import DispatchCounter

    c = DispatchCounter()
    c.record("a")
    c.record("a")
    c.record_compile("a")
    snap = c.snapshot()
    assert snap["total"] == 2 and snap["by_family"] == {"a": 2}
    c.reset()
    assert c.total == 0 and not c.by_family and not c.compiles
    assert c.phase is None


# ---------------------------------------------------------------------------
# serving surface: TripleStore exposes its dispatch ledger + audit
# ---------------------------------------------------------------------------

def test_triple_store_dispatch_counts_and_audit():
    from repro.data.generator import generate, sample_update_stream
    from repro.serve.triple_store import TripleStore

    facts, prog, dic = generate(
        n_groups=2, group_size=3, n_spokes_per=1, n_plain=25,
        hierarchy_depth=1, seed=2,
    )
    store = TripleStore(facts, prog, dic)
    for op, delta in sample_update_stream(facts, dic, n_events=2, batch=5,
                                          seed=2):
        store.submit_update(op, delta)
    store.drain()
    assert store.audit() == []
    d = store.dispatch_counts
    assert d["total"] > 0
    assert d["by_family"]
    assert d["by_phase"] and all("/" in k for k in d["by_phase"])
    assert sum(d["by_phase"].values()) <= d["total"]
    assert d["compiles_by_family"]
    # compiles are cache fills, strictly rarer than dispatches per family
    for fam, n in d["compiles_by_family"].items():
        assert d["by_family"].get(fam, 0) >= 0 and n >= 1


# ---------------------------------------------------------------------------
# CLI: the tier-1 entry point the CI gate shells
# ---------------------------------------------------------------------------

def _cli(*args, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=timeout,
    )


def test_cli_check_passes_on_inventory(tmp_path):
    out_json = tmp_path / "report.json"
    r = _cli("--check", "--json", str(out_json))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 violation(s), 0 dispatch problem(s)" in r.stdout
    report = json.loads(out_json.read_text())
    assert report["violations"] == []
    assert report["dispatch"]["problems"] == []
    assert report["fns"] and report["passes"]
    assert report["dispatch"]["total"] > 0
    # static profile covers every runtime-observed phase/family pair
    for key in report["dispatch"]["runtime_by_phase"]:
        ph, fam = key.rsplit("/", 1)
        assert fam in report["dispatch"]["static_profile"][ph], key


@pytest.mark.parametrize("name", FIXTURES)
def test_cli_fixture_exits_nonzero(name):
    r = _cli("--fixture", name, "--json", "-")
    # rc 1 == expected pass fired (rc 2 would mean the audit went blind)
    assert r.returncode == 1, (name, r.returncode, r.stdout + r.stderr)
    assert EXPECTED_PASS[name] in r.stdout
    assert "fired as planted" in r.stdout

"""Trace-audit subsystem: jaxpr invariant linter + dispatch auditor.

The engine's hot-path guarantees — no arena-length sorts inside the round
fns, delta-width joins, packed keys staying int64, no host callbacks inside
compiled code, a bounded number of compiled-call dispatches per maintenance
phase — were informal discipline plus one ad-hoc trace test.  This package
machine-checks them as *static analysis passes over jaxprs*:

  * :func:`jaxpr_walk` — generic recursive traversal of a jaxpr and every
    sub-jaxpr reachable through eqn params (``pjit``/``closed_call`` bodies,
    ``scan``/``while`` carries, every ``cond`` branch, ``shard_map`` bodies,
    arbitrarily nested containers), yielding ``(eqn, path)`` pairs;
  * :mod:`repro.analysis.passes` — pluggable passes over the walk
    (``NoArenaSort``, ``NoArenaScatter``, ``DtypeSafety``,
    ``NoHostCallback``), each returning :class:`Violation` records that name
    the pass, the audited fn, the offending primitive and its nesting path;
  * the **inventory** — every auditable engine/maintenance fn registers a
    trace builder in ``repro.core.engine_jax.AUDIT_REGISTRY``
    (:func:`repro.core.engine_jax.register_auditable`); :func:`audit_engine`
    traces the full registry at a *probe geometry* (arena strictly larger
    than every other buffer, so "arena-length" is unambiguous in the
    traces) and runs every applicable pass;
  * the **dispatch auditor** — :func:`static_dispatch_profile` (in
    :mod:`repro.core.incremental_spmd`) states which compiled-fn families
    each maintenance phase may dispatch and how many distinct compiled
    calls one round/wave costs; the runtime side is
    :class:`repro.core.stats.DispatchCounter` on ``JaxEngine.dispatches``
    (every fn-cache hit is counted under the phase the generators tag);
    :func:`dispatch_crosscheck` verifies observed (phase, family) dispatch
    pairs against the static profile.

``python -m repro.analysis --check`` audits the registered inventory and
exits nonzero on violations — the CI gate that turns the implicit perf
contracts into enforced ones (docs/analysis.md).
"""

from __future__ import annotations

from .passes import (
    ALL_PASSES,
    AnalysisPass,
    DtypeSafety,
    NoArenaScatter,
    NoArenaSort,
    NoHostCallback,
    Violation,
    count_sorts_at_least,
    jaxpr_walk,
    sub_jaxprs,
)

__all__ = [
    "ALL_PASSES",
    "AnalysisPass",
    "DtypeSafety",
    "NoArenaScatter",
    "NoArenaSort",
    "NoHostCallback",
    "Violation",
    "audit_engine",
    "build_probe",
    "count_sorts_at_least",
    "dispatch_crosscheck",
    "jaxpr_walk",
    "run_report",
    "sub_jaxprs",
]


# ---------------------------------------------------------------------------
# inventory audit
# ---------------------------------------------------------------------------

def build_probe(dataset: str = "pex", capacity: int = 4096, cap: int = 256):
    """A representative engine + materialised state for tracing the registry.

    The arena is strictly larger than every other buffer (asserted) so an
    arena-length operand is unambiguous in the traces — the same probe
    geometry the historical trace test used.  Returns
    ``(engine, state, program)``.
    """
    from repro.core.engine_jax import JaxEngine
    from repro.data.datasets import clique_with_spokes, pex, single_clique

    if dataset == "pex":
        facts, prog, dic = pex()
    elif dataset == "chain":
        facts, prog, dic = single_clique(8)
    elif dataset == "clique":
        facts, prog, dic = clique_with_spokes(6, 4)
    elif dataset == "dbpedia_like":
        from repro.data.generator import generate

        facts, prog, dic = generate(
            n_groups=2, group_size=3, n_spokes_per=2, n_plain=40,
            hierarchy_depth=2, chain_rules=True, seed=5,
        )
    else:
        raise ValueError(f"unknown probe dataset {dataset!r}")
    eng = JaxEngine(
        dic.n_resources, capacity=capacity, bind_cap=cap, out_cap=cap,
        rewrite_cap=cap,
    )
    state = eng.materialise_state(facts, prog)
    arena_rows = int(state.spo.shape[0])
    assert arena_rows > 4 * max(eng.bind_cap, eng.out_cap, eng.rewrite_cap), (
        "probe geometry degenerated: arena must dominate every buffer "
        f"(arena {arena_rows}, caps {eng.bind_cap}/{eng.out_cap}/"
        f"{eng.rewrite_cap}) — capacity growth during materialisation?"
    )
    return eng, state, prog


def audit_engine(engine, state, passes=None) -> list[Violation]:
    """Trace every registered auditable fn and run the applicable passes.

    The registry lives in :mod:`repro.core.engine_jax`
    (``AUDIT_REGISTRY``); :mod:`repro.core.incremental_spmd` registers its
    maintenance step fns on import.  Each entry may exempt itself from
    specific passes (e.g. the index rebuild fn IS the one allowed arena
    argsort).  ``arena_rows`` for the length thresholds is taken from the
    traced state.
    """
    from repro.compat import enable_x64

    from repro.core import incremental_spmd  # noqa: F401  (registers fns)
    from repro.core.engine_jax import AUDIT_REGISTRY
    from repro.sparql import batched  # noqa: F401  (registers "bgp")

    passes = list(ALL_PASSES) if passes is None else list(passes)
    arena_rows = int(state.spo.shape[0])
    violations: list[Violation] = []
    with enable_x64():
        for spec in AUDIT_REGISTRY.values():
            for label, jx in spec.builder(engine, state):
                for p in passes:
                    if p.name in spec.skip_passes:
                        continue
                    violations += p.run(label, jx, arena_rows)
    return violations


def audited_fn_labels(engine, state) -> list[str]:
    """The labels of every traced fn in the registered inventory."""
    from repro.compat import enable_x64

    from repro.core import incremental_spmd  # noqa: F401
    from repro.core.engine_jax import AUDIT_REGISTRY
    from repro.sparql import batched  # noqa: F401

    labels = []
    with enable_x64():
        for spec in AUDIT_REGISTRY.values():
            labels += [label for label, _ in spec.builder(engine, state)]
    return labels


# ---------------------------------------------------------------------------
# dispatch auditor (static profile x runtime counter cross-check)
# ---------------------------------------------------------------------------

def dispatch_crosscheck(counter, program=None) -> list[str]:
    """Verify runtime dispatches against the static per-phase profile.

    ``counter`` is a :class:`repro.core.stats.DispatchCounter` populated by
    running maintenance through the engine; every (phase, family) pair it
    observed must be admitted by
    :func:`repro.core.incremental_spmd.static_dispatch_profile` — a
    dispatch from an unregistered family inside a tagged phase means a
    compiled fn joined a hot path without declaring itself to the auditor.
    Dispatches outside any phase (``phase=None``: the base fixpoint,
    ad-hoc engine use) are not checked.  Returns problem strings
    (empty == consistent).
    """
    from repro.core.incremental_spmd import static_dispatch_profile

    profile = static_dispatch_profile(program)
    problems: list[str] = []
    for (phase, family), n in sorted(
        counter.by_phase.items(), key=lambda kv: (str(kv[0][0]), kv[0][1])
    ):
        if phase is None:
            continue
        allowed = profile.get(phase)
        if allowed is None:
            problems.append(
                f"dispatches under unknown phase {phase!r} (family {family} x{n})"
            )
        elif family not in allowed:
            problems.append(
                f"{phase}: dispatched unregistered fn family {family!r} x{n} "
                f"(static profile allows {sorted(allowed)})"
            )
    return problems


def run_report(dataset: str = "pex", events: int = 2) -> dict:
    """The full audit as a JSON-able report dict (the CLI / bench embed).

    Traces the registered inventory at the probe geometry and runs every
    pass; then drives ``events`` small maintenance operations (one add, one
    delete, alternating) through the engine so the runtime dispatch counter
    is populated, and cross-checks it against the static phase profile.
    """
    import numpy as np

    from repro.core.incremental_spmd import static_dispatch_profile

    engine, state, program = build_probe(dataset)
    violations = audit_engine(engine, state)
    labels = audited_fn_labels(engine, state)

    # drive a tiny update stream so every maintenance phase dispatches
    explicit = state.explicit
    for i in range(events):
        k = min(2, explicit.shape[0])
        rows = explicit[:k] if k else np.zeros((0, 3), np.int32)
        if i % 2 == 0 and rows.shape[0]:
            engine.delete_facts(state, rows)
        elif rows.shape[0]:
            engine.add_facts(state, rows)
        explicit = state.explicit
    dispatch_problems = dispatch_crosscheck(engine.dispatches, program)

    return {
        "dataset": dataset,
        "arena_rows": int(state.spo.shape[0]),
        "passes": [p.name for p in ALL_PASSES],
        "fns": sorted(labels),
        "violations": [v.as_dict() for v in violations],
        "dispatch": {
            "static_profile": {
                ph: dict(sorted(fams.items()))
                for ph, fams in static_dispatch_profile(program).items()
            },
            "runtime_by_family": dict(sorted(engine.dispatches.by_family.items())),
            "runtime_by_phase": {
                f"{ph}/{fam}": n
                for (ph, fam), n in sorted(
                    engine.dispatches.by_phase.items(),
                    key=lambda kv: (str(kv[0][0]), kv[0][1]),
                )
                if ph is not None
            },
            "compiles_by_family": dict(sorted(engine.dispatches.compiles.items())),
            "total": engine.dispatches.total,
            "problems": dispatch_problems,
        },
    }

"""Incremental materialisation maintenance for the REW (rewriting) mode.

The source paper (arXiv:1411.3622) materialises once; its successor —
Motik et al., *Combining Rewriting and Incremental Materialisation
Maintenance for Datalog Programs with Equality* (arXiv:1505.00212) — extends
the same rewriting machinery to fact addition and deletion without a
from-scratch rerun.  This module is the bulk-synchronous adaptation of that
algorithm on top of the existing engine pieces:

``add_facts``
    Additions are the easy direction: the semi-naive delta discipline of
    :func:`repro.core.materialise.rew_rounds` is *restartable* — seeding the
    round loop with the new explicit triples considers exactly the
    substitutions that involve at least one new fact (old-only substitutions
    were found by the base run), so the existing loop is reused verbatim,
    including rho maintenance, the Algorithm-3 sweep and rule rewriting.

``delete_facts``
    Deletions use a rewriting-aware Backward/Forward (B/F-style) pass:

    1. **Overdelete** (DRed backward step, batched): starting from the
       rho-normal forms of the deleted triples, repeatedly evaluate the
       current program's delta plans with Delta = the overdeleted frontier
       and all other atoms against the *pre-deletion* store, and overdelete
       every stored fact the derived heads normalise onto.
    2. **Overdelete reflexivity children**: a ``<c, sameAs, c>`` fact has
       its genesis in the facts that mention ``c``, so when such a fact is
       overdeleted its resources' reflexive witnesses are overdeleted too.
       This is deliberately over-approximate — a model-based "is there
       surviving support" check is unsound under the refl-row -> rule-head
       cycles that equality programs produce; DRed soundness needs the full
       may-be-affected cone, with survivors restored in step 4.
    3. **Split sameAs cliques**: a clique is *suspect* iff its reflexive
       witness ``<r, sameAs, r>`` was overdeleted — every derivation of an
       equality between members normalises onto that witness, so an intact
       witness proves no merge lost support.  Suspect cliques are split by
       resetting their members to singletons (the inverse of min-hooking;
       re-merging below goes through the same
       :func:`repro.core.uf.merge_pairs_np` machinery), and every stored
       fact touching a suspect representative is overdeleted too — a stored
       normal form conflates clique members, so after a split it cannot be
       trusted until rederived.
    Steps 1-3 iterate to a joint fixpoint (each can enable the others).
    4. **Rederive + forward**: the rules are re-rewritten from the *base*
       program under the split rho, and three candidate families are seeded
       back into :func:`rew_rounds`: every still-explicit triple whose
       normal form went missing, every head derivable in one step from the
       surviving store, and the reflexive witnesses of resources that still
       occur in surviving facts.  The loop re-merges whatever equalities
       still hold and re-rewrites affected triples through the normal
       Algorithm-3 sweep.

    Correctness oracle (tests/test_incremental.py): the incremental result
    must equal the from-scratch REW materialisation of the updated explicit
    set — same rho, same normal-form store, same Theorem-1 expansion.

Normal forms of large batches can be computed through the Pallas kernel
:func:`repro.kernels.ops.rewrite_triples` (``use_kernel=True``; Mosaic on a
TPU, interpreted elsewhere) or through plain numpy gathers (the default).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from .materialise import MatResult, rew_rounds
from .rules import Program, Rule
from .seminaive import _const_filter, eval_rule_delta, eval_rule_full
from .stats import MatStats
from .terms import SAME_AS, is_var
from .triples import TripleArena, dedup_rows, pack, setdiff_rows
from .uf import clique_sizes, split_cliques

__all__ = [
    "IncrementalState",
    "materialise_incremental",
    "add_facts",
    "delete_facts",
    "normal_forms",
]


def normal_forms(
    spo: np.ndarray, rep: np.ndarray, use_kernel: bool = False
) -> np.ndarray:
    """``rho[spo]`` for an (n, 3) batch; optionally on the Pallas kernel."""
    spo = np.asarray(spo, dtype=np.int32).reshape(-1, 3)
    if spo.shape[0] == 0:
        return spo
    if use_kernel:
        import jax.numpy as jnp

        from repro.kernels import ops

        out, _changed = ops.rewrite_triples(
            jnp.asarray(spo, jnp.int32), jnp.asarray(rep, jnp.int32)
        )
        return np.asarray(out, dtype=np.int32)
    return rep[spo].astype(np.int32)


@dataclass
class IncrementalState:
    """A materialised store that supports add/delete maintenance.

    ``rep`` is always fully compressed; ``program`` is the current rewritten
    program rho(``base_program``); ``explicit`` is the current explicit fact
    set in *original* resource IDs (the set a from-scratch run would start
    from); ``stats`` accumulates across the base run and every update.
    """

    arena: TripleArena
    rep: np.ndarray
    program: Program
    base_program: Program
    explicit: np.ndarray
    n_resources: int
    stats: MatStats = field(default_factory=lambda: MatStats(mode="REW-inc"))
    use_kernel: bool = False

    def result(self) -> MatResult:
        self.stats.triples_total = self.arena.total
        self.stats.triples_unmarked = self.arena.unmarked
        self.stats.memory_bytes = self.arena.nbytes
        return MatResult(self.arena, self.rep, self.program, self.stats)

    def triples(self) -> np.ndarray:
        return self.arena.valid_triples()

    # -- internal ------------------------------------------------------------
    def _grow_rep(self, facts: np.ndarray) -> None:
        """Extend rho with identity entries for unseen resource IDs."""
        if facts.shape[0] == 0:
            return
        hi = int(facts.max()) + 1
        if hi > self.rep.shape[0]:
            ext = np.arange(self.rep.shape[0], hi, dtype=self.rep.dtype)
            self.rep = np.concatenate([self.rep, ext])
            self.n_resources = hi


def materialise_incremental(
    facts: np.ndarray,
    program: Program,
    n_resources: int,
    max_rounds: int = 10_000,
    use_kernel: bool = False,
) -> IncrementalState:
    """From-scratch REW materialisation that returns a maintainable state."""
    t0 = time.perf_counter()
    stats = MatStats(mode="REW-inc")
    arena = TripleArena()
    rep = np.arange(n_resources, dtype=np.int32)
    facts = dedup_rows(facts)
    stats.triples_explicit = facts.shape[0]
    rep, p_cur = rew_rounds(arena, rep, program, facts, stats, max_rounds)
    stats.wall_seconds += time.perf_counter() - t0
    return IncrementalState(
        arena=arena,
        rep=rep,
        program=p_cur,
        base_program=program,
        explicit=facts,
        n_resources=n_resources,
        stats=stats,
        use_kernel=use_kernel,
    )


def add_facts(
    state: IncrementalState, delta: np.ndarray, max_rounds: int = 10_000
) -> IncrementalState:
    """Add explicit triples and maintain the materialisation in place.

    Seeds the shared round loop with the fresh triples: the delta-plan
    discipline guarantees every substitution involving at least one new fact
    is considered exactly once, and old-only substitutions were exhausted by
    the base run.  May raise :class:`repro.core.materialise.Contradiction`
    (rule ~=5), in which case the state is left partially updated and should
    be discarded, exactly like a failed from-scratch run.
    """
    t0 = time.perf_counter()
    delta = dedup_rows(delta)
    delta = setdiff_rows(delta, state.explicit)
    if delta.shape[0] == 0:
        state.stats.wall_seconds += time.perf_counter() - t0
        return state
    state._grow_rep(delta)
    state.explicit = np.concatenate([state.explicit, delta], axis=0)
    state.stats.triples_explicit = state.explicit.shape[0]
    state.rep, state.program = rew_rounds(
        state.arena, state.rep, state.program, delta, state.stats, max_rounds
    )
    state.stats.wall_seconds += time.perf_counter() - t0
    return state


# ---------------------------------------------------------------------------
# deletion: B/F-style overdelete + clique split + rederive
# ---------------------------------------------------------------------------

def _rule_touches(rule: Rule, f_spo: np.ndarray) -> bool:
    """True iff some frontier fact matches some body atom's constant
    pattern — a rule none of whose atoms can bind a frontier fact cannot
    contribute to the overdeletion wave, so its delta plans are skipped."""
    for atom in rule.body:
        if _const_filter(atom, f_spo).any():
            return True
    return False


def _rule_may_rederive(rule: Rule, o_spo: np.ndarray, rep_old: np.ndarray) -> bool:
    """False iff no overdeleted fact can match the rule's head pattern.

    Rederivation only ever needs to restore *overdeleted* facts (everything
    else either survived in the store or requires a new fact to derive), so
    rules whose head constants are incompatible with every overdeleted
    normal form are skipped.  Constants are collapsed through the
    pre-deletion rho because ``o_spo`` rows are normal under it while the
    rule was rewritten under the post-split rho.
    """
    if o_spo.shape[0] == 0:
        return False
    mask = np.ones(o_spo.shape[0], dtype=bool)
    for pos, t in enumerate(rule.head):
        if not is_var(t):
            mask &= o_spo[:, pos] == rep_old[t]
    return bool(mask.any())


def _overdelete(
    state: IncrementalState, deleted: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """The backward half of the B/F pass.

    Returns ``(overdel_rows, suspect_reps)``: the arena row indices to
    retract and the representatives of the sameAs cliques that must be
    split.  Pure analysis — the arena is not modified here.
    """
    arena, rep = state.arena, state.rep
    n = arena.n
    valid = arena.valid[:n]
    spo_all = arena.spo[:n]
    t_snapshot = spo_all[valid]  # pre-deletion store (DRed matches against T)

    overdel = np.zeros(n, dtype=bool)
    suspect = np.zeros(rep.shape[0], dtype=bool)
    sizes = clique_sizes(rep)

    # seed: normal forms of the deleted explicit triples
    frontier = arena.rows_of(normal_forms(deleted, rep, state.use_kernel))
    overdel[frontier] = True

    while frontier.shape[0]:
        # 1) backward rule closure: heads derivable with >= 1 body atom in
        # the frontier and the rest anywhere in the pre-deletion store
        f_spo = spo_all[frontier]
        outs = []
        for rule in state.program:
            if not _rule_touches(rule, f_spo):
                continue
            h, _nd, _na = eval_rule_delta(rule, t_snapshot, t_snapshot, f_spo)
            if h.shape[0]:
                outs.append(h)
        heads = (
            np.concatenate(outs, axis=0)
            if outs
            else np.zeros((0, 3), np.int32)
        )
        heads = normal_forms(heads, rep, state.use_kernel)

        new_rows = arena.rows_of(heads)
        new_rows = new_rows[~overdel[new_rows]]

        # 2) reflexivity children: <c, sameAs, c> for every resource of this
        # wave may have lost its genesis — overdelete, rederive survivors
        res = np.unique(np.append(np.unique(f_spo), SAME_AS))
        refl = np.stack(
            [res, np.full_like(res, SAME_AS), res], axis=1
        ).astype(np.int32)
        refl_rows = arena.rows_of(refl)
        refl_rows = refl_rows[~overdel[refl_rows]]
        new_rows = np.concatenate([new_rows, refl_rows])

        # 3) suspect cliques: the reflexive witness <r, sameAs, r> of a
        # multi-member clique was overdeleted -> split required, and every
        # stored fact touching r is no longer trustworthy
        wit = np.concatenate([frontier, new_rows])
        wit_spo = spo_all[wit]
        is_wit = (
            (wit_spo[:, 1] == SAME_AS)
            & (wit_spo[:, 0] == wit_spo[:, 2])
            & (sizes[wit_spo[:, 0]] > 1)
        )
        fresh_sus = np.unique(wit_spo[is_wit][:, 0])
        fresh_sus = fresh_sus[~suspect[fresh_sus]]
        if fresh_sus.shape[0]:
            suspect[fresh_sus] = True
            touch = valid & ~overdel & np.isin(spo_all, fresh_sus).any(axis=1)
            touch[wit] = False  # already in this wave
            grabbed = np.flatnonzero(touch)
            new_rows = np.concatenate([new_rows, grabbed])

        overdel[new_rows] = True
        frontier = np.unique(new_rows)

    return np.flatnonzero(overdel), np.flatnonzero(suspect)


def delete_facts(
    state: IncrementalState, delta: np.ndarray, max_rounds: int = 10_000
) -> IncrementalState:
    """Retract explicit triples and maintain the materialisation in place.

    Rows of ``delta`` that are not currently explicit are ignored.  See the
    module docstring for the B/F algorithm; the result is oracle-equal to a
    from-scratch REW run on ``explicit \\ delta`` (tests/test_incremental.py).
    """
    t0 = time.perf_counter()
    delta = dedup_rows(delta)
    if delta.shape[0] and state.explicit.shape[0]:
        delta = delta[np.isin(pack(delta), pack(state.explicit))]
    else:
        delta = np.zeros((0, 3), np.int32)
    if delta.shape[0] == 0:
        state.stats.wall_seconds += time.perf_counter() - t0
        return state

    explicit_new = setdiff_rows(state.explicit, delta)

    # -- backward: overdelete + find suspect cliques -------------------------
    overdel_rows, suspect_reps = _overdelete(state, delta)
    state.arena.mark_rows(overdel_rows)

    # -- split: only affected connected components are recomputed ------------
    rep_split = split_cliques(state.rep, suspect_reps)

    # -- rebuild rules under the split rho (suspect constants revert) --------
    p_split, _changed = state.base_program.rewrite(rep_split)

    # -- forward: rederive and run the shared round loop ---------------------
    # seed 1: explicit facts whose normal form went missing
    miss = np.zeros(0, dtype=bool)
    seeds = []
    if explicit_new.shape[0]:
        nf = normal_forms(explicit_new, rep_split, state.use_kernel)
        miss = ~state.arena.contains(nf)
        if miss.any():
            seeds.append(explicit_new[miss])
    # seed 2: one-step rederivations — heads derivable from the surviving
    # store (old+old substitutions the delta discipline would never revisit)
    t_surv = state.arena.valid_triples()
    if t_surv.shape[0] and overdel_rows.shape[0]:
        o_spo = state.arena.spo[overdel_rows]
        for rule in p_split:
            if not _rule_may_rederive(rule, o_spo, state.rep):
                continue
            h, _nd, _na = eval_rule_full(rule, t_surv)
            if h.shape[0]:
                seeds.append(h)
        # seed 3: reflexive witnesses whose genesis survived — resources
        # still occurring in surviving facts keep their <c, sameAs, c>
        res = np.unique(np.append(np.unique(t_surv), SAME_AS))
        refl = np.stack(
            [res, np.full_like(res, SAME_AS), res], axis=1
        ).astype(np.int32)
        miss_refl = refl[~state.arena.contains(refl)]
        if miss_refl.shape[0]:
            seeds.append(miss_refl)
    cands = (
        dedup_rows(np.concatenate(seeds, axis=0))
        if seeds
        else np.zeros((0, 3), np.int32)
    )
    if cands.shape[0]:
        cands = cands[
            ~state.arena.contains(normal_forms(cands, rep_split, state.use_kernel))
        ]

    rep_new, p_new = rew_rounds(
        state.arena, rep_split, p_split, cands, state.stats, max_rounds
    )

    state.rep = rep_new
    state.program = p_new
    state.explicit = explicit_new
    state.stats.triples_explicit = explicit_new.shape[0]
    state.stats.wall_seconds += time.perf_counter() - t0
    return state

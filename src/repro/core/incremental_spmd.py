"""Sharded incremental maintenance: SPMD overdelete/rederive on the engine.

The host subsystem (:mod:`repro.core.incremental`) runs every maintenance
round on the host, so update streams do not scale with the mesh the way the
base fixpoint in :meth:`repro.core.engine_jax.JaxEngine.materialise` does.
This module ports the add/delete rounds into the fixed-capacity SPMD engine:

**Additions** reuse the engine's forward round loop directly — the delta
batch is padded into the candidate stream and processed exactly like the
explicit facts of the base run, at the next epoch.  The epoch discipline of
``_epoch_ok`` makes the loop restartable: the first new round's delta plans
match exactly the freshly inserted rows, and old-only substitutions were
exhausted earlier.

**Deletions** are the DRed-style backward/forward pass of the host module,
with the backward closure run on-device as *epoch-tagged tombstones*:

1. *Seed*: the rho-normal forms of the deleted explicit triples are routed
   to every shard (replicated query batch); each shard tags its matching
   rows ``tomb = 0``.
2. *Overdelete waves*: wave ``w`` evaluates every rule's tombstone plans
   (:func:`repro.core.engine_jax.build_plans` with ``tombstone=True``) —
   Delta = rows with ``tomb == w-1``, all other atoms the full pre-deletion
   store — then :func:`_od_step` tags the derived heads, the reflexivity
   children of the wave's frontier, and every fact touching a freshly
   *suspect* clique (one whose reflexive witness ``<r, sameAs, r>`` was
   tombstoned).  Cross-shard delta triples are exchanged with the same
   owner-routed ``all_to_all`` (keyed on the subject representative) the
   forward rounds use; the suspect set leaves the device only as a psum'd
   boolean mask — clique split/re-merge stays a host decision.
3. *Finalize*: tombstones flip to ``marked`` (the paper's mark-don't-delete
   bit), per-position masks of the overdeleted normal forms are reduced for
   the host-side rederive rule filter, and ``tomb`` resets to -1 — the
   invariant the forward predicates rely on.
4. *Split + rederive*: the host splits suspect cliques
   (:func:`repro.core.uf.split_cliques` — only rho bookkeeping leaves the
   device), re-rewrites the base program under the split rho, and runs
   **targeted rederivation**: for each rule whose head pattern can restore
   an overdeleted fact, the head variables are pre-bound to the overdeleted
   instances (:func:`_head_bindings` on the finalised tombstone set) and
   the body is chained backward through the persistent sorted index
   (:func:`repro.core.engine_jax.eval_plan_rederive`) — the B/F refinement
   of DRed's rederive step, with join cost proportional to the overdelete
   delta rather than the surviving store.  The restored instances seed the
   shared forward loop together with (a) still-explicit triples whose
   normal form went missing and (b) missing reflexive witnesses of
   surviving resources; only variable-free heads still fall back to a
   whole-rule requeue.  Re-merging then happens through the normal round
   machinery (``merge_pairs_jax`` + the Algorithm-3 sweep).

Correctness oracle (tests/test_incremental_spmd.py + the differential fuzz
harness in tests/test_incremental.py): after any update sequence the state
equals the from-scratch REW materialisation of the updated explicit set —
same rho, same normal-form store — and is invariant to the device count.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .engine_jax import (
    I32,
    KEY_MAX,
    CapacityError,
    EngineState,
    _compact as _engine_compact,
    _index_remove,
    _pack3,
    _pow2,
    _route_rows,
    argsort_keys,
    register_auditable,
)
from repro.kernels import ops as kernel_ops

from .terms import SAME_AS, is_var
from .triples import dedup_rows, pack, setdiff_rows
from .uf import clique_sizes, split_cliques

__all__ = [
    "spmd_add_facts",
    "spmd_add_phases",
    "spmd_delete_facts",
    "spmd_delete_phases",
    "static_dispatch_profile",
]


# ---------------------------------------------------------------------------
# per-shard step functions (pure; run under shard_map via engine._wrap)
# ---------------------------------------------------------------------------

def _probe_index(sorted_keys, sort_perm, select, queries, qvalid):
    """Row index of each query triple among the locally ``select``-ed rows,
    via the shard's persistent sorted index — no arena sort per probe.

    Returns ``(rows, hit)`` — rows are clamped-garbage where ``hit`` is
    False.  Live keys are unique by the arena's insert-time dedup, so at
    most one index entry matches a query; ``select`` prunes subsets of the
    live rows (e.g. already-tombstoned ones).  Invalid query slots are
    excluded by masking ``hit`` with ``qvalid`` explicitly — the former
    ``KEY_MAX - 1`` sentinel aliased a legitimate packed key at the 21-bit
    ID boundary.
    """
    qk = _pack3(queries)
    pos = jnp.clip(jnp.searchsorted(sorted_keys, qk), 0, sorted_keys.shape[0] - 1)
    rows = sort_perm[pos]
    hit = (sorted_keys[pos] == qk) & qvalid & select[rows]
    return rows, hit


def _psum_bool(x, axis):
    if axis is None:
        return x
    return jax.lax.psum(x.astype(I32), axis) > 0


def _resource_mask(res, valid, n_res):
    """Bool ``(n_res,)`` mask of the resources ``res[valid]``.

    The scatter-max runs on int32: the TPU compiler lowers a bool scatter
    through a sort of its indices, an int32 one without.
    """
    hits = jnp.zeros(n_res, I32).at[jnp.where(valid, res, 0)].max(
        valid.astype(I32)
    )
    return hits > 0


def _seed_tombs(sorted_keys, sort_perm, epoch, marked, tomb, q, qv, *, axis):
    """Tag wave-0 tombstones: local rows matching the replicated queries."""
    untagged = (epoch >= 0) & ~marked & (tomb < 0)
    rows, hit = _probe_index(sorted_keys, sort_perm, untagged, q, qv)
    tgt = jnp.where(hit, rows, tomb.shape[0])
    tomb = tomb.at[tgt].set(jnp.zeros(tgt.shape, I32), mode="drop")
    n = hit.sum().astype(I32)
    if axis is not None:
        n = jax.lax.psum(n, axis)
    return tomb, n[None]


def _od_step(
    spo, epoch, marked, tomb, sorted_keys, sort_perm, rep, sizes, suspect,
    heads, hv, w,
    *, axis, n_shards, route_cap, refl_cap,
    with_masks: bool = True, use_kernel: bool = False,
):
    """One overdelete wave: tag heads + reflexivity children, detect suspect
    cliques (psum'd mask — the only state that leaves the shard), and grab
    every live fact touching a fresh suspect.  Returns
    ``(tomb', suspect', n_new, overflow, frontier_masks)``.

    ``with_masks=False`` skips the per-position frontier mask reduction
    (returning all-False masks): the fused wave loop evaluates every
    tombstone plan unconditionally, so the host-side plan filter the masks
    feed never runs — dead-plan skipping is an orchestration optimisation,
    not a semantic one (a skipped plan's delta atom matches zero rows).
    """
    C = spo.shape[0]
    store = (epoch >= 0) & ~marked  # the pre-deletion store (DRed's T)
    frontier = store & (tomb == w - 1)

    # heads derived from the wave's delta plans, normalised under rho
    heads_n = jnp.where(hv[:, None], rep[heads], 0).astype(I32)

    # reflexivity children: <c, sameAs, c> for every resource of the
    # frontier (plus the sameAs row itself, mirroring the host pass).  The
    # frontier is compacted first so the stream scales with the wave, not
    # the arena; overflow raises the update's capacity retry.
    fcols, fvalid, f_ov = _engine_compact(
        {"s": spo[:, 0], "p": spo[:, 1], "o": spo[:, 2]}, frontier, refl_cap
    )
    # column-major: flattening an (n, 3) block is a relayout on the TPU
    res = jnp.concatenate([fcols["s"], fcols["p"], fcols["o"]])
    res_v = jnp.concatenate([fvalid] * 3)
    refl = jnp.stack([res, jnp.full_like(res, SAME_AS), res], axis=1)
    sa_row = jnp.asarray([[SAME_AS] * 3], I32)
    any_f = frontier.any()
    stream = jnp.concatenate([heads_n, refl, sa_row], axis=0)
    sv = jnp.concatenate([hv, res_v, any_f[None]])

    # dedup locally before the exchange (shrinks bucket pressure)
    keys = jnp.where(sv, _pack3(stream), KEY_MAX)
    if use_kernel:  # sort-free Pallas counting-rank dedup
        order = kernel_ops.dedup_order(keys)
    else:
        order = argsort_keys(keys)
    sk = keys[order]
    uniq = jnp.concatenate([jnp.asarray([True]), sk[1:] != sk[:-1]])
    stream, sv = stream[order], uniq & (sk < KEY_MAX)

    # owner-routed delta exchange, keyed on the subject representative
    stream, _, sv, overflow = _route_rows(
        stream, None, sv, axis, n_shards, route_cap
    )

    # tombstone the matching local rows that are not already tagged —
    # probed against the persistent index (tomb tagging does not change
    # liveness, so the index stays exact across the whole backward pass)
    untagged = store & (tomb < 0)
    rows, hit = _probe_index(sorted_keys, sort_perm, untagged, stream, sv)
    tgt = jnp.where(hit, rows, C)
    tomb = tomb.at[tgt].set(jnp.where(hit, w, 0).astype(I32), mode="drop")

    # suspect cliques: a tombstoned reflexive witness <r, sameAs, r> of a
    # multi-member clique means every merge of that clique lost its proof.
    # Checked on this wave's new rows AND the frontier so the wave-0 seeds
    # are examined exactly once (grabbed rows are re-checked next wave).
    wit = store & ((tomb == w) | (tomb == w - 1))
    is_wit = (
        wit
        & (spo[:, 1] == SAME_AS)
        & (spo[:, 0] == spo[:, 2])
        & (sizes[spo[:, 0]] > 1)
    )
    cand = _resource_mask(spo[:, 0], is_wit, rep.shape[0])
    cand = _psum_bool(cand, axis)
    fresh = cand & ~suspect
    suspect = suspect | cand

    # grab: a stored normal form conflates members of a split clique, so
    # every live fact touching a fresh suspect must be rederived
    touch = fresh[spo[:, 0]] | fresh[spo[:, 1]] | fresh[spo[:, 2]]
    grab = store & (tomb < 0) & touch
    tomb = jnp.where(grab, w, tomb)

    new = store & (tomb == w)
    n_new = new.sum().astype(I32)
    if axis is not None:
        n_new = jax.lax.psum(n_new, axis)

    # per-position resource masks of the wave's new rows: the host driver
    # skips next wave's tombstone plans whose delta atom cannot match them
    if with_masks:
        od_masks = _psum_bool(jnp.stack([
            _resource_mask(spo[:, pos], new, rep.shape[0]) for pos in range(3)
        ]), axis)
    else:
        od_masks = jnp.zeros((3, rep.shape[0]), bool)
    return tomb, suspect, n_new[None], overflow[None], f_ov[None], od_masks


def _finalize_tombs(spo, epoch, marked, tomb, sorted_keys, sort_perm, rep, *, axis):
    """Flip tombstones into the paper's outdated bit and reduce the
    per-position masks of overdeleted normal forms (the host-side rederive
    rule filter).  Restores the ``tomb == -1`` forward invariant; the
    finalised rows leave the persistent index by a stable partition (no
    sort), keeping it exact for the rederive phase's membership probes."""
    tombed = tomb >= 0
    od_mask = jnp.stack([
        _resource_mask(spo[:, pos], tombed, rep.shape[0]) for pos in range(3)
    ])  # (3, n_res)
    od_mask = _psum_bool(od_mask, axis)
    n_od = tombed.sum().astype(I32)
    if axis is not None:
        n_od = jax.lax.psum(n_od, axis)
    marked = marked | tombed
    tomb = jnp.full_like(tomb, -1)
    sort_perm, sorted_keys = _index_remove(
        sort_perm, sorted_keys, tombed, spo.shape[0] - 1
    )
    return marked, tomb, sorted_keys, sort_perm, od_mask, n_od[None]


def _extract_tombed(spo, tomb, *, axis, cap):
    """Compact the overdeleted rows (``tomb >= 0``) — the finalised
    tombstone set that drives targeted rederivation.  Must run BEFORE
    :func:`_finalize_tombs` resets ``tomb``; ``cap`` is sized from the
    host's running overdelete count (a global bound, hence per-shard
    sufficient), so the overflow flag only fires if the driver miscounted.
    """
    del axis  # per-shard compaction; the host concatenates the blocks
    tombed = tomb >= 0
    cols, valid, ov = _engine_compact(
        {"s": spo[:, 0], "p": spo[:, 1], "o": spo[:, 2]}, tombed, cap
    )
    rows = jnp.stack([cols["s"], cols["p"], cols["o"]], axis=1)
    return rows, valid, ov[None]


def _member(sorted_keys, q, qv, *, axis):
    """Replicated membership of query triples among live store rows.

    The index contains exactly the live rows, so a key hit IS liveness —
    no row lookup or epoch/marked recheck needed.  The all-max-ID triple
    packs to KEY_MAX itself (the padding sentinel, reserved — see
    ``terms.MAX_ID``) and must not match the padding.
    """
    qk = _pack3(q)
    pos = jnp.clip(jnp.searchsorted(sorted_keys, qk), 0, sorted_keys.shape[0] - 1)
    hit = (sorted_keys[pos] == qk) & qv & (qk < KEY_MAX)
    return _psum_bool(hit, axis)


def _occupancy(spo, epoch, marked, rep, *, axis):
    """Replicated mask of resources occurring in live store rows."""
    live = (epoch >= 0) & ~marked
    # column by column: flattening the (C, 3) arena is a relayout that the
    # TPU compiler takes about a minute to build
    occ = (
        _resource_mask(spo[:, 0], live, rep.shape[0])
        | _resource_mask(spo[:, 1], live, rep.shape[0])
        | _resource_mask(spo[:, 2], live, rep.shape[0])
    )
    return _psum_bool(occ, axis)


# ---------------------------------------------------------------------------
# wrapped-fn getters (cached on the engine like its plan/process fns)
# ---------------------------------------------------------------------------

_KEY_FAMILY = {"refl_cap": "out", "route_cap": "route"}


def _get_step_fn(engine, name, fn, in_specs, out_specs, **static):
    # cap-valued statics are tagged with their buffer family so the
    # engine's precise post-growth eviction finds them
    key = (name,) + tuple(
        sorted((_KEY_FAMILY.get(k, k), v) for k, v in static.items())
    )
    if key not in engine._fns:
        a = engine.axis
        engine._register_fn(key, engine._wrap(
            partial(fn, axis=a, **static), in_specs=in_specs, out_specs=out_specs
        ))
    return engine._fns[key]


def _specs(engine):
    a = engine.axis
    d = P(a) if a else None
    rpl = P() if a else None
    return d, rpl


def _seed_fn(engine):
    d, rpl = _specs(engine)
    return _get_step_fn(
        engine, "seed_tombs", _seed_tombs,
        in_specs=(d, d, d, d, d, rpl, rpl), out_specs=(d, rpl),
    )


def _od_fn(engine, n_heads: int):
    d, rpl = _specs(engine)
    route_cap = engine.route_cap if engine.axis is not None else None
    return _get_step_fn(
        engine, ("od", n_heads), _od_step,
        in_specs=(d, d, d, d, d, d, rpl, rpl, rpl, d, d, rpl),
        out_specs=(d, rpl, rpl, d, d, rpl),
        n_shards=engine.n_shards, route_cap=route_cap,
        refl_cap=engine._active_delta_out,
        use_kernel=engine.use_kernel,
    )


def _fwave_fn(engine, plans_sig: tuple):
    """Wrapped :func:`repro.core.fused.fused_delete_waves` for this engine.

    Keyed like the engine's own fused-forward fn: the plan signature plus
    every cap the trace closes over, each tagged with its buffer family so
    post-growth eviction stays precise."""
    key = (
        "fwave", plans_sig,
        ("bind", engine._active_bind), ("out", engine._active_delta_out),
        ("route", engine.route_cap),
    )
    if key not in engine._fns:
        from .fused import fused_delete_waves

        a = engine.axis
        fn = partial(
            fused_delete_waves,
            plans=plans_sig,
            bind_cap=engine._active_bind,
            plan_out_cap=engine._active_delta_out,
            route_cap=engine.route_cap if a is not None else None,
            refl_cap=engine._active_delta_out,
            axis=a,
            n_shards=engine.n_shards,
            use_kernel=engine.use_kernel,
        )
        d, rpl = _specs(engine)
        flag_specs = {
            k: rpl
            for k in (
                "iters", "n_od", "n_new",
                "ov_route", "ov_refl", "ov_bind", "ov_out", "ov_squeeze",
            )
        }
        engine._register_fn(key, engine._wrap(
            fn,
            in_specs=(d, d, d, d, d, d, rpl, rpl, rpl, rpl, rpl, rpl),
            out_specs=(d, rpl, flag_specs),
        ))
    return engine._fns[key]


def _finalize_fn(engine):
    d, rpl = _specs(engine)
    return _get_step_fn(
        engine, "finalize_tombs", _finalize_tombs,
        in_specs=(d, d, d, d, d, d, rpl), out_specs=(d, d, d, d, rpl, rpl),
    )


def _extract_fn(engine, cap: int):
    d, rpl = _specs(engine)
    return _get_step_fn(
        engine, "extract_od", _extract_tombed,
        in_specs=(d, d), out_specs=(d, d, d), cap=cap,
    )


def _member_fn(engine):
    d, rpl = _specs(engine)
    return _get_step_fn(
        engine, "member", _member,
        in_specs=(d, rpl, rpl), out_specs=rpl,
    )


def _occ_fn(engine):
    d, rpl = _specs(engine)
    return _get_step_fn(
        engine, "occupancy", _occupancy,
        in_specs=(d, d, d, rpl), out_specs=rpl,
    )


# ---------------------------------------------------------------------------
# host-side helpers
# ---------------------------------------------------------------------------

def _chunks(rows: np.ndarray, size: int):
    for i in range(0, rows.shape[0], size):
        chunk = rows[i : i + size]
        padn = size - chunk.shape[0]
        q = np.pad(chunk, ((0, padn), (0, 0))).astype(np.int32)
        qv = np.arange(size) < chunk.shape[0]
        yield chunk.shape[0], jnp.asarray(q), jnp.asarray(qv)


def _seed_query(engine, state: EngineState, rows: np.ndarray) -> int:
    """Tag wave-0 tombstones for ``rows`` (chunked replicated queries)."""
    total = 0
    fn = _seed_fn(engine)
    for _n, q, qv in _chunks(rows, engine.seed_chunk):
        state.tomb, n = fn(
            state.sorted_keys, state.sort_perm, state.epoch, state.marked,
            state.tomb, q, qv,
        )
        total += int(np.asarray(n).reshape(-1)[0])
    return total


def _member_query(engine, state: EngineState, rows: np.ndarray) -> np.ndarray:
    """Boolean membership of ``rows`` among live store rows (chunked)."""
    if rows.shape[0] == 0:
        return np.zeros(0, dtype=bool)
    fn = _member_fn(engine)
    out = []
    for n, q, qv in _chunks(rows, engine.seed_chunk):
        hit = np.asarray(fn(state.sorted_keys, q, qv))
        out.append(hit[:n])
    return np.concatenate(out)


def _tomb_heads(engine, state: EngineState, w: int, masks: np.ndarray):
    """Evaluate the tombstone delta plans for wave ``w``, skipping plans
    whose delta atom cannot match the frontier (``masks`` = the previous
    wave's per-position resource masks).  Wide bucketed head streams are
    squeezed to the active delta width so the wave step's dedup/probe work
    scales with the wave, not with the number of rules that fired."""
    bufs = []
    for k, rule in enumerate(state.program.rules):
        bufs += engine._eval_rule(state, w, rule, k, "tomb", None, delta_masks=masks)
    if not bufs:
        return jnp.zeros((0, 3), I32), jnp.zeros((0,), bool)
    heads, hv = engine._bucket_cands(bufs)
    rows_global = engine._active_delta_out * engine.n_shards
    if int(heads.shape[0]) > rows_global:
        sq = engine._get_squeeze_fn(int(heads.shape[0]), engine._active_delta_out)
        heads, hv, sq_ov = sq(heads, hv)
        if bool(np.asarray(sq_ov).any()):
            raise CapacityError(engine._active_delta_kind)
    return heads, hv


def _head_may_rederive(rule, od_mask: np.ndarray, rep_old: np.ndarray) -> bool:
    """False iff no overdeleted fact can match the rule's head pattern.

    Per-position relaxation of the host filter (a superset, hence sound):
    head constants are collapsed through the *pre-deletion* rho because the
    overdelete masks were reduced over pre-split normal forms while the rule
    was rewritten under the post-split rho.
    """
    for pos, t in enumerate(rule.head):
        if not is_var(t) and not od_mask[pos][rep_old[t]]:
            return False
    return True


def _head_bindings(rule, od_rows: np.ndarray, rep_old: np.ndarray):
    """Head-variable bindings of the overdeleted instances matching
    ``rule``'s head pattern, or ``None`` for a variable-free head.

    The exact (row-wise) version of :func:`_head_may_rederive`'s
    per-position relaxation, sharing its pre-/post-split correspondence:
    ``od_rows`` are normal forms under the PRE-deletion rho while the rule
    is rewritten under the post-split rho, so head constants are collapsed
    through ``rep_old`` before comparing (a split only refines cliques, so
    ``rep_old[rho_split(c)] == rep_old[c]``).  Variable positions need no
    mapping: a restorable instance binds its head variables from surviving
    store rows, whose values are pre-deletion representatives already —
    bindings holding a *split* representative simply match nothing live
    (those facts come back through the explicit re-insertion seeds).

    Rows are deduplicated; column order is the head's first-occurrence
    variable order — the seed-table contract of
    :func:`repro.core.engine_jax.build_rederive_plan`.
    """
    m = np.ones(od_rows.shape[0], dtype=bool)
    first: dict[int, int] = {}
    for pos, t in enumerate(rule.head):
        if is_var(t):
            if t in first:
                m &= od_rows[:, pos] == od_rows[:, first[t]]
            else:
                first[t] = pos
        else:
            m &= od_rows[:, pos] == rep_old[t]
    if not first:
        return None
    cols = [od_rows[m, pos] for pos in first.values()]
    return np.unique(np.stack(cols, axis=1), axis=0).astype(np.int32)


# ---------------------------------------------------------------------------
# drivers (called by JaxEngine.add_facts / delete_facts inside enable_x64)
# ---------------------------------------------------------------------------

def spmd_add_phases(engine, state: EngineState, delta, max_rounds: int):
    """Phase generator behind :func:`spmd_add_facts`.

    Yields a label at each point a serving scheduler may interleave other
    work (the mutation is NOT epoch-consistent until the generator is
    exhausted): ``"prepared"`` after the explicit-set bookkeeping, then the
    forward fixpoint runs to completion.  A driver must either exhaust the
    generator or roll the state back to a snapshot taken before it started
    (:meth:`JaxEngine._snapshot`) — e.g. on :class:`CapacityError`, whose
    retry restarts the phases from scratch against the restored state.
    A no-effect delta yields nothing.
    """
    tag = engine.dispatches
    try:
        tag.phase = "add:prepare"
        engine._ensure_index(state)  # rebuild only after a capacity re-layout
        delta = dedup_rows(delta)
        delta = setdiff_rows(delta, state.explicit)
        if delta.shape[0] == 0:
            return
        hi = int(delta.max()) + 1
        if hi > state.n_res:  # unseen resource IDs: extend rho with identities
            rep_host = np.asarray(state.rep)
            ext = np.arange(rep_host.shape[0], hi, dtype=rep_host.dtype)
            state.rep = jnp.asarray(np.concatenate([rep_host, ext]))
        state.explicit = np.concatenate([state.explicit, delta], axis=0)
        state.stats.triples_explicit = state.explicit.shape[0]
        engine._presize_delta(delta.shape[0])  # known admitted-batch cardinality
        cands, cand_valid = engine._pad_cands(delta)
        yield "prepared"
        tag.phase = "add:forward"
        engine._forward(state, cands, cand_valid, [], max_rounds)
    finally:
        tag.phase = None


def spmd_add_facts(engine, state: EngineState, delta, max_rounds: int) -> EngineState:
    """Additions: seed the engine's forward loop with the fresh triples."""
    for _phase in spmd_add_phases(engine, state, delta, max_rounds):
        pass
    return state


def spmd_delete_phases(engine, state: EngineState, delta, max_rounds: int):
    """Phase generator behind :func:`spmd_delete_facts`.

    Yield points mark the scheduler-visible stages of the DRed pass:

      * ``"seeded"`` — wave-0 tombstones tagged for the deleted normal forms,
      * ``"wave"`` — after each overdelete wave that tagged new tombstones,
      * ``"overdeleted"`` — tombstones finalised into ``marked`` (the live
        arena now HIDES overdeleted rows that rederivation will restore —
        the mid-round state an epoch snapshot must never expose),
      * ``"split"`` — suspect cliques reverted to singletons and the program
        re-rewritten under the split rho,
      * ``"rederive"`` — the targeted (head-bound, backward-chained)
        rederivation joins have produced their restored instances; the
        forward fixpoint then runs to completion and the generator ends.

    Same contract as :func:`spmd_add_phases`: exhaust or roll back; a
    no-effect delta yields nothing.
    """
    tag = engine.dispatches
    try:
        yield from _delete_phases_tagged(engine, state, delta, max_rounds, tag)
    finally:
        tag.phase = None


def _delete_phases_tagged(engine, state, delta, max_rounds, tag):
    tag.phase = "delete:prepare"
    engine._ensure_index(state)  # rebuild only after a capacity re-layout
    delta = dedup_rows(delta)
    if delta.shape[0] and state.explicit.shape[0]:
        delta = delta[np.isin(pack(delta), pack(state.explicit))]
    else:
        delta = np.zeros((0, 3), np.int32)
    if delta.shape[0] == 0:
        return

    explicit_new = setdiff_rows(state.explicit, delta)
    rep_host = np.asarray(state.rep)
    sizes = clique_sizes(rep_host)

    # -- backward: seed + overdelete waves (epoch-tagged tombstones) ---------
    if engine.use_kernel:
        nf_j, owner_j = kernel_ops.rewrite_owner(
            jnp.asarray(delta, jnp.int32),
            jnp.asarray(rep_host, jnp.int32),
            engine.n_shards,
        )
        nf, owner = np.asarray(nf_j), np.asarray(owner_j)
    else:
        nf = rep_host[delta].astype(np.int32)
        owner = nf[:, 0] % engine.n_shards
    # owner-sorted queries: each shard's matches land in contiguous runs
    nf = dedup_rows(nf[np.argsort(owner, kind="stable")])
    tag.phase = "delete:seed"
    n_od_host = _seed_query(engine, state, nf)
    yield "seeded"
    tag.phase = "delete:wave"

    # wave-1 frontier masks come from the seed normal forms themselves
    masks = np.zeros((3, state.n_res), dtype=bool)
    for pos in range(3):
        masks[pos][nf[:, pos]] = True

    suspect = jnp.zeros((state.n_res,), bool)
    sizes_j = jnp.asarray(sizes, dtype=I32)
    if engine.fuse_rounds:
        # one compiled fixpoint over every wave: tombstone plans + od step
        # run in a single lax.while_loop, convergence decided on device.
        # The host's dead-plan mask filtering is dropped (impossible plans
        # match zero rows inside the trace) — what it saved in compute it
        # cost in per-wave dispatches, the quantity this path exists to kill.
        from .fused import forward_plan_signature, program_tables

        plans_sig = forward_plan_signature(state.program, tombstone=True)
        fn = _fwave_fn(engine, plans_sig)
        ac, hc, _cv, _cvd = program_tables(state.program)
        state.tomb, suspect, fl = fn(
            state.spo, state.epoch, state.marked, state.tomb,
            state.sorted_keys, state.sort_perm, state.rep, sizes_j, suspect,
            jnp.asarray(max_rounds, I32), ac, hc,
        )

        def _flag(name: str) -> bool:
            return bool(np.asarray(fl[name]).reshape(-1)[0])

        state.stats.od_waves += int(np.asarray(fl["iters"]).reshape(-1)[0])
        if _flag("ov_route"):
            raise CapacityError("route")
        if _flag("ov_bind"):
            raise CapacityError(engine._active_bind_kind)
        if _flag("ov_refl") or _flag("ov_out") or _flag("ov_squeeze"):
            # the reflexivity buffer and the plan-output stream are both
            # sized by the ACTIVE delta width — under the wide-buffer
            # fallback that is out_cap, whose growth kind must be named or
            # the (clamped) delta cap would stop growing and the retry loop
            # would spin on the same overflow
            raise CapacityError(engine._active_delta_kind)
        if int(np.asarray(fl["n_new"]).reshape(-1)[0]) > 0:
            raise RuntimeError("did not converge")
        n_wave_total = int(np.asarray(fl["n_od"]).reshape(-1)[0])
        n_od_host += n_wave_total
        if n_wave_total:
            yield "wave"
    else:
        w = 0
        while True:
            w += 1
            state.stats.od_waves += 1
            heads, hv = _tomb_heads(engine, state, w, masks)
            fn = _od_fn(engine, int(heads.shape[0]))
            state.tomb, suspect, n_new, ov_route, ov_refl, od_masks = fn(
                state.spo, state.epoch, state.marked, state.tomb,
                state.sorted_keys, state.sort_perm,
                state.rep, sizes_j, suspect, heads, hv, jnp.asarray(w, I32),
            )
            if bool(np.asarray(ov_route).any()):
                raise CapacityError("route")
            if bool(np.asarray(ov_refl).any()):
                # the reflexivity buffer is sized by the ACTIVE delta width —
                # under the wide-buffer fallback that is out_cap, whose
                # growth kind must be named or the (clamped) delta cap would
                # stop growing and the retry loop would spin on the same
                # overflow
                raise CapacityError(engine._active_delta_kind)
            n_wave = int(np.asarray(n_new).reshape(-1)[0])
            if n_wave == 0:
                break
            n_od_host += n_wave
            masks = np.asarray(od_masks)
            yield "wave"

    tag.phase = "delete:finalize"
    # pre-size the delta buffers from the now-known overdelete cardinality:
    # the rederive seeds and the restored candidate stream scale with it,
    # and discovering that width by overflow restarts mid-stream is the
    # direct mechanism behind the uobm_like steady-event regression
    engine._presize_delta(max(n_od_host, delta.shape[0]))

    # grab the overdeleted rows for the head-bound rederive joins while the
    # tombstone column still identifies them (finalize resets it to -1)
    od_rows = np.zeros((0, 3), np.int32)
    if n_od_host and engine.rederive_mode == "targeted":
        rows, rv, ov = _extract_fn(engine, _pow2(n_od_host))(
            state.spo, state.tomb
        )
        if bool(np.asarray(ov).any()):
            # the extract buffer is sized from the host's running count, so
            # overflow means the count itself is wrong — an invariant
            # violation no capacity growth can fix; surfacing it as a
            # CapacityError would spin the retry loop growing unrelated
            # caps against the same miscount forever
            raise RuntimeError(
                "overdelete extraction overflowed its host-counted bound "
                f"({n_od_host} rows) — tombstone accounting is inconsistent"
            )
        od_rows = np.asarray(rows).reshape(-1, 3)[np.asarray(rv).reshape(-1)]

    (
        state.marked, state.tomb, state.sorted_keys, state.sort_perm,
        od_mask, n_od,
    ) = _finalize_fn(engine)(
        state.spo, state.epoch, state.marked, state.tomb,
        state.sorted_keys, state.sort_perm, state.rep,
    )
    n_od = int(np.asarray(n_od).reshape(-1)[0])
    state.stats.overdeleted += n_od
    yield "overdeleted"

    # -- split: suspect cliques revert to singletons (host rho bookkeeping) --
    suspect_reps = np.flatnonzero(np.asarray(suspect))
    state.stats.suspects_split += int(suspect_reps.shape[0])
    rep_split = split_cliques(rep_host, suspect_reps)
    p_split, _ = state.base_program.rewrite(rep_split)
    state.rep = jnp.asarray(rep_split.astype(np.int32))
    state.program = p_split
    yield "split"
    tag.phase = "delete:rederive"

    # -- rederive: restore overdeleted facts still derivable from survivors --
    # Targeted (default): for each rule whose head pattern can match an
    # overdeleted instance, bind the head variables to those instances and
    # chain the body backward through the persistent sorted index — the
    # DRed/B-F one-step rederivation, with cost proportional to the
    # overdelete delta.  The restored instances seed the forward fixpoint,
    # whose delta discipline finds every consequence.  Whole-rule requeue
    # (evaluating the rule unconstrained against the surviving store)
    # remains only for variable-free heads — a head with no variables
    # admits no instance constraint — and as the "requeue" differential
    # baseline.
    od_mask_h = np.asarray(od_mask)
    requeued = []
    rederived: list[np.ndarray] = []
    if n_od:
        for k, rule in enumerate(p_split.rules):
            if not _head_may_rederive(rule, od_mask_h, rep_host):
                continue
            if engine.rederive_mode != "targeted":
                requeued.append(k)
                state.stats.rederive_full_fallback += 1
                continue
            bind = _head_bindings(rule, od_rows, rep_host)
            if bind is None:
                requeued.append(k)
                state.stats.rederive_full_fallback += 1
            elif bind.shape[0]:
                heads = engine._eval_rule_rederive(state, k, rule, bind)
                state.stats.rederive_targeted += 1
                if heads.shape[0]:
                    rederived.append(heads)
    yield "rederive"

    # seeds: the rederived instances, explicit rows whose (post-split)
    # normal form went missing, and missing reflexive witnesses of
    # resources surviving in the store
    seeds = rederived
    if explicit_new.shape[0]:
        nf_exp = rep_split[explicit_new].astype(np.int32)
        miss = ~_member_query(engine, state, nf_exp)
        if miss.any():
            seeds.append(explicit_new[miss])
    occ = np.asarray(_occ_fn(engine)(state.spo, state.epoch, state.marked, state.rep))
    if occ.any() and n_od:
        res = np.union1d(np.flatnonzero(occ), [SAME_AS]).astype(np.int32)
        refl = np.stack([res, np.full_like(res, SAME_AS), res], axis=1)
        miss_refl = refl[~_member_query(engine, state, refl)]
        if miss_refl.shape[0]:
            seeds.append(miss_refl)
    cands = (
        dedup_rows(np.concatenate(seeds, axis=0))
        if seeds
        else np.zeros((0, 3), np.int32)
    )

    state.explicit = explicit_new
    state.stats.triples_explicit = explicit_new.shape[0]
    cj, cv = engine._pad_cands(cands)
    tag.phase = "delete:forward"
    engine._forward(state, cj, cv, requeued, max_rounds)


def spmd_delete_facts(engine, state: EngineState, delta, max_rounds: int) -> EngineState:
    """Deletions: tombstone waves on-device, split on host, rederive on-device."""
    for _phase in spmd_delete_phases(engine, state, delta, max_rounds):
        pass
    return state


# ---------------------------------------------------------------------------
# dispatch auditor (static half) + audit trace builders (repro.analysis)
# ---------------------------------------------------------------------------

def static_dispatch_profile(program=None) -> dict:
    """Which compiled-fn families each maintenance phase may dispatch.

    The static half of the DispatchAuditor.  Keys are the phase labels the
    generators tag on ``engine.dispatches``; values map each admissible fn
    family to its static dispatch count per unit of that phase — per
    forward ROUND, per overdelete WAVE, per query CHUNK, or per OPERATION —
    the dispatch floor the ROADMAP's fused-fixpoint item is trying to
    lower.  With ``program`` the plan counts are exact for that rule set
    (one delta/tomb plan per body atom; mask filtering and full-plan
    requeues make the observed count vary around them); without it they are
    ``None`` (family admissible, count unstated).  The runtime counter
    (:class:`repro.core.stats.DispatchCounter`) is reconciled against this
    table by :func:`repro.analysis.dispatch_crosscheck` — a family
    dispatching inside a phase that does not list it means a compiled fn
    joined a hot path without declaring itself to the auditor.
    """
    n_plans = (
        sum(len(r.body) for r in program.rules) if program is not None else None
    )
    n_rules = len(program.rules) if program is not None else None
    # the shared forward round.  Fused engines (fuse_rounds=True, the
    # default) dispatch ONE ``fforward`` fixpoint per convergence stretch;
    # host-loop engines (and the wide/requeued rounds the fused branch
    # hands back to the host body) dispatch one process step, the delta
    # plans, and at most one squeeze PER ROUND.  Rounds whose rho merge
    # rewrote rule constants additionally dispatch one merge-targeted
    # ``mplan`` per changed rule (the forward-side analogue of ``rplan``;
    # the "plan" full-mode requeue remains only as the ground-anchor
    # fallback and the rederive_mode="requeue" baseline).
    forward = {
        "fforward": 1, "process": 1, "plan": n_plans, "squeeze": 1,
        "mplan": n_rules,
    }
    return {
        "add:prepare": {"rebuild_index": 1},          # only if index dirty
        "add:forward": dict(forward),
        "delete:prepare": {"rebuild_index": 1},       # only if index dirty
        "delete:seed": {"seed_tombs": 1},             # per query chunk
        # fused: one ``fwave`` fixpoint for ALL waves; host loop: the
        # tombstone plans + squeeze + od step per wave
        "delete:wave": {
            "fwave": 1, "plan": n_plans, "squeeze": 1, "od": 1,
        },
        "delete:finalize": {"extract_od": 1, "finalize_tombs": 1},
        # per matching rule, plus the seed membership/occupancy probes that
        # assemble the forward seeds (member: per query chunk)
        "delete:rederive": {"rplan": n_rules, "member": 1, "occupancy": 1},
        "delete:forward": dict(forward),
        # the capacity-retry machinery (rollback, growth, arena re-layout)
        # tags its own dispatches "retry" so restart costs never masquerade
        # as phase work; the restarted generator re-tags from the top, so
        # only the recovery step itself (at most an index rebuild after a
        # re-layout) may dispatch here
        "retry": {"rebuild_index": 1},
        # serving-tier phases (repro.serve / repro.sparql.batched).
        # "publish" is the per-barrier snapshot publication: one snapshot
        # build, plus an index rebuild riding along when the arena was
        # re-laid-out this epoch.  "query" is batched BGP execution: one
        # ``bgp`` dispatch per (shape, batch) group drained — the count per
        # drain varies with the query mix, so it is admissible-unstated.
        "publish": {"snapshot": 1, "rebuild_index": 1},
        "query": {"bgp": None},
    }


# Builders trace the per-shard step fns exactly as dispatched (single
# device, un-jitted) at the caller's probe geometry.  The ``od`` /
# ``finalize_tombs`` / ``occupancy`` exemptions are deliberate: their
# per-``n_res`` mask reductions scatter arena-length update streams by
# design (the accepted DRed bookkeeping cost), and the arena-length probes
# stay gather-based.

def _audit_chunk(engine):
    q = jnp.zeros((engine.seed_chunk, 3), I32)
    qv = jnp.zeros((engine.seed_chunk,), bool)
    return q, qv


@register_auditable("seed_tombs")
def _audit_seed_tombs(engine, state):
    q, qv = _audit_chunk(engine)
    fn = partial(_seed_tombs, axis=None)
    jx = jax.make_jaxpr(fn)(
        state.sorted_keys, state.sort_perm, state.epoch, state.marked,
        state.tomb, q, qv,
    )
    yield "seed_tombs", jx


@register_auditable("od", skip_passes=("NoArenaScatter",))
def _audit_od(engine, state):
    n_heads = engine.delta_out
    fn = partial(
        _od_step, axis=None, n_shards=1, route_cap=None,
        refl_cap=engine.delta_out,
    )
    jx = jax.make_jaxpr(fn)(
        state.spo, state.epoch, state.marked, state.tomb,
        state.sorted_keys, state.sort_perm, state.rep,
        jnp.zeros((state.n_res,), I32), jnp.zeros((state.n_res,), bool),
        jnp.zeros((n_heads, 3), I32), jnp.zeros((n_heads,), bool),
        jnp.asarray(1, I32),
    )
    yield "od", jx


@register_auditable("finalize_tombs", skip_passes=("NoArenaScatter",))
def _audit_finalize_tombs(engine, state):
    fn = partial(_finalize_tombs, axis=None)
    jx = jax.make_jaxpr(fn)(
        state.spo, state.epoch, state.marked, state.tomb,
        state.sorted_keys, state.sort_perm, state.rep,
    )
    yield "finalize_tombs", jx


@register_auditable("extract_od")
def _audit_extract_od(engine, state):
    fn = partial(_extract_tombed, axis=None, cap=64)
    jx = jax.make_jaxpr(fn)(state.spo, state.tomb)
    yield "extract_od", jx


@register_auditable("member")
def _audit_member(engine, state):
    q, qv = _audit_chunk(engine)
    fn = partial(_member, axis=None)
    jx = jax.make_jaxpr(fn)(state.sorted_keys, q, qv)
    yield "member", jx


@register_auditable("occupancy", skip_passes=("NoArenaScatter",))
def _audit_occupancy(engine, state):
    fn = partial(_occupancy, axis=None)
    jx = jax.make_jaxpr(fn)(state.spo, state.epoch, state.marked, state.rep)
    yield "occupancy", jx


# imported for its registration side effect: the fused fixpoint fns join
# the audit inventory (``fforward`` / ``fwave``) whenever the incremental
# machinery is loaded.  Must sit at module END — fused.py lazily imports
# ``_od_step`` back from this module inside its wave body.
from . import fused  # noqa: E402, F401

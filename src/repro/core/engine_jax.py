"""Fixed-capacity JAX materialisation engine (REW mode) — the production path.

The numpy engine in :mod:`repro.core.seminaive` is the flexible reference
oracle; this module is the TPU-shaped implementation: every buffer has a
static capacity, every step is a pure jittable function, and the same round
body runs single-device or SPMD under ``shard_map`` (pass ``mesh=``).

Design:
  * store  = arena ``spo (CAP,3) int32`` + ``epoch (CAP,) int32`` (-1 = free,
    else the round the fact was inserted) + ``marked (CAP,) bool`` (the
    paper's outdated bit; marked facts are skipped by matching but retained),
  * delta discipline via epochs: round r matches Delta = (epoch == r-1),
    T_old = (epoch <= r-2), T_all = (epoch <= r-1),
  * joins  = range scans on a persistent index for atoms whose fixed
    positions prefix (s, p, o) or (p, o, s); the rest sort the (small)
    binding table + searchsorted over packed int64 keys.  Static output
    capacities and overflow flags (host retries with doubled capacity) —
    the arena itself is never sorted inside a round,
  * index  = two persistent sorted views of each shard's live arena rows,
    in (s, p, o) order (``EngineState.sort_perm``/``sorted_keys``) and in
    (p, o, s) order (``pos_perm``/``pos_keys``), built once and maintained
    incrementally: fresh rows rank-merge in (:mod:`repro.kernels.merge`),
    swept/finalised rows leave via a stable partition, and a full argsort
    happens at most once per mutation epoch (capacity growth / adoption),
  * rho    = replicated representative array; merges via
    :func:`repro.core.uf.merge_pairs_jax` (min-hooking + pointer doubling),
  * rule rewriting happens on the host at the round barrier; rule *constants*
    are traced arguments, so rewriting a rule never re-traces its plan.

Distribution (the paper's N threads -> mesh ``data`` axis):
  * the arena is sharded by rows; a fact lives on shard ``subject % D``,
  * plan evaluation joins replicated bindings against the local shard and
    ``all_gather``s bindings between atoms (new sameAs pairs and candidate
    heads are few relative to the store — the paper's own observation),
  * rho is replicated and updated identically on every shard (min-hooking is
    order-independent, so no coordination is needed — the paper needed CAS),
  * candidate facts and sweep rewrites are re-routed to their owner shard by
    the gather + ownership filter (the all_to_all analogue),
  * convergence flags are psum'd.

Everything runs inside an ``enable_x64`` scope because packed triple keys
need 63 bits; inputs/outputs stay int32.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import enable_x64, shard_map as compat_shard_map

from repro.kernels import ops as kernel_ops
from repro.kernels.merge import merge_sorted

from .rules import Program, Rule
from .stats import DispatchCounter, MatStats
from .terms import DIFFERENT_FROM, SAME_AS, is_var
from .uf import FrozenRho, compress_np, merge_pairs_jax

I32 = jnp.int32
# numpy scalar (not jnp): module import happens outside the enable_x64 scope
KEY_MAX = np.int64((1 << 63) - 1)  # > any packed key (IDs <= MAX_ID)

# epoch predicates for matching.  PRED_OLD/DELTA/ALL drive the forward
# (derivation) rounds; PRED_TSTORE/TDELTA drive the DRed overdelete waves of
# the incremental delete path (repro.core.incremental_spmd): deletions are
# epoch-tagged *tombstones* in the ``tomb`` column (-1 = live, else the
# overdelete wave that retracted the row), and wave w matches
# Delta = (tomb == w-1) against the full pre-deletion store.
PRED_OLD, PRED_DELTA, PRED_ALL = 0, 1, 2
PRED_TSTORE, PRED_TDELTA = 3, 4


def _pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1)."""
    return 1 << max(int(n) - 1, 0).bit_length()


def _pack3(spo: jnp.ndarray) -> jnp.ndarray:
    s = spo[..., 0].astype(jnp.int64)
    p = spo[..., 1].astype(jnp.int64)
    o = spo[..., 2].astype(jnp.int64)
    return (s << 42) | (p << 21) | o


def _pack_pos(spo: jnp.ndarray) -> jnp.ndarray:
    """Packed key in (p, o, s) order: the second index's key, and the
    published snapshot's secondary view's."""
    s = spo[..., 0].astype(jnp.int64)
    p = spo[..., 1].astype(jnp.int64)
    o = spo[..., 2].astype(jnp.int64)
    return (p << 42) | (o << 21) | s


# key orders of the two persistent arena indexes: key position i holds
# triple position ORDER[i]
SPO_ORDER, POS_ORDER = (0, 1, 2), (1, 2, 0)


def _pack_cols(cols: list[jnp.ndarray]) -> jnp.ndarray:
    key = jnp.zeros(cols[0].shape, dtype=jnp.int64)
    for c in cols:
        key = (key << 21) | c.astype(jnp.int64)
    return key


def argsort_keys(keys: jnp.ndarray) -> jnp.ndarray:
    """Stable ascending argsort of packed int64 keys, as int32 positions.

    The permutation of ``jnp.argsort(keys, stable=True)``, with the position
    as a second sort key in place of a stable sort: the TPU compiler builds
    that in about half the time for 64-bit keys (2^19 rows: 35 s against
    76 s, compiled for a v5e on a shared CPU host), and every engine program
    that sorts keys pays it once per shape.
    """
    iota = jax.lax.broadcasted_iota(I32, keys.shape, 0)
    return jax.lax.sort((keys, iota), num_keys=2, is_stable=False)[1]


def _epoch_ok(
    epoch: jnp.ndarray, marked: jnp.ndarray, tomb: jnp.ndarray, r, pred: int
) -> jnp.ndarray:
    """Row-selection predicates.

    The forward predicates ignore ``tomb``: process_candidates and the
    forward rounds only ever run when every tombstone has been finalised
    into ``marked`` (the invariant kept by incremental_spmd).  The tombstone
    predicates match the *pre-deletion* store — a tombstoned row is still a
    join candidate during the backward closure, exactly like DRed matching
    deleted facts against T.
    """
    live = (epoch >= 0) & ~marked
    if pred == PRED_TSTORE:
        return live
    if pred == PRED_TDELTA:
        return live & (tomb == r - 1)
    if pred == PRED_OLD:
        return live & (epoch <= r - 2)
    if pred == PRED_DELTA:
        return live & (epoch == r - 1)
    return live & (epoch <= r - 1)


def _match_atom(spo, ok, consts, const_mask, eq_pairs):
    """const_mask/eq_pairs are static; consts is a traced (3,) int32."""
    for pos in range(3):
        if const_mask[pos]:
            ok = ok & (spo[:, pos] == consts[pos])
    for a, b in eq_pairs:
        ok = ok & (spo[:, a] == spo[:, b])
    return ok


def _compact(cols: dict, valid: jnp.ndarray, cap: int):
    """Pack valid rows to the front, truncating (or padding) at ``cap``.

    A stable partition *without sorting*: output slot ``j`` gathers the
    ``(j+1)``-th valid row, found by binary search over the inclusive
    cumsum of ``valid`` — one O(cap log n) search plus gathers, instead of
    an input-length scatter per column.  Invalid rows — and valid rows past
    ``cap``, which raise the overflow flag — are simply never gathered.
    Output rows beyond ``n_valid`` hold zeros and must stay masked by the
    returned validity.
    """
    cum = jnp.cumsum(valid)
    n_valid = cum[-1]
    j = jnp.arange(cap)
    src = jnp.clip(
        jnp.searchsorted(cum, j + 1, side="left"), 0, valid.shape[0] - 1
    )
    out_valid = j < n_valid
    out_cols = {v: jnp.where(out_valid, c[src], 0) for v, c in cols.items()}
    overflow = n_valid > cap
    return out_cols, out_valid, overflow


def _index_remove(sort_perm, sorted_keys, dead, trash):
    """Drop rows flagged ``dead`` from the sorted arena index.

    A stable partition of the surviving entries (cumsum + binary-searched
    gather, no sort — survivors keep their relative, hence sorted, order);
    freed tail slots revert to the ``trash`` row / KEY_MAX padding.
    """
    C = sorted_keys.shape[0]
    keep = (sorted_keys < KEY_MAX) & ~dead[sort_perm]
    cum = jnp.cumsum(keep)
    src = jnp.clip(
        jnp.searchsorted(cum, jnp.arange(C) + 1, side="left"), 0, C - 1
    )
    ok = jnp.arange(C) < cum[-1]
    new_perm = jnp.where(ok, sort_perm[src], trash)
    new_keys = jnp.where(ok, sorted_keys[src], KEY_MAX)
    return new_perm, new_keys


def _expand_join(
    cols, valid, spo, ok, bound_items, free_items, out_cap,
    use_kernel=False,
):
    """Join bindings against (spo, ok) on ``bound_items``; static structure.

    bound_items: list of (var, atom_pos) already present in ``cols``.
    free_items:  list of (var, atom_pos) newly bound by this atom.

    The *binding table* (bind_cap rows) is sorted — never the arena: each
    ok store row counts its matching bindings by searchsorted, and the
    output enumerates (store row, binding) pairs store-major.  Invalid
    bindings are excluded by explicit mask logic, not a key sentinel: their
    keys are forced to KEY_MAX and KEY_MAX store keys are excluded from
    counting (KEY_MAX packs only the all-max-ID triple, above ``MAX_ID`` —
    the former ``KEY_MAX - 1`` probe sentinel aliased a representable key
    at the 21-bit ID boundary).
    """
    if bound_items:
        skey = _pack_cols([spo[:, pos] for _, pos in bound_items])
        bkey = _pack_cols([cols[v] for v, _ in bound_items])
    else:
        skey = jnp.zeros(spo.shape[0], dtype=jnp.int64)
        bkey = jnp.zeros(valid.shape[0], dtype=jnp.int64)
    bkey = jnp.where(valid, bkey, KEY_MAX)
    if use_kernel:  # sort-free Pallas counting-rank dedup (same stable order)
        border = kernel_ops.dedup_order(bkey)
    else:
        border = argsort_keys(bkey)  # bind_cap-sized — never the arena
    bkey_s = bkey[border]
    # unrolled binary search: the arena-length query side makes the scan
    # loop's per-step dispatch the dominant cost on CPU
    lo = jnp.searchsorted(bkey_s, skey, side="left", method="scan_unrolled")
    hi = jnp.searchsorted(bkey_s, skey, side="right", method="scan_unrolled")
    counts = jnp.where(ok & (skey != KEY_MAX), hi - lo, 0)
    cum = jnp.cumsum(counts) - counts  # exclusive
    total = counts.sum()
    j = jnp.arange(out_cap)
    seg = jnp.searchsorted(cum, j, side="right") - 1
    seg = jnp.clip(seg, 0, spo.shape[0] - 1)
    within = j - cum[seg]
    brow = border[jnp.clip(lo[seg] + within, 0, valid.shape[0] - 1)]
    out_valid = j < total
    new_cols = {v: jnp.where(out_valid, cols[v][brow], 0) for v in cols}
    for v, pos in free_items:
        new_cols[v] = jnp.where(out_valid, spo[seg, pos], 0)
    return new_cols, out_valid, total > out_cap, total


@dataclass(frozen=True)
class _AtomSpec:
    """Static structure of one body atom within a plan."""

    index: int
    const_mask: tuple[bool, bool, bool]
    eq_pairs: tuple[tuple[int, int], ...]
    bound_items: tuple[tuple[int, int], ...]
    free_items: tuple[tuple[int, int], ...]
    pred: int
    count_appl: bool = False  # this atom feeds the 'Rule appl.' counter


def _index_prefix(spec: _AtomSpec):
    """Static test: which persistent index, if any, serves this atom's join?

    An index serves it when the atom's *fixed* positions (constants +
    already-bound variables, including equality duplicates of bound
    variables) form a prefix of that index's key order, so each binding's
    matches are one contiguous key range.  (s, p, o) is tried first, so
    atoms it serves keep its path; (p, o, s) serves the (p, o)- and
    p-bound atoms it cannot.  Returns ``(order, k, components)`` with
    ``order`` the index's key order (:data:`SPO_ORDER` / :data:`POS_ORDER`),
    ``k`` the prefix length and ``components`` the value source of each
    prefix position in key order (``("const", pos)`` or ``("var",
    var_id)``), or ``(None, None, None)`` when the join must fall back to
    the generic arena-probing path.
    """
    pos_src: dict[int, tuple] = {}
    for v, p in spec.bound_items:
        pos_src[p] = ("bound", v)
    for v, p in spec.free_items:
        pos_src[p] = ("free", v)
    for a, b in spec.eq_pairs:
        if a in pos_src:
            pos_src[b] = pos_src[a]
    fixed = [
        spec.const_mask[p] or pos_src.get(p, ("free",))[0] == "bound"
        for p in range(3)
    ]
    for order in (SPO_ORDER, POS_ORDER):
        k = 0
        while k < 3 and fixed[order[k]]:
            k += 1
        if k == 0 or any(fixed[p] for p in order[k:]):
            continue
        comp = tuple(
            ("const", p) if spec.const_mask[p] else ("var", pos_src[p][1])
            for p in order[:k]
        )
        return order, k, comp
    return None, None, None


def plan_join_counts(plan, seeded: bool = False) -> tuple[int, int]:
    """``(index_joins, arena_probe_joins)``: the join steps of one
    evaluation of ``plan`` that run as index range scans and as the
    generic arena-probing join.  An unseeded plan (:func:`eval_plan`)
    opens with a scan of its first atom, which is no join; a seeded one
    (:func:`eval_plan_rederive`) joins at every step."""
    n_index = n_arena = 0
    for step, spec in enumerate(plan):
        if step == 0 and not seeded and not spec.bound_items:
            continue
        if _index_prefix(spec)[0] is None:
            n_arena += 1
        else:
            n_index += 1
    return n_index, n_arena


def count_joins(stats: MatStats, plans, seeded: bool = False, times: int = 1):
    """Book ``times`` evaluations of each of ``plans`` on ``stats``'s
    ``index_joins`` / ``arena_probe_joins``."""
    for plan in plans:
        n_index, n_arena = plan_join_counts(plan, seeded)
        stats.index_joins += times * n_index
        stats.arena_probe_joins += times * n_arena


def _atom_static(atom, bound_vars: set[int]):
    const_mask = tuple(not is_var(t) for t in atom)
    eq_pairs = []
    first_pos: dict[int, int] = {}
    for pos, t in enumerate(atom):
        if is_var(t):
            if t in first_pos:
                eq_pairs.append((first_pos[t], pos))
            else:
                first_pos[t] = pos
    bound = tuple((v, p) for v, p in first_pos.items() if v in bound_vars)
    free = tuple((v, p) for v, p in first_pos.items() if v not in bound_vars)
    return const_mask, tuple(eq_pairs), bound, free


def _bound_first(body, remaining, bound) -> list[int]:
    """Greedy join order of the ``remaining`` body atoms: each step takes
    the first atom sharing a variable with the variables bound so far (else
    the first left), so bound positions form index key prefixes."""
    remaining, bound, order = list(remaining), set(bound), []
    while remaining:
        j = next(
            (i for i in remaining
             if any(is_var(t) and t in bound for t in body[i])),
            remaining[0],
        )
        remaining.remove(j)
        order.append(j)
        bound |= {t for t in body[j] if is_var(t)}
    return order


def build_plans(
    rule: Rule, full: bool, tombstone: bool = False
) -> list[list[_AtomSpec]]:
    """Delta plans (or the single full-evaluation plan) of a rule.

    Delta plan ``i`` starts at its delta atom ``i`` — a scan of the delta
    rows — and chains the other atoms greedily bound-first
    (:func:`_bound_first`), so each join step binds positions that prefix
    one of the two persistent indexes and runs as range scans
    (:func:`_index_prefix`) instead of probing the arena.  Each atom keeps
    its semi-naive predicate (PRED_OLD before the delta atom in body order,
    PRED_ALL after it) and its body index (``_AtomSpec.index``), so the
    order changes no match and no count.  The full plan keeps body order.

    ``tombstone=True`` builds the DRed overdelete variants: the delta atom
    matches the last overdelete wave (PRED_TDELTA) and every other atom the
    full pre-deletion store (PRED_TSTORE) — the device analogue of the host
    path's ``eval_rule_delta(rule, T, T, frontier)``.
    """
    assert not (full and tombstone)
    plans = []
    body = rule.body
    delta_positions = [0] if full else list(range(len(body)))
    for i in delta_positions:
        if full:
            order = list(range(len(body)))
        else:
            rest = [j for j in range(len(body)) if j != i]
            order = [i] + _bound_first(body, rest, {t for t in body[i] if is_var(t)})
        specs = []
        bound: set[int] = set()
        for j in order:
            const_mask, eq_pairs, b, f = _atom_static(body[j], bound)
            if full:
                pred = PRED_ALL
            else:
                pred = PRED_OLD if j < i else (PRED_DELTA if j == i else PRED_ALL)
            if tombstone:
                pred = PRED_TDELTA if pred == PRED_DELTA else PRED_TSTORE
            count_appl = not tombstone and (
                (pred == PRED_DELTA) or (full and j == 0)
            )
            specs.append(_AtomSpec(j, const_mask, eq_pairs, b, f, pred, count_appl))
            bound |= {v for v, _ in b} | {v for v, _ in f}
        plans.append(specs)
    return plans


def _expand_join_index(
    cols, valid, spo, epoch, marked, tomb, r, keys, perm,
    consts, spec: "_AtomSpec", k: int, comp: tuple, out_cap: int,
):
    """Index-backed variant of :func:`_expand_join` for prefix-key atoms.

    ``keys``/``perm`` is the persistent index whose key order the atom's
    fixed positions prefix — (s, p, o) or (p, o, s), per
    :func:`_index_prefix`, whose ``comp`` lists the prefix in that order.
    Each binding's matches in the live store are one contiguous range of
    it (``[pack(prefix, 0..), pack(prefix, max..)]``), so the join is two
    ``searchsorted`` calls *per binding table* plus the output enumeration
    — O(bind log C + out) with no arena-length intermediate at all.  A
    range holds every live row with the prefix; matched rows are
    post-filtered by the atom's predicate (``_epoch_ok(spec.pred)``) and
    its intra-atom equalities, so rows the predicate drops (older or newer
    than a PRED_OLD / PRED_DELTA atom admits) cost masked output slots,
    never correctness.
    """
    maxid = jnp.int64((1 << 21) - 1)
    lo_parts, hi_parts = [], []
    for p in range(3):
        if p < k:
            src, ref = comp[p]
            if src == "const":
                col = jnp.broadcast_to(
                    consts[ref].astype(jnp.int64), valid.shape
                )
            else:
                col = cols[ref].astype(jnp.int64)
            lo_parts.append(col)
            hi_parts.append(col)
        else:
            lo_parts.append(jnp.zeros(valid.shape, jnp.int64))
            hi_parts.append(jnp.broadcast_to(maxid, valid.shape))
    lokey = _pack_cols(lo_parts)
    hikey = _pack_cols(hi_parts)
    lo = jnp.searchsorted(keys, lokey, side="left")
    hi = jnp.searchsorted(keys, hikey, side="right")
    counts = jnp.where(valid, jnp.maximum(hi - lo, 0), 0)
    cum = jnp.cumsum(counts) - counts  # exclusive
    total = counts.sum()
    j = jnp.arange(out_cap)
    seg = jnp.searchsorted(cum, j, side="right") - 1
    seg = jnp.clip(seg, 0, valid.shape[0] - 1)
    within = j - cum[seg]
    srow = perm[jnp.clip(lo[seg] + within, 0, perm.shape[0] - 1)]
    out_valid = j < total
    rows = spo[srow]
    okr = _epoch_ok(epoch[srow], marked[srow], tomb[srow], r, spec.pred)
    okr = _match_atom(rows, okr, consts, spec.const_mask, spec.eq_pairs)
    out_valid = out_valid & okr
    new_cols = {v: jnp.where(out_valid, cols[v][seg], 0) for v in cols}
    for v, pos in spec.free_items:
        new_cols[v] = jnp.where(out_valid, rows[:, pos], 0)
    return new_cols, out_valid, total > out_cap


def _gather(x, axis):
    return jax.lax.all_gather(x, axis, tiled=True)


def _route_rows(stream, flags, valid, axis, n_shards, route_cap):
    """Owner-route an (N, 3) triple stream to shard ``subject % n_shards``.

    The bulk analogue of the paper's per-thread insertion into the shared
    store, shared by process_candidates and the incremental delete path
    (tombstone waves): each shard routes every row to its owner with one
    ``all_to_all`` of (n_shards, route_cap) buckets.  ``flags`` is an
    optional (N, k) int32 array of side columns that ride along with the
    rows.  Returns ``(stream', flags', valid', overflow)``:

      * ``axis is None`` — identity (single device),
      * ``route_cap is None`` — all-gather fallback: every shard sees the
        global stream, masked down to the rows it owns,
      * otherwise — bucket exchange; per-destination overflow beyond
        ``route_cap`` raises the engine's capacity-retry via the flag.
    """
    if axis is None:
        return stream, flags, valid, jnp.zeros((), bool)
    if route_cap is None:
        me = jax.lax.axis_index(axis)
        stream = _gather(stream, axis)
        flags = _gather(flags, axis) if flags is not None else None
        valid = _gather(valid, axis)
        own = (stream[:, 0] % n_shards).astype(I32) == me
        return stream, flags, valid & own, jnp.zeros((), bool)
    k = 0 if flags is None else flags.shape[1]
    owner = (stream[:, 0] % n_shards).astype(I32)
    okey = jnp.where(valid, owner, n_shards)
    order = jnp.argsort(okey, stable=True).astype(I32)
    so = okey[order]
    starts = jnp.searchsorted(so, jnp.arange(n_shards, dtype=I32)).astype(I32)
    pos = jnp.arange(so.shape[0], dtype=I32) - starts[jnp.clip(so, 0, n_shards - 1)]
    keep = (so < n_shards) & (pos < route_cap)
    overflow = jnp.any((so < n_shards) & (pos >= route_cap))
    cols = [stream[order]]
    if flags is not None:
        cols.append(flags[order])
    cols.append(keep[:, None].astype(I32))
    payload = jnp.concatenate(cols, axis=1)  # (N, 3 + k + 1)
    buckets = jnp.zeros((n_shards, route_cap, 3 + k + 1), I32)
    tgt_shard = jnp.where(keep, so, 0)
    tgt_slot = jnp.where(keep, pos, route_cap)  # out-of-range -> dropped
    buckets = buckets.at[tgt_shard, tgt_slot].set(
        jnp.where(keep[:, None], payload, 0), mode="drop"
    )
    recv = jax.lax.all_to_all(buckets, axis, split_axis=0, concat_axis=0, tiled=True)
    out_stream = recv[..., :3].reshape(-1, 3)
    out_flags = recv[..., 3 : 3 + k].reshape(-1, k) if flags is not None else None
    out_valid = recv[..., 3 + k].reshape(-1).astype(bool)
    return out_stream, out_flags, out_valid, overflow


def eval_plan(
    spo,
    epoch,
    marked,
    tomb,
    sorted_keys,
    sort_perm,
    pos_keys,
    pos_perm,
    r,
    atom_consts,  # (n_atoms, 3) traced rule constants (vars hold garbage 0)
    head_consts,  # (3,) traced
    plan: tuple,  # static tuple of _AtomSpec
    head_var_slots: tuple,  # static: per head position, var id or None
    bind_cap: int,
    out_cap: int,
    axis: str | None = None,
    use_kernel: bool = False,
):
    """Evaluate one delta plan; returns (heads (out_cap,3), valid, stats...).

    Under SPMD (``axis`` set): each atom joins against the *local* store
    shard; bindings are all_gathered between atoms so every shard sees the
    global binding table.  The final join's results stay local — their union
    over shards is the global candidate set.

    The plan's first atom (the delta atom of a delta plan) is scanned;
    every later atom joins through :func:`_join_step` — range scans on the
    (s, p, o) or (p, o, s) index where its fixed positions prefix one,
    else the generic bindings-sorting join.
    """
    indexes = {SPO_ORDER: (sorted_keys, sort_perm), POS_ORDER: (pos_keys, pos_perm)}
    cols: dict[int, jnp.ndarray] = {}
    valid = jnp.ones((1,), dtype=bool)  # the unit binding
    n_appl = jnp.zeros((), I32)
    overflow = jnp.zeros((), bool)
    for step, spec in enumerate(plan):
        is_join = not (step == 0 and not spec.bound_items)
        if spec.count_appl or not is_join:
            ok = _epoch_ok(epoch, marked, tomb, r, spec.pred)
            ok = _match_atom(
                spo, ok, atom_consts[spec.index], spec.const_mask, spec.eq_pairs
            )
            if spec.count_appl:
                n_appl = n_appl + ok.sum().astype(I32)
        if not is_join:
            # initial scan: bindings = matching rows directly (no join needed)
            cols = {v: jnp.where(ok, spo[:, p], 0) for v, p in spec.free_items}
            valid = ok
            cols, valid, ov = _compact(cols, valid, bind_cap)
        else:
            cols, valid, ov = _join_step(
                cols, valid, spo, epoch, marked, tomb, r,
                indexes, atom_consts[spec.index], spec, bind_cap,
                use_kernel=use_kernel,
            )
        overflow |= ov
        if axis is not None and step < len(plan) - 1:
            cols = {v: _gather(c, axis) for v, c in cols.items()}
            valid = _gather(valid, axis)
    out, out_valid, n_deriv, ov = _emit_heads(
        cols, valid, head_consts, head_var_slots, out_cap
    )
    # bind and out overflow reported separately so the host retry can grow
    # exactly the capacity that was exhausted
    return out, out_valid, n_deriv[None], n_appl[None], overflow[None], ov[None]


def _join_step(
    cols, valid, spo, epoch, marked, tomb, r, indexes: dict,
    consts, spec: _AtomSpec, bind_cap: int, use_kernel: bool = False,
):
    """One join step of a plan, shared by :func:`eval_plan` and
    :func:`eval_plan_rederive`.  ``indexes`` maps each key order
    (:data:`SPO_ORDER`, :data:`POS_ORDER`) to its persistent index
    ``(keys, perm)``.  An atom whose fixed positions prefix either order
    runs as range scans on that index (:func:`_index_prefix` prefers
    (s, p, o)), whatever its predicate — the matched rows are
    post-filtered by it; the rest take the generic bindings-sorting join,
    which probes every arena row.  Returns ``(cols, valid, overflow)``.
    """
    order, k, comp = _index_prefix(spec)
    if order is not None:
        keys, perm = indexes[order]
        return _expand_join_index(
            cols, valid, spo, epoch, marked, tomb, r,
            keys, perm, consts, spec, k, comp, bind_cap,
        )
    ok = _epoch_ok(epoch, marked, tomb, r, spec.pred)
    ok = _match_atom(spo, ok, consts, spec.const_mask, spec.eq_pairs)
    cols, valid, ov, _ = _expand_join(
        cols, valid, spo, ok, spec.bound_items, spec.free_items, bind_cap,
        use_kernel=use_kernel,
    )
    return cols, valid, ov


def _emit_heads(cols, valid, head_consts, head_var_slots: tuple, out_cap: int):
    """Instantiate the head pattern over a binding table and compact it to
    the output buffer; returns ``(out, out_valid, n_deriv, overflow)``."""
    heads = []
    for pos in range(3):
        v = head_var_slots[pos]
        if v is None:
            heads.append(jnp.broadcast_to(head_consts[pos], valid.shape).astype(I32))
        else:
            heads.append(cols[v].astype(I32))
    out = jnp.stack(heads, axis=1)
    outc, out_valid, ov = _compact(
        {"s": out[:, 0], "p": out[:, 1], "o": out[:, 2]}, valid, out_cap
    )
    out = jnp.stack([outc["s"], outc["p"], outc["o"]], axis=1)
    return out, out_valid, out_valid.sum().astype(I32), ov


def build_rederive_plan(rule: Rule) -> tuple[list[_AtomSpec], tuple[int, ...]]:
    """The single head-bound plan of a rule for targeted rederivation.

    Delete-side rederivation only ever needs to restore *overdeleted* head
    instances, so instead of evaluating the whole rule against the surviving
    store the join is chained backward from the head: the head variables are
    pre-bound (to the overdeleted instances — see
    ``incremental_spmd._head_bindings``) and every body atom matches the
    surviving live store (``PRED_TSTORE``).  Body atoms are greedily
    reordered so each step shares a variable with the already-bound set
    where possible — bound positions then form packed-key prefixes and the
    join runs as range queries on a persistent index.

    Returns ``(specs, head_vars)`` where ``head_vars`` is the head's
    first-occurrence variable order — the column order the seed table must
    use (``_AtomSpec.index`` keeps the original atom index for constant
    lookup).
    """
    head_vars = tuple(dict.fromkeys(t for t in rule.head if is_var(t)))
    bound: set[int] = set(head_vars)
    specs: list[_AtomSpec] = []
    for j in _bound_first(rule.body, range(len(rule.body)), bound):
        const_mask, eq_pairs, b, f = _atom_static(rule.body[j], bound)
        specs.append(_AtomSpec(j, const_mask, eq_pairs, b, f, PRED_TSTORE))
        bound |= {v for v, _ in b} | {v for v, _ in f}
    return specs, head_vars


def eval_plan_rederive(
    spo,
    epoch,
    marked,
    tomb,
    sorted_keys,
    sort_perm,
    pos_keys,
    pos_perm,
    atom_consts,  # (n_atoms, 3) traced rule constants (vars hold garbage 0)
    head_consts,  # (3,) traced
    seeds,        # (seed_cap, n_seed_vars) replicated head-variable bindings
    seed_valid,   # (seed_cap,) replicated
    plan: tuple,  # static tuple of _AtomSpec from build_rederive_plan
    head_var_slots: tuple,
    seed_vars: tuple,  # static: variable id per seed column
    bind_cap: int,
    out_cap: int,
    axis: str | None = None,
    use_kernel: bool = False,
):
    """Head-bound rederivation join; returns (heads, valid, n_deriv, ovs...).

    The binding table starts from the replicated seed columns instead of an
    arena scan, so every join intermediate — and every sort — scales with
    the overdelete delta, never with the surviving arena.  Atoms whose fixed
    positions prefix (s, p, o) or (p, o, s) probe that persistent index
    (:func:`_expand_join_index`); the rest take the generic
    bindings-sorting join.  Mirrors :func:`eval_plan`'s SPMD discipline:
    bindings are all_gathered between atoms, the final join's results stay
    local.
    """
    indexes = {SPO_ORDER: (sorted_keys, sort_perm), POS_ORDER: (pos_keys, pos_perm)}
    r = jnp.zeros((), I32)  # PRED_TSTORE ignores the round counter
    cols = {v: seeds[:, i].astype(I32) for i, v in enumerate(seed_vars)}
    valid = seed_valid
    overflow = jnp.zeros((), bool)
    for step, spec in enumerate(plan):
        cols, valid, ov = _join_step(
            cols, valid, spo, epoch, marked, tomb, r,
            indexes, atom_consts[spec.index], spec, bind_cap,
            use_kernel=use_kernel,
        )
        overflow |= ov
        if axis is not None and step < len(plan) - 1:
            cols = {v: _gather(c, axis) for v, c in cols.items()}
            valid = _gather(valid, axis)
    out, out_valid, n_deriv, ov_out = _emit_heads(
        cols, valid, head_consts, head_var_slots, out_cap
    )
    return out, out_valid, n_deriv[None], overflow[None], ov_out[None]


def classify_remerge(rule_old: Rule, rule_new: Rule):
    """How to re-evaluate one rule whose constants a rho re-merge rewrote.

    Returns ``("skip", None)``, ``("anchor", j)`` or ``("full", None)``:

    * ``"skip"`` — only the head changed.  The body is unchanged, so the
      match set is exactly the one already enumerated under the old
      spelling, and the sweep re-normalises the stored head instances under
      the new rho; nothing needs evaluating.
    * ``("anchor", j)`` — body atom ``j`` changed and has at least one
      variable: evaluate the single merge-targeted plan of
      :func:`build_merge_plan` anchored there.  Among changed variable
      atoms the anchor is the one sharing the most variables with the rest
      of the body (ties to the earliest atom), so the chained joins stay
      bound-first.
    * ``"full"`` — every changed body atom is variable-free.  A ground
      anchor contributes no binding columns, so the remaining atoms would
      chain as unconstrained cross-products at delta widths — strictly
      worse than the wide-buffer full plan.  Whole-rule requeue.
    """
    changed = [
        j for j, (a, b) in enumerate(zip(rule_old.body, rule_new.body))
        if a != b
    ]
    if not changed:
        return "skip", None
    scored = []
    for j in changed:
        vs = {t for t in rule_new.body[j] if is_var(t)}
        if not vs:
            continue
        rest = {
            t for i, atom in enumerate(rule_new.body) if i != j
            for t in atom if is_var(t)
        }
        scored.append((len(vs & rest), -j))
    if not scored:
        return "full", None
    _, neg_j = max(scored)
    return "anchor", -neg_j


def build_merge_plan(rule: Rule, anchor: int) -> list[_AtomSpec]:
    """The single merge-targeted plan of a rule a rho re-merge rewrote.

    A re-merge creates new matches in two disjoint ways: matches using at
    least one row of the merge round's fresh delta (the sweep re-inserts
    every rewritten spelling as a fresh row, so the ordinary delta plans of
    the rewritten program cover those), and matches whose rows are ALL
    pre-merge.  An all-old match that is new must place an old row at a
    *changed* atom — under the old spelling that row could not have
    matched — so scanning one changed atom (the anchor) against the
    pre-merge store (``PRED_OLD``) and chaining the remaining atoms through
    the live store (``PRED_ALL``) enumerates a superset of the new all-old
    matches.  The anchor's rewritten constant keeps that scan narrow (rows
    touching the merged representative), which is the point: the whole-rule
    full plan this replaces opens with an unconstrained store-wide scan.

    Remaining atoms are ordered greedily bound-first (exactly like
    :func:`build_rederive_plan`) so bound positions form packed-key
    prefixes for a persistent index.
    """
    const_mask, eq_pairs, b, f = _atom_static(rule.body[anchor], set())
    specs = [_AtomSpec(anchor, const_mask, eq_pairs, b, f, PRED_OLD, True)]
    bound = {v for v, _ in b} | {v for v, _ in f}
    remaining = [j for j in range(len(rule.body)) if j != anchor]
    for j in _bound_first(rule.body, remaining, bound):
        const_mask, eq_pairs, b, f = _atom_static(rule.body[j], bound)
        specs.append(_AtomSpec(j, const_mask, eq_pairs, b, f, PRED_ALL))
        bound |= {v for v, _ in b} | {v for v, _ in f}
    return specs


def process_candidates(
    spo,
    epoch,
    marked,
    n_used,
    rep,
    sort_perm,
    sorted_keys,
    pos_perm,
    pos_keys,
    cands,
    cand_valid,
    r,
    rewrite_cap: int,
    axis: str | None = None,
    n_shards: int = 1,
    route_cap: int | None = None,
    pair_cap: int = 4096,
    use_kernel: bool = False,
    delta_window: int = 4096,
):
    """Normalise, merge equalities, sweep, insert — the state-update half of a
    round (Algorithms 3-6 in bulk).  Pure; runs per-shard under shard_map.

    ``sort_perm``/``sorted_keys`` and ``pos_perm``/``pos_keys`` are the
    shard's two persistent indexes of its live rows, in (s, p, o) and
    (p, o, s) key order; the first is consumed by the membership probe, and
    both are returned up to date — swept rows leave via a stable partition,
    fresh rows rank-merge in (their (s, p, o) keys the dedup step already
    sorted; their (p, o, s) keys are sorted at stream width).  No step here
    sorts the arena.

    Under SPMD there are two exchange schemes:

      * ``route_cap=None`` (baseline): candidates are ALL-GATHERED so every
        shard sees/sorts the global padded stream; an ownership mask
        (``subject % n_shards``) picks the inserting shard.  The per-shard
        sort is O(n_shards x out_cap x 4) — 33.5M rows on the 256-chip
        round_268m cell, 99% padding (measured, §Perf).
      * ``route_cap=k`` (owner routing — the bulk analogue of the paper's
        per-thread insertion into the shared store): each shard expands its
        OWN candidates (rewrites + reflexivity), then routes every row to
        its owner with one all_to_all of (n_shards, k) buckets.  Only the
        few global sameAs pairs are still all-gathered (rho must update
        identically everywhere).  Per-shard sort shrinks to
        n_shards x route_cap rows and the exchange moves bucket payloads
        instead of the padded stream.  Bucket overflow raises the engine's
        capacity-retry (host doubles ``route_cap``).
    """
    arena_cap = spo.shape[0] - 1  # last row is the scatter trash slot
    n_used = n_used.reshape(())
    routed = axis is not None and route_cap is not None
    route_overflow = jnp.zeros((), bool)
    pair_overflow = jnp.zeros((), bool)

    if axis is not None and not routed:
        cands = _gather(cands, axis)
        cand_valid = _gather(cand_valid, axis)

    # 1) normalise with current rho
    cands = jnp.where(cand_valid[:, None], rep[cands], 0).astype(I32)

    # 2) merge sameAs pairs (deterministic min-hooking -> identical on shards)
    is_pair = cand_valid & (cands[:, 1] == SAME_AS) & (cands[:, 0] != cands[:, 2])
    if routed:
        # pairs are few: compact locally, gather the compacted buffer
        n_pairs = jax.lax.psum(is_pair.sum().astype(I32), axis)
        pcols, pvalid, p_ov = _compact(
            {"a": cands[:, 0], "b": cands[:, 2]}, is_pair, pair_cap
        )
        pair_overflow |= p_ov
        pairs = _gather(jnp.stack([pcols["a"], pcols["b"]], axis=1), axis)
        pair_valid = _gather(pvalid, axis)
    else:
        pairs = jnp.stack([cands[:, 0], cands[:, 2]], axis=1)
        pair_valid = is_pair
        n_pairs = is_pair.sum().astype(I32)
    new_rep = merge_pairs_jax(rep, pairs, pair_valid)
    rep_changed = jnp.any(new_rep != rep)
    rep = new_rep

    # 3) re-normalise candidates under the new rho
    cands = jnp.where(cand_valid[:, None], rep[cands], 0).astype(I32)

    # 4) sweep the local store shard (bulk Algorithm 3).  Most steady-state
    # rounds sweep nothing (rho unchanged), so the compaction and the index
    # partition sit behind a ``cond`` — XLA only runs the taken branch,
    # turning the arena-wide scatter work into a no-op on quiet rounds.
    live = (epoch >= 0) & ~marked
    rewritten = rep[spo].astype(I32)
    changed = live & jnp.any(rewritten != spo, axis=1)
    marked = marked | changed

    def _do_sweep(_):
        rw_cols, rw_valid, rw_overflow = _compact(
            {"s": rewritten[:, 0], "p": rewritten[:, 1], "o": rewritten[:, 2]},
            changed,
            rewrite_cap,
        )
        rw = jnp.stack([rw_cols["s"], rw_cols["p"], rw_cols["o"]], axis=1)
        # swept rows leave both persistent indexes (stable partition, no sort)
        perm, keys = _index_remove(sort_perm, sorted_keys, changed, arena_cap)
        pperm, pkeys = _index_remove(pos_perm, pos_keys, changed, arena_cap)
        return rw, rw_valid, rw_overflow, perm, keys, pperm, pkeys

    def _no_sweep(_):
        return (
            jnp.zeros((rewrite_cap, 3), I32), jnp.zeros((rewrite_cap,), bool),
            jnp.zeros((), bool), sort_perm, sorted_keys, pos_perm, pos_keys,
        )

    (rw, rw_valid, rw_overflow, sort_perm, sorted_keys,
     pos_perm, pos_keys) = jax.lax.cond(changed.any(), _do_sweep, _no_sweep, 0)
    if axis is not None and not routed:
        rw = _gather(rw, axis)
        rw_valid = _gather(rw_valid, axis)

    all_c = jnp.concatenate([cands, rw], axis=0)
    all_v = jnp.concatenate([cand_valid, rw_valid], axis=0)

    # 5) contradiction check (~=5) on normal forms — pre-ownership, so every
    # shard reports the same verdict
    contradiction = jnp.any(
        all_v & (all_c[:, 1] == DIFFERENT_FROM) & (all_c[:, 0] == all_c[:, 2])
    )
    if routed:  # local verdicts -> identical global verdict
        contradiction = jax.lax.psum(contradiction.astype(I32), axis) > 0

    # 6) reflexivity (Algorithm 4 lines 17-18): <c, sameAs, c> for each
    # resource of each candidate, plus <sameAs,sameAs,sameAs>
    # column-major: flattening an (n, 3) block is a relayout on the TPU
    res = jnp.concatenate([all_c[:, 0], all_c[:, 1], all_c[:, 2]])
    res_valid = jnp.concatenate([all_v] * 3)
    refl = jnp.stack([res, jnp.full_like(res, SAME_AS), res], axis=1)
    sa_row = jnp.asarray([[SAME_AS, SAME_AS, SAME_AS]], dtype=I32)
    any_v = jnp.any(all_v)
    stream = jnp.concatenate([all_c, refl, sa_row], axis=0)
    stream_v = jnp.concatenate([all_v, res_valid, any_v[None]], axis=0)
    # origin flag: True for rows created by the reflexivity expansion (so a
    # rule-derived reflexive fact is booked as a rule derivation, not here;
    # stable sort keeps the candidate occurrence on duplicates)
    stream_refl = jnp.concatenate(
        [jnp.zeros(all_c.shape[0], bool), jnp.ones(res.shape[0] + 1, bool)]
    )

    # ownership: a row is inserted only by shard ``subject % n_shards``
    if routed:
        # route rows to their owners: one all_to_all of (n_shards, route_cap)
        # buckets replaces sorting the global padded stream on every shard
        stream, refl_col, stream_v, r_ov = _route_rows(
            stream, stream_refl[:, None].astype(I32), stream_v,
            axis, n_shards, route_cap,
        )
        stream_refl = refl_col[:, 0].astype(bool)
        route_overflow |= r_ov
    elif axis is not None:
        own = (stream[:, 0] % n_shards) == jax.lax.axis_index(axis)
        stream_v = stream_v & own

    # 7) dedup within the stream
    skeys = jnp.where(stream_v, _pack3(stream), KEY_MAX)
    if use_kernel:  # sort-free Pallas counting-rank dedup (same stable order)
        order = kernel_ops.dedup_order(skeys)
    else:
        order = argsort_keys(skeys)
    sk = skeys[order]
    uniq = jnp.concatenate([jnp.asarray([True]), sk[1:] != sk[:-1]])
    uniq = uniq & (sk < KEY_MAX)

    # 8) membership against live local store rows: probe the persistent
    # sorted index instead of re-sorting the arena
    pos = jnp.clip(jnp.searchsorted(sorted_keys, sk), 0, sorted_keys.shape[0] - 1)
    member = sorted_keys[pos] == sk
    fresh = uniq & ~member

    # 9) scatter fresh rows into free local slots
    n_fresh = fresh.sum().astype(I32)
    slot = n_used + jnp.cumsum(fresh) - 1
    insert_overflow = (n_used + n_fresh) > arena_cap
    tgt = jnp.where(fresh, jnp.minimum(slot, arena_cap), arena_cap)
    rows = stream[order]
    spo = spo.at[tgt].set(jnp.where(fresh[:, None], rows, spo[tgt]))
    epoch = epoch.at[tgt].set(jnp.where(fresh, r, epoch[tgt]))
    # the trash row must stay dead no matter what was scattered into it
    spo = spo.at[arena_cap].set(0)
    epoch = epoch.at[arena_cap].set(-1)
    n_used = n_used + n_fresh

    # 9b) merge the fresh delta into the sorted index: ``sk`` is ascending,
    # so compacting the fresh (key, slot, row) tuples (stable, no sort)
    # yields a sorted delta that rank-merges into the index in O(C) gather
    # work — the full-arena argsort this replaces was the round loop's
    # single biggest cost on sort-bound backends.  Like the sweep above,
    # the merge sits behind a ``cond`` so rounds that inserted nothing
    # (every operation's final convergence round) skip the arena-length
    # work entirely.
    dcols, dvalid, _ = _compact(
        {
            "k": sk, "v": tgt.astype(I32),
            "s": rows[:, 0], "p": rows[:, 1], "o": rows[:, 2],
        },
        fresh, sk.shape[0],
    )

    def _do_merge(_):
        d_keys = jnp.where(dvalid, dcols["k"], KEY_MAX)
        d_vals = jnp.where(dvalid, dcols["v"], arena_cap).astype(I32)
        keys, perm = merge_sorted(
            sorted_keys, sort_perm, d_keys, d_vals,
            out_len=sorted_keys.shape[0],
        )
        # the (p, o, s) index takes the same fresh rows, sorted by their
        # (p, o, s) keys at stream width (never the arena)
        p_keys = jnp.where(fresh, _pack_pos(rows), KEY_MAX)
        if use_kernel:
            p_order = kernel_ops.dedup_order(p_keys)
        else:
            p_order = argsort_keys(p_keys)
        pkeys, pperm = merge_sorted(
            pos_keys, pos_perm, p_keys[p_order], tgt[p_order].astype(I32),
            out_len=pos_keys.shape[0],
        )
        return keys, perm, pkeys, pperm

    sorted_keys, sort_perm, pos_keys, pos_perm = jax.lax.cond(
        n_fresh > 0, _do_merge,
        lambda _: (sorted_keys, sort_perm, pos_keys, pos_perm), 0,
    )

    # reflexive-added stat: fresh rows originating from the reflexivity step
    is_refl = fresh & stream_refl[order]
    n_refl = is_refl.sum().astype(I32)

    # the compacted fresh delta rides back to the host, which derives the
    # per-position resource masks for dead-plan elimination there — a few
    # delta rows of numpy work instead of per-round arena-length scatters
    # and a psum on the device.  Truncated to a bounded width so the
    # per-round device-to-host transfer never scales with a wide padded
    # stream; on overflow (n_new exceeds the window) the host falls back
    # to all-True masks, which skip nothing and stay sound.
    d_window = min(sk.shape[0], delta_window)
    delta_rows = jnp.stack(
        [dcols["s"][:d_window], dcols["p"][:d_window], dcols["o"][:d_window]],
        axis=1,
    )

    flags = {
        "rep_changed": rep_changed,
        "contradiction": contradiction,
        "ov_rewrite": rw_overflow[None],
        "ov_store": insert_overflow[None],
        "ov_route": route_overflow[None],
        "ov_pair": pair_overflow[None],
        "n_new": n_fresh[None],
        "n_pairs": n_pairs,
        "n_marked": changed.sum().astype(I32)[None],
        "n_reflexive": n_refl[None],
        "delta_rows": delta_rows,
        "delta_valid": dvalid[:d_window],
    }
    return (
        spo, epoch, marked, n_used[None], rep, sort_perm, sorted_keys,
        pos_perm, pos_keys, flags,
    )


class CapacityError(RuntimeError):
    pass


def index_invariant_report(state: "EngineState", n_shards: int = 1) -> list[str]:
    """Violations of the persistent-index invariant (empty == healthy).

    Per shard block and for each of the two indexes — ``sorted_keys`` /
    ``sort_perm`` in (s, p, o) order and ``pos_keys`` / ``pos_perm`` in
    (p, o, s) order: the keys must be exactly the packed keys of the live
    rows, sorted ascending, as a prefix followed by KEY_MAX padding, and
    the perm's prefix must enumerate exactly those rows.
    Host-side diagnostic shared by the invariant fuzz tests and debugging;
    states whose index is marked dirty (pending rebuild) are reported as
    such rather than checked.
    """
    from .triples import pack  # host-side numpy packing (same bit layout)

    if state.index_dirty:
        return ["index_dirty: rebuild pending"]
    probs: list[str] = []
    spo = np.asarray(state.spo).reshape(n_shards, -1, 3)
    epoch = np.asarray(state.epoch).reshape(n_shards, -1)
    marked = np.asarray(state.marked).reshape(n_shards, -1)
    for name, order, keys, perm in (
        ("sorted_keys", SPO_ORDER, state.sorted_keys, state.sort_perm),
        ("pos_keys", POS_ORDER, state.pos_keys, state.pos_perm),
    ):
        keys = np.asarray(keys).reshape(n_shards, -1)
        perm = np.asarray(perm).reshape(n_shards, -1)
        cols = list(order)
        for s in range(n_shards):
            live = (epoch[s] >= 0) & ~marked[s]
            want = np.sort(pack(spo[s][live][:, cols]))
            n = want.shape[0]
            if not (keys[s][n:] == KEY_MAX).all():
                probs.append(f"shard {s}: {name}: non-sentinel entries beyond live prefix")
            if not np.array_equal(keys[s][:n], want):
                probs.append(f"shard {s}: {name} != sort(pack(live rows))")
            if not np.array_equal(np.sort(perm[s][:n]), np.flatnonzero(live)):
                probs.append(f"shard {s}: {name}: perm prefix is not the live row set")
            got = pack(spo[s][perm[s][:n]][:, cols])
            if not np.array_equal(got, keys[s][:n]):
                probs.append(f"shard {s}: {name}: perm rows disagree with the keys")
    return probs


@dataclass
class EngineState:
    """Device-resident materialisation state that survives update batches.

    The arena columns live sharded on the mesh; ``rep`` is replicated;
    ``explicit`` is the current explicit fact set (host, original IDs) and
    ``r`` the running round counter — epochs keep increasing across updates
    so the delta discipline of :func:`_epoch_ok` carries over unchanged.
    ``tomb`` is -1 everywhere except inside a delete operation's backward
    pass (see :mod:`repro.core.incremental_spmd`).

    ``sort_perm``/``sorted_keys`` is the **persistent sorted arena index**:
    per shard block, ``sorted_keys`` holds the packed int64 keys of exactly
    the live (``epoch >= 0 & ~marked``) rows in ascending order (KEY_MAX
    padding behind) and ``sort_perm`` the local row index of each entry.
    Every membership probe — store insertion, tombstone seeding/waves,
    rederive seeds, serving snapshots — binary-searches this shared view.
    ``pos_perm``/``pos_keys`` is the same view in (p, o, s) key order; the
    joins of atoms whose fixed positions prefix (p, o, s) but not
    (s, p, o) range-scan it.  Both are maintained *incrementally*, at the
    same points (rank-merge on insert, stable partition on
    sweep/finalize), so the arena is argsorted at most once per mutation
    epoch: ``index_dirty`` marks the rare rebuild points (capacity growth
    re-layout) and :meth:`JaxEngine._ensure_index` pays the sorts lazily
    at the next operation's start.
    """

    spo: jnp.ndarray
    epoch: jnp.ndarray
    marked: jnp.ndarray
    tomb: jnp.ndarray
    n_used: jnp.ndarray
    rep: jnp.ndarray
    sort_perm: jnp.ndarray
    sorted_keys: jnp.ndarray
    pos_perm: jnp.ndarray
    pos_keys: jnp.ndarray
    program: Program
    base_program: Program
    explicit: np.ndarray
    r: int
    stats: MatStats
    # maintenance-epoch counter: number of COMPLETED update operations since
    # the base fixpoint (which is epoch 0).  Distinct from ``r``/``epoch``
    # (the per-round delta discipline): readers version themselves on this,
    # and it only ever advances at an epoch barrier — never mid-operation.
    update_epoch: int = 0
    # True when the indexes no longer describe the arena (set on
    # capacity re-layout); cleared by JaxEngine._ensure_index
    index_dirty: bool = False

    @property
    def n_res(self) -> int:
        return int(self.rep.shape[0])


class StoreSnapshot:
    """Immutable, epoch-consistent read view of an :class:`EngineState`.

    Published at epoch barriers only — after a maintenance operation's
    fixpoint completes, never mid-round — so a query evaluated against a
    snapshot observes exactly the fixpoint of maintenance epoch ``epoch``:
    no tombstoned-but-not-yet-rederived rows, no half-applied clique split.
    ``rho`` is the frozen representative view whose clique tables are shared
    by every query answered at this epoch (the serving contract of
    :mod:`repro.serve.triple_store`; docs/serving.md).

    Two backing forms:

      * **host** — ``triples`` is an eager host copy of the live
        normal-form store (:meth:`JaxEngine.read_snapshot`, and the SPMD
        path, build these);
      * **device-resident** (:meth:`JaxEngine.publish_snapshot`) — the
        live rows stay on the accelerator in TWO sorted orders: ``(s,p,o)``
        packed-key order (``d_triples``/``d_keys``) and ``(p,o,s)`` order
        (``d_triples_pos``/``d_keys_pos``), each padded to the arena width
        with KEY_MAX keys behind the ``n_live`` live rows.  The batched
        query executor (:mod:`repro.sparql.batched`) range-probes these
        directly, so serving a query costs no device->host copy at all;
        ``triples`` is materialised to host lazily, only when a
        non-batchable query falls back to the host matcher.

    Both forms are immutable: device arrays are never written after
    publication (the double-buffer swap retires, never mutates, the
    previous epoch's buffers) and the host copy is marked read-only.
    """

    __slots__ = (
        "epoch", "rho", "_triples", "n_live",
        "d_triples", "d_keys", "d_triples_pos", "d_keys_pos",
    )

    def __init__(
        self, epoch: int, rho: FrozenRho, triples: np.ndarray | None = None,
        device: tuple | None = None,
    ) -> None:
        self.epoch = epoch
        self.rho = rho
        self._triples = triples
        if device is not None:
            (self.d_triples, self.d_keys, self.d_triples_pos,
             self.d_keys_pos, self.n_live) = device
        else:
            self.d_triples = self.d_keys = None
            self.d_triples_pos = self.d_keys_pos = None
            self.n_live = None if triples is None else int(triples.shape[0])

    @property
    def on_device(self) -> bool:
        return self.d_keys is not None

    @property
    def triples(self) -> np.ndarray:
        """Host copy of the normal-form store (lazy for device snapshots)."""
        if self._triples is None:
            t = np.asarray(self.d_triples)[: self.n_live]
            t.setflags(write=False)
            self._triples = t
        return self._triples

    @property
    def n_res(self) -> int:
        return len(self.rho)


# -- auditable-fn registry (repro.analysis) ---------------------------------
#
# Every compiled fn family the engine dispatches registers a *trace builder*
# here: ``builder(engine, state)`` yields ``(label, jaxpr)`` pairs covering
# the family's variants at the caller's probe geometry.  ``repro.analysis``
# runs its invariant passes over the full registry — a new hot fn that does
# not register is caught by the dispatch cross-check instead (its runtime
# family shows up in no phase profile).  ``skip_passes`` names passes whose
# invariant the family is deliberately exempt from (each exemption is a
# documented cost decision, not a loophole — see docs/analysis.md).

@dataclass(frozen=True)
class AuditableFn:
    name: str
    builder: callable
    skip_passes: tuple = ()


AUDIT_REGISTRY: dict[str, AuditableFn] = {}


def register_auditable(name: str, skip_passes: tuple = ()):
    def deco(builder):
        AUDIT_REGISTRY[name] = AuditableFn(name, builder, tuple(skip_passes))
        return builder

    return deco


def _rebuild_index(spo, epoch, marked):
    """Full rebuild of both indexes: the ONE allowed arena argsort (per
    mutation epoch), once per key order.  Returns ``(sort_perm,
    sorted_keys, pos_perm, pos_keys)``."""
    live = (epoch >= 0) & ~marked
    keys = jnp.where(live, _pack3(spo), KEY_MAX)
    perm = argsort_keys(keys)
    pkeys = jnp.where(live, _pack_pos(spo), KEY_MAX)
    pperm = argsort_keys(pkeys)
    return perm, keys[perm], pperm, pkeys[pperm]


def _publish_snapshot(spo, sort_perm, sorted_keys):
    """Device-resident snapshot build — the per-barrier publication step.

    Gathers the live rows through the persistent sorted index (one gather:
    the ``(s,p,o)``-ordered view is the index itself) and derives the
    secondary ``(p,o,s)``-ordered view with ONE argsort — the only sort the
    publication pays, off the query path entirely (the NoArenaSort
    exemption mirrors ``rebuild_index``: a deliberate, counted, per-epoch
    cost — see docs/serving.md).  The two orders make every atom whose
    bound positions prefix either ``(s,p,o)`` or ``(p,o,s)`` a contiguous
    range probe for the batched query executor.  Returns
    ``(tri, keys, tri_pos, keys_pos, n_live)``; padding rows carry KEY_MAX
    keys behind the live prefix.
    """
    tri = spo[sort_perm]
    live = sorted_keys < KEY_MAX
    n_live = live.sum()
    pos_keys = jnp.where(live, _pack_pos(tri), KEY_MAX)
    perm2 = argsort_keys(pos_keys)
    return tri, sorted_keys, tri[perm2], pos_keys[perm2], n_live


def _squeeze_stream(cands, valid, *, target):
    """Compact a bucketed candidate stream to ``target`` rows (+ overflow)."""
    cols, v, ov = _compact(
        {"s": cands[:, 0], "p": cands[:, 1], "o": cands[:, 2]}, valid, target,
    )
    out = jnp.stack([cols["s"], cols["p"], cols["o"]], axis=1)
    return out, v, ov[None]


class _CountedFn:
    """Callable wrapper counting dispatches through the engine's fn cache.

    Counting wraps the *call*, not the cache fetch — the maintenance host
    helpers fetch a fn once and call it per chunk, and the dispatch floor
    the ROADMAP tracks is calls, not fetches."""

    __slots__ = ("fn", "family", "counter")

    def __init__(self, fn, family: str, counter: DispatchCounter) -> None:
        self.fn = fn
        self.family = family
        self.counter = counter

    def __call__(self, *args):
        self.counter.record(self.family)
        return self.fn(*args)


def _key_family(key) -> str:
    """The fn family of a cache key: its head, unwrapping tagged heads
    like ``("od", n_heads)``."""
    head = key[0] if isinstance(key, tuple) else key
    return head if isinstance(head, str) else head[0]


class JaxEngine:
    """REW materialisation with static capacities; single-device or SPMD.

    Pass ``mesh`` (a 1-D ``jax.sharding.Mesh`` whose axis shards the arena)
    to run distributed; capacities are then per shard.  ``materialise``
    retries with doubled capacities on overflow, so callers normally never
    see :class:`CapacityError`.

    ``materialise_state`` returns a device-resident :class:`EngineState`
    that :meth:`add_facts` / :meth:`delete_facts` maintain on the
    accelerator (epoch-tagged tombstones + owner-routed delta exchange; the
    algorithms live in :mod:`repro.core.incremental_spmd`).
    """

    def __init__(
        self,
        n_resources: int,
        capacity: int = 1 << 12,
        bind_cap: int = 1 << 12,
        out_cap: int = 1 << 12,
        rewrite_cap: int = 1 << 12,
        mesh=None,
        axis: str = "data",
        route_cap: int | None = None,
        seed_chunk: int = 2048,
        delta_out_cap: int | None = None,
        use_kernel: bool = False,
        rederive_mode: str = "targeted",
        fuse_rounds: bool = True,
        delta_window: int = 4096,
    ) -> None:
        self.n_resources = n_resources
        self.capacity = capacity
        self.bind_cap = bind_cap
        self.out_cap = out_cap
        self.rewrite_cap = rewrite_cap
        self.route_cap = route_cap
        # compacted sameAs-pair rows gathered between shards in routed mode;
        # grows independently so a pair burst cannot masquerade as a route
        # overflow (which would retry without ever converging)
        self.pair_cap = min(out_cap, 4096)
        # bounded per-round device-to-host window for the fresh delta's
        # resource masks (process_candidates flags); rounds whose fresh-row
        # count exceeds it fall back to all-True masks — sound but
        # unfiltered, counted in ``stats.delta_mask_fallbacks``.  Tunable
        # mainly so tests can force the fallback path at toy scale.
        self.delta_window = delta_window
        self.seed_chunk = seed_chunk
        # delta/tomb plans of incremental updates emit into much smaller
        # buffers than full-evaluation plans — the candidate stream (and its
        # sorts) then scales with the update's blast radius, not with the
        # base fixpoint's worst round.  The base run itself uses ``out_cap``
        # for every plan (its early deltas are dataset-sized).  The same
        # narrowing applies to the join binding table (``delta_bind``) and
        # the sweep rewrite buffer (``delta_rewrite``): with the persistent
        # index covering membership, these padded widths are what is left
        # of the arena-proportional per-round cost.
        self.delta_out = delta_out_cap or min(out_cap, max(1 << 12, out_cap >> 4))
        # bind holds JOIN INTERMEDIATES, which on rule-heavy programs exceed
        # the delta long before the candidate stream does — its floor is one
        # notch higher so typical updates never pay a growth retry
        self.delta_bind = min(bind_cap, max(1 << 13, bind_cap >> 4))
        self.delta_rewrite = min(rewrite_cap, max(1 << 11, rewrite_cap >> 4))
        self._active_delta_out = out_cap
        self._active_delta_kind = "out"
        self._active_bind = bind_cap
        self._active_bind_kind = "bind"
        self._active_rewrite = rewrite_cap
        self._active_rewrite_kind = "rewrite"
        # an update whose blast radius exceeds a narrow delta buffer retries
        # with the WIDE (base-run, already-compiled) buffers instead of
        # rediscovering the right delta width one doubling-plus-recompile at
        # a time; the named delta cap still doubles once.  The flag is
        # STICKY across operations — a workload whose updates are
        # store-scale (clique-split-heavy deletes on small stores) should
        # not pay a narrow attempt + rollback per op — but every few ops
        # :meth:`_maybe_reset_fallback` probes narrow again, so one
        # anomalous giant update cannot degrade a delta-scale stream
        # permanently.
        self._delta_fallback = False
        # whether the engine is inside a maintenance operation (add/delete)
        # as opposed to a base materialisation; kept in sync by
        # :meth:`_set_update_buffers` and gates merge-targeted requeue
        self._updating = False
        # update_epoch at which fallback mode was (last) entered/probed —
        # the narrow re-probe schedule is keyed off epoch barriers, which
        # advance once per operation whether the rounds run host-looped or
        # as one fused fixpoint (a per-round counter stopped advancing when
        # the round loop moved on device)
        self._fallback_since: int | None = None
        # delete-side rederivation strategy: "targeted" chains the rederive
        # join backward from the overdeleted head instances (the default);
        # "requeue" keeps the historical whole-rule re-evaluation — retained
        # as the differential-testing baseline (tests/test_incremental_spmd)
        if rederive_mode not in ("targeted", "requeue"):
            raise ValueError(f"unknown rederive_mode {rederive_mode!r}")
        self.rederive_mode = rederive_mode
        self.use_kernel = use_kernel
        # fuse the inner maintenance round loop into one compiled
        # lax.while_loop fixpoint per pass (repro.core.fused); False keeps
        # the host-orchestrated per-round loop — the differential baseline
        self.fuse_rounds = fuse_rounds
        self.mesh = mesh
        self.axis = axis if mesh is not None else None
        self.n_shards = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
        self._fns: dict = {}
        # runtime half of the dispatch auditor: every call through the fn
        # cache is recorded by family (+ the maintenance phase, when one is
        # tagged); repro.analysis cross-checks against the static profile
        self.dispatches = DispatchCounter()

    @classmethod
    def from_config(cls, cfg, mesh=None, axis: str = "data", **overrides):
        """Build an engine from a :mod:`repro.configs.sameas_rew` EngineConfig."""
        kw = dict(
            n_resources=cfg.n_resources,
            capacity=cfg.capacity,
            bind_cap=cfg.bind_cap,
            out_cap=cfg.out_cap,
            rewrite_cap=cfg.rewrite_cap,
            route_cap=cfg.route_cap,
            seed_chunk=getattr(cfg, "seed_chunk", 2048),
            delta_out_cap=getattr(cfg, "delta_out_cap", None),
        )
        kw.update(overrides)
        return cls(mesh=mesh, axis=axis, **kw)

    # -- jit wrappers -------------------------------------------------------
    def _jit_fn(self, key, fn, in_specs, out_specs) -> "_CountedFn":
        """Jit ``fn`` (under ``shard_map`` on a mesh) and install it under
        ``key``.  The program is named after the key's family, so a device
        trace reads ``jit_<family>`` (``jit_fwave``, ``jit_plan``) where an
        unnamed ``functools.partial`` would read ``jit__unknown``."""
        if self.mesh is not None:
            fn = compat_shard_map(
                fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            )
        named = partial(fn)
        named.__name__ = _key_family(key)
        return self._register_fn(key, jax.jit(named))

    def _register_fn(self, key, fn) -> "_CountedFn":
        """Install a compiled fn in the cache under dispatch accounting.

        Every cache fill goes through here (``("padbuf", ...)`` entries are
        device *buffers*, not fns — they bypass this and stay uncounted) so
        each subsequent call records one dispatch under the key's family.
        """
        counted = _CountedFn(fn, _key_family(key), self.dispatches)
        self.dispatches.record_compile(counted.family)
        self._fns[key] = counted
        return counted

    # buffer family of each growable cap attr: cache keys tag every cap
    # value with its family, so eviction after growth is precise even when
    # two different buffers happen to share a width
    _CAP_FAMILY = {
        "bind_cap": "bind", "delta_bind": "bind",
        "out_cap": "out", "delta_out": "out",
        "rewrite_cap": "rewrite", "delta_rewrite": "rewrite",
        "pair_cap": "pair", "route_cap": "route",
    }

    def _get_plan_fn(self, plan_key, plan, head_slots, bind_cap, out_cap):
        if plan_key not in self._fns:
            a = self.axis
            fn = partial(
                eval_plan,
                plan=plan,
                head_var_slots=head_slots,
                bind_cap=bind_cap,
                out_cap=out_cap,
                axis=a,
                use_kernel=self.use_kernel,
            )
            d = P(a) if a else None
            rpl = P() if a else None
            self._jit_fn(
                plan_key, fn,
                in_specs=(d, d, d, d, d, d, d, d, rpl, rpl, rpl),
                out_specs=(d, d, d, d, d, d),
            )
        return self._fns[plan_key]

    def _get_squeeze_fn(self, n_rows: int, target: int):
        """Compact a wide bucketed candidate stream down to ``target`` rows.

        Rounds can bucket several plan buffers (rederive even full-width
        ones); their valid rows almost always fit one active-width buffer,
        and squeezing once is far cheaper than dragging the padded width
        through the process step's sorts (which touch the stream ~4x after
        refl expansion).  During updates ``target`` is the narrow
        ``delta_out`` width, so steady-state rounds stream delta-sized
        buffers end to end.
        """
        key = ("squeeze", n_rows, ("out", target))
        if key not in self._fns:
            a = self.axis
            fn = partial(_squeeze_stream, target=target)
            d = P(a) if a else None
            self._jit_fn(key, fn, in_specs=(d, d), out_specs=(d, d, d))
        return self._fns[key]

    def _get_process_fn(self, n_cand_rows: int):
        key = (
            "process", n_cand_rows, ("rewrite", self._active_rewrite),
            ("route", self.route_cap), ("out", self.out_cap),
            ("pair", self.pair_cap), ("dwin", self.delta_window),
        )
        if key not in self._fns:
            a = self.axis
            fn = partial(
                process_candidates,
                rewrite_cap=self._active_rewrite,
                axis=a,
                n_shards=self.n_shards,
                route_cap=self.route_cap if a is not None else None,
                pair_cap=self.pair_cap,
                use_kernel=self.use_kernel,
                delta_window=self.delta_window,
            )
            d = P(a) if a else None
            rpl = P() if a else None
            flag_specs = {
                "rep_changed": rpl,
                "contradiction": rpl,
                "ov_rewrite": d,
                "ov_store": d,
                "ov_route": d,
                "ov_pair": d,
                "n_new": d,
                "n_pairs": rpl,
                "n_marked": d,
                "n_reflexive": d,
                "delta_rows": d,
                "delta_valid": d,
            }
            self._jit_fn(
                key, fn,
                in_specs=(d, d, d, d, rpl, d, d, d, d, d, d, rpl),
                out_specs=(d, d, d, d, rpl, d, d, d, d, flag_specs),
            )
        return self._fns[key]

    # -- state lifecycle -----------------------------------------------------
    def _fresh_state(self, program: Program) -> EngineState:
        cap, D = self.capacity, self.n_shards
        return EngineState(
            spo=jnp.zeros(((cap + 1) * D, 3), I32),
            epoch=jnp.full(((cap + 1) * D,), -1, I32),
            marked=jnp.zeros(((cap + 1) * D,), bool),
            tomb=jnp.full(((cap + 1) * D,), -1, I32),
            n_used=jnp.zeros((D,), I32),
            rep=jnp.arange(self.n_resources, dtype=I32),
            # a valid index of the empty store: KEY_MAX padding pointing at
            # each shard's trash row (local index ``cap``)
            sort_perm=jnp.full(((cap + 1) * D,), cap, I32),
            sorted_keys=jnp.full(((cap + 1) * D,), KEY_MAX, jnp.int64),
            pos_perm=jnp.full(((cap + 1) * D,), cap, I32),
            pos_keys=jnp.full(((cap + 1) * D,), KEY_MAX, jnp.int64),
            program=program,
            base_program=program,
            explicit=np.zeros((0, 3), np.int32),
            r=0,
            stats=MatStats(
                mode="REW-jax" + ("-spmd" if self.mesh is not None else "")
            ),
        )

    def _pad_cands(self, rows: np.ndarray):
        """Pad a host candidate batch to the active candidate stream shape.

        During updates that is the narrow ``delta_out`` width — the whole
        round then streams delta-sized buffers through the process step —
        and during the base run the full ``out_cap``.
        """
        rows = np.asarray(rows, np.int32).reshape(-1, 3)
        rows_global = self._active_delta_out * self.n_shards
        if rows.shape[0] > rows_global:
            raise CapacityError(self._active_delta_kind)
        pad = rows_global - rows.shape[0]
        cands = jnp.asarray(np.pad(rows, ((0, pad), (0, 0))), I32)
        cand_valid = jnp.asarray(np.arange(rows_global) < rows.shape[0])
        return cands, cand_valid

    def _set_update_buffers(self, updating: bool) -> None:
        """Select the output buffer delta/tomb plans emit into.

        During maintenance updates those are the narrow ``delta_out`` /
        ``delta_bind`` / ``delta_rewrite`` buffers; during the base run —
        or an update retrying after a delta-buffer overflow
        (``_delta_fallback``) — the full ``out_cap`` / ``bind_cap`` /
        ``rewrite_cap`` (base-run widths, so their compiled fns are reused
        rather than recompiled per doubling).  The active *kind* names the
        capacity a retry must grow — the buffers can coincide in size, so
        the label cannot be recovered from the value.
        """
        narrow = updating and not self._delta_fallback
        self._updating = updating
        self._active_delta_out = self.delta_out if narrow else self.out_cap
        self._active_delta_kind = "delta_out" if narrow else "out"
        self._active_bind = self.delta_bind if narrow else self.bind_cap
        self._active_bind_kind = "delta_bind" if narrow else "bind"
        self._active_rewrite = self.delta_rewrite if narrow else self.rewrite_cap
        self._active_rewrite_kind = "delta_rewrite" if narrow else "rewrite"

    def _evict_stale_fns(self, grew: set) -> None:
        """Drop compiled fns (and padbuf device buffers) that baked an
        outgrown capacity.  ``grew`` holds ``(family, old_value)`` pairs
        and cache keys tag every cap with its buffer family, so eviction
        is precise: growing ``bind`` no longer evicts every fn that merely
        mentions an *equal* ``out`` width — the collateral recompile storm
        that used to follow a mid-stream growth.  Keys whose widths are
        *derived* from the caps (padbuf buffers, process/squeeze stream
        widths) carry bare ints; those are matched by value, since an
        outgrown width can no longer be produced and would otherwise
        retain its XLA executable / device buffers for the engine's (a
        standing service's) lifetime — a coincidental match there merely
        costs one recompile."""
        old_values = {v for _, v in grew}

        def hit(x, by_value=False):
            if isinstance(x, tuple):
                if len(x) == 2 and isinstance(x[0], str) and x in grew:
                    return True
                return any(hit(y, by_value) for y in x)
            return by_value and isinstance(x, int) and x in old_values

        def stale(key):
            by_value = (
                isinstance(key, tuple)
                and key
                and key[0] in ("padbuf", "process", "squeeze", "fforward")
            )
            return hit(key, by_value)

        for key in [k for k in self._fns if stale(k)]:
            del self._fns[key]

    def _grow_for(self, kind: str) -> None:
        """Double exactly the capacity a :class:`CapacityError` names.

        Growing only the exhausted buffer keeps padded join/sort costs
        proportional to the workload — a bind-table overflow must not
        quadruple the arena sort.  Every tunable cap is part of the compiled
        fn cache keys (and jit itself re-traces on array-shape changes), so
        correctness needs no invalidation; stale-cap entries are still
        evicted so their executables are reclaimed.
        """
        grew: set = set()

        def double(attr: str, factor: int = 2) -> None:
            # the arena capacity is not part of any fn cache key (jit
            # re-traces on the new array shapes), so it never marks stale
            if attr != "capacity":
                grew.add((self._CAP_FAMILY[attr], getattr(self, attr)))
            setattr(self, attr, getattr(self, attr) * factor)

        # each wide-cap growth mid-update restarts the operation and
        # recompiles every fn keyed on the outgrown width; once an update
        # is already in its fallback retry, grow x4 to halve those restarts
        wide_factor = 4 if self._delta_fallback else 2

        if kind == "store":
            double("capacity")
        elif kind == "bind":
            double("bind_cap", wide_factor)
        elif kind in ("out", "out_cap"):
            double("out_cap", wide_factor)
        elif kind == "rewrite":
            double("rewrite_cap", wide_factor)
        elif kind in ("delta_out", "delta_bind", "delta_rewrite"):
            # a delta buffer overflowed: double it for FUTURE updates, but
            # retry the current one against the wide (base-run, compiled)
            # buffers — iterative width discovery would recompile every
            # delta-width fn per doubling.  Clamped at the wide cap: on a
            # persistently store-scale workload the periodic narrow probe
            # must not keep doubling (and recompiling) past the width the
            # wide buffers already provide — all caps are powers of two,
            # so doubling from below the wide cap never overshoots it.
            wide = {"delta_out": "out_cap", "delta_bind": "bind_cap",
                    "delta_rewrite": "rewrite_cap"}[kind]
            if getattr(self, kind) < getattr(self, wide):
                double(kind)
            self._delta_fallback = True
            self._fallback_since = None  # restart the narrow-probe clock
        elif kind == "pair":
            double("pair_cap")
        elif kind == "route" and self.route_cap is not None:
            double("route_cap")
        else:  # unknown kind: grow everything (defensive)
            for attr in ("capacity", "bind_cap", "delta_bind", "out_cap",
                         "delta_out", "rewrite_cap", "delta_rewrite",
                         "pair_cap"):
                double(attr)
            if self.route_cap is not None:
                double("route_cap")
        # keep the active delta buffer (and its retry label) in sync with
        # whichever capacity the running operation is emitting into
        self._set_update_buffers(self._active_delta_kind == "delta_out")
        if grew:
            self._evict_stale_fns(grew)

    def _bucket_cands(self, bufs):
        """Concatenate plan output buffers, padding each width group with
        empty buffers to a power-of-two count — process fns then compile for
        O(log #plans) distinct candidate widths instead of one per plan
        subset (the delta-mask filter makes the subset vary round to round,
        and delta plans emit narrower buffers than full plans)."""
        groups: dict[int, list] = {}
        for b in bufs:
            groups.setdefault(int(b[0].shape[0]), []).append(b)
        heads, valids = [], []
        for rows, bs in sorted(groups.items()):
            total = 1
            while total < len(bs):
                total *= 2
            key = ("padbuf", rows)
            if key not in self._fns:
                self._fns[key] = (
                    jnp.zeros((rows, 3), I32),
                    jnp.zeros((rows,), bool),
                )
            pad_h, pad_v = self._fns[key]
            heads += [b[0] for b in bs] + [pad_h] * (total - len(bs))
            valids += [b[1] for b in bs] + [pad_v] * (total - len(bs))
        return jnp.concatenate(heads, axis=0), jnp.concatenate(valids, axis=0)

    def _grow_state_arena(self, state: EngineState, old_cap: int) -> None:
        """Re-layout the sharded arena columns after ``capacity`` doubled.

        Each shard's block grows from ``old_cap + 1`` to ``capacity + 1``
        rows; the old trash slot becomes an ordinary free row (dead, epoch
        -1) that insertion reuses once ``n_used`` reaches it.
        """
        D, new_cap = self.n_shards, self.capacity

        def regrow(x, fill):
            h = np.asarray(x)
            h = h.reshape(D, old_cap + 1, *h.shape[1:])
            pad = [(0, 0)] * h.ndim
            pad[1] = (0, new_cap - old_cap)
            h = np.pad(h, pad, constant_values=fill)
            return jnp.asarray(h.reshape(D * (new_cap + 1), *h.shape[2:]))

        state.spo = regrow(state.spo, 0)
        state.epoch = regrow(state.epoch, -1)
        state.marked = regrow(state.marked, False)
        state.tomb = regrow(state.tomb, -1)
        # the sorted index keys survive the re-layout unchanged but the
        # arrays are the wrong shape now; rebuild lazily (the one full
        # argsort this mutation epoch) at the next operation's start
        state.index_dirty = True

    @staticmethod
    def _snapshot(state: EngineState) -> dict:
        import copy

        snap = {f: getattr(state, f) for f in (
            "spo", "epoch", "marked", "tomb", "n_used", "rep",
            "sort_perm", "sorted_keys", "pos_perm", "pos_keys", "index_dirty",
            "program", "explicit", "r", "update_epoch",
        )}
        snap["stats"] = copy.copy(state.stats)
        return snap

    @staticmethod
    def _restore(state: EngineState, snap: dict) -> None:
        for f, v in snap.items():
            setattr(state, f, v)

    def _maybe_reset_fallback(self, state: EngineState) -> None:
        """Sticky wide-buffer fallback with a periodic narrow probe.

        Once ``state.update_epoch`` has advanced 4 epoch barriers past the
        epoch at which fallback was entered (or last re-asserted by a delta
        overflow), the next operation tries the narrow delta buffers again
        — one rollback if the workload is still store-scale, a return to
        delta-scale costs if load has dropped.  The schedule is keyed off
        epoch barriers (one per committed operation) rather than any round
        count: the fused fixpoint advances rounds on device, so a per-round
        or per-call counter would tick at a rate that depends on how the
        rounds are orchestrated, not on how many operations ran.
        """
        if not self._delta_fallback:
            self._fallback_since = None
            return
        if self._fallback_since is None:
            self._fallback_since = state.update_epoch
        elif state.update_epoch - self._fallback_since >= 4:
            self._delta_fallback = False
            self._fallback_since = None

    def _presize_delta(self, n_rows: int) -> None:
        """Pre-size the delta buffers for a KNOWN cardinality — the admitted
        batch or the finalised overdelete delta — so mid-stream width
        discovery (overflow -> rollback -> growth -> recompile, repeated)
        never fires for a width the driver can predict up front.  The
        narrow delta caps grow to cover ``n_rows`` (clamped at the wide
        caps, matching the overflow path's clamp); a cardinality exceeding
        even the wide caps grows those too — *without* a restart, since
        this runs at a phase boundary with no buffers in flight.

        ``n_rows`` is a GLOBAL cardinality while every cap is per shard
        (``_pad_cands``: global stream width = cap x n_shards), so the
        target width divides by the shard count — a skewed row
        distribution is the overflow retry's job, exactly as for any other
        per-shard buffer.

        An EMPTY admitted batch (a no-op epoch) still selects buffers: the
        cardinality clamps to 1 so the pow2 target is the minimum delta
        width, never a degenerate 0-row presize that the next phase would
        have to repair with a width-discovery restart booked against
        ``wide_growth_restarts`` on an idle epoch.
        """
        n_rows = max(int(n_rows), 1)
        need = _pow2(-(-n_rows // self.n_shards))
        grew: set = set()
        for attr, wide in (
            ("delta_out", "out_cap"),
            ("delta_bind", "bind_cap"),
            ("delta_rewrite", "rewrite_cap"),
        ):
            if getattr(self, wide) < need:
                grew.add((self._CAP_FAMILY[wide], getattr(self, wide)))
                setattr(self, wide, need)
            target = min(need, getattr(self, wide))
            if getattr(self, attr) < target:
                grew.add((self._CAP_FAMILY[attr], getattr(self, attr)))
                setattr(self, attr, target)
        self._set_update_buffers(True)
        if grew:
            self._evict_stale_fns(grew)

    def _ensure_index(self, state: EngineState) -> None:
        """(Re)build the persistent sorted index if it is stale.

        The ONLY full argsorts of the arena (one per index), paid at most once per mutation
        epoch — after a capacity re-layout, or to adopt a hand-built state
        — never inside the round loop (``stats.index_rebuilds`` counts the
        sorts so tests can pin that budget).  Must run inside the engine's
        x64 scope.
        """
        if not state.index_dirty:
            return
        key = ("rebuild_index",)
        if key not in self._fns:
            a = self.axis
            d = P(a) if a else None
            self._jit_fn(
                key, _rebuild_index, in_specs=(d, d, d),
                out_specs=(d, d, d, d),
            )
        (state.sort_perm, state.sorted_keys,
         state.pos_perm, state.pos_keys) = self._fns[key](
            state.spo, state.epoch, state.marked
        )
        state.index_dirty = False
        state.stats.index_rebuilds += 1

    def _refresh_stats(self, state: EngineState) -> None:
        stats = state.stats
        stats.triples_total = int(np.asarray(state.n_used).sum())
        stats.merged_resources = int(
            (compress_np(np.asarray(state.rep)) != np.arange(state.n_res)).sum()
        )
        stats.triples_explicit = state.explicit.shape[0]

    def state_triples(self, state: EngineState) -> np.ndarray:
        """The current normal-form store as a host (n, 3) array."""
        epoch = np.asarray(state.epoch)
        marked = np.asarray(state.marked)
        live = (epoch >= 0) & ~marked
        state.stats.triples_unmarked = int(live.sum())
        return np.asarray(state.spo)[live]

    def state_rep(self, state: EngineState) -> np.ndarray:
        return compress_np(np.asarray(state.rep))

    def snapshot_arrays(
        self, spo, epoch, marked, rep, at_epoch: int,
        sort_perm=None, sorted_keys=None, index_dirty: bool = True,
    ) -> StoreSnapshot:
        """Build a :class:`StoreSnapshot` from raw barrier-consistent arrays.

        The arrays must describe an epoch barrier (an operation fixpoint) —
        either a live :class:`EngineState` between updates, or the rollback
        snapshot captured before an in-flight update started (the serving
        scheduler's lazy-publication path).  When the persistent sorted
        index is supplied (and clean), the live rows are extracted through
        it — one gather instead of a full-arena boolean scan, and the
        published triples come out packed-key-sorted per shard block.
        """
        if sorted_keys is not None and not index_dirty:
            keys = np.asarray(sorted_keys).reshape(self.n_shards, -1)
            perm = np.asarray(sort_perm).reshape(self.n_shards, -1)
            spo_h = np.asarray(spo).reshape(self.n_shards, keys.shape[1], 3)
            triples = np.concatenate(
                [spo_h[s][perm[s][keys[s] < KEY_MAX]] for s in range(self.n_shards)],
                axis=0,
            )
        else:
            live = (np.asarray(epoch) >= 0) & ~np.asarray(marked)
            triples = np.asarray(spo)[live]
        triples.setflags(write=False)  # shared by every reader at this epoch
        return StoreSnapshot(
            epoch=at_epoch,
            triples=triples,
            rho=FrozenRho(np.asarray(rep)),
        )

    def read_snapshot(self, state: EngineState) -> StoreSnapshot:
        """Epoch-versioned read snapshot: host triples copy + frozen rho.

        Only valid at an epoch barrier (no update in flight on ``state``) —
        mid-operation the arena holds tombstoned-but-not-yet-rederived rows
        that no reader may observe.  :meth:`add_facts`/:meth:`delete_facts`
        bump ``state.update_epoch`` exactly when the barrier is reached, so
        snapshots taken between public API calls are always consistent.
        Serving epochs reuse the persistent index for free: live rows come
        out through one ``sort_perm`` gather.
        """
        snap = self.snapshot_arrays(
            state.spo, state.epoch, state.marked, state.rep, state.update_epoch,
            sort_perm=state.sort_perm, sorted_keys=state.sorted_keys,
            index_dirty=state.index_dirty,
        )
        state.stats.triples_unmarked = int(snap.triples.shape[0])
        return snap

    def publish_snapshot(
        self, state: EngineState, prev: StoreSnapshot | None = None,
    ) -> StoreSnapshot:
        """Device-resident epoch snapshot — the serving publication step.

        Like :meth:`read_snapshot` this is only valid at an epoch barrier,
        but instead of copying the live rows to host it keeps them on the
        accelerator in the two sorted orders the batched query executor
        range-probes (:func:`_publish_snapshot`); the host ``triples`` copy
        is materialised lazily only if a host-path reader asks for it.
        ``prev`` (the previously published snapshot) enables the
        incremental :meth:`~repro.core.uf.FrozenRho.refreshed` rho refresh:
        epochs that touched no clique reuse the entire expansion table.

        Runs in the ``"publish"`` phase (an index rebuild may ride along
        when the arena was re-laid-out this epoch).  Falls back to the host
        path under SPMD: per-shard sorted blocks are not a globally sorted
        view, and the serving store is a single-controller tier.
        """
        with self.dispatches.in_phase("publish"):
            if self.n_shards != 1:
                snap = self.read_snapshot(state)
                if prev is not None:
                    snap.rho = prev.rho.refreshed(np.asarray(state.rep))
                return snap
            with enable_x64():
                self._ensure_index(state)
                key = ("snapshot", int(state.spo.shape[0]))
                if key not in self._fns:
                    self._register_fn(key, jax.jit(_publish_snapshot))
                tri, keys, tri_pos, keys_pos, n_live = self._fns[key](
                    state.spo, state.sort_perm, state.sorted_keys
                )
            rep_host = np.asarray(state.rep)
            rho = prev.rho.refreshed(rep_host) if prev is not None \
                else FrozenRho(rep_host)
            n_live = int(n_live)
            state.stats.triples_unmarked = n_live
            return StoreSnapshot(
                state.update_epoch, rho,
                device=(tri, keys, tri_pos, keys_pos, n_live),
            )

    def _recover_capacity(
        self, state: EngineState, snap: dict, err: CapacityError
    ) -> None:
        """Roll back to ``snap``, grow exactly the exhausted capacity, and
        re-layout the sharded arena if the store itself grew — the shared
        retry step of :meth:`_apply_update` and the serving scheduler
        (:mod:`repro.serve.triple_store`)."""
        # dispatches issued by the rollback/grow/restart machinery must not
        # inherit whatever phase tag was live (or stale) when the overflow
        # fired — attribute them to a distinct "retry" phase the static
        # dispatch profile admits; the restarted generator re-tags its own
        # phases from the top
        with self.dispatches.in_phase("retry"):
            self._restore(state, snap)
            old_cap = self.capacity
            kind = str(err)
            self._grow_for(kind)
            if self.capacity != old_cap:
                self._grow_state_arena(state, old_cap)
        # restart bookkeeping (BENCH_incremental records these per profile):
        # every retry rolls the operation back; growing a WIDE cap
        # additionally recompiles every fn keyed on the outgrown width —
        # the "wide-growth discovery" cost _presize_delta exists to avoid
        state.stats.capacity_retries += 1
        if kind in ("bind", "out", "out_cap", "rewrite"):
            state.stats.wide_growth_restarts += 1

    def _barrier(self, state: EngineState) -> None:
        """The epoch barrier: an update operation's fixpoint is complete.
        No-op updates cross it too — their fixpoint is the unchanged store,
        and readers' epochs must stay monotone and attributable."""
        with self.dispatches.in_phase("barrier"):
            state.update_epoch += 1
            self._refresh_stats(state)

    def _rewrite_program(self, state: EngineState, stats):
        """Rewrite the program under the compressed current rho and classify
        each changed rule for re-evaluation.

        The ONE booking site for ``rule_rewrites``/``rules_requeued`` —
        both the host round loop and the fused rewrite-due exit go through
        here, so a single rho change can never be double-booked no matter
        which loop detected it (the fused exit round is re-run by the host,
        which used to hold its own copy of this block).

        Returns ``(merge_q, full_q)``: ``merge_q`` is ``[(rule_idx,
        anchor_atom), ...]`` for merge-targeted evaluation
        (:meth:`_eval_rule_merge`), ``full_q`` the rules that keep the
        whole-rule full-plan requeue — every changed rule when
        ``rederive_mode="requeue"`` (the differential baseline), else only
        the variable-free-anchor corner cases (``remerge_full_fallback``).
        """
        rep_host = compress_np(np.asarray(state.rep))
        p_old = state.program
        p_new, changed_idx = p_old.rewrite(rep_host)
        merge_q: list[tuple[int, int]] = []
        full_q: list[int] = []
        if changed_idx:
            stats.rule_rewrites += 1
            stats.rules_requeued += len(changed_idx)
            # targeting applies to MAINTENANCE operations (like the delete
            # side's rederive): the base fixpoint keeps the whole-rule
            # requeue so its derivation/application counters stay exactly
            # the paper's Table 2 semantics (parity with the numpy oracle)
            targeted = self._updating and self.rederive_mode == "targeted"
            for k in changed_idx:
                if not targeted:
                    full_q.append(k)
                    continue
                how, anchor = classify_remerge(p_old.rules[k], p_new.rules[k])
                if how == "anchor":
                    merge_q.append((k, anchor))
                elif how == "full":
                    full_q.append(k)
                    stats.remerge_full_fallback += 1
                # "skip": head-only change — the sweep re-normalises the
                # stored head instances, no evaluation needed
        state.program = p_new
        return merge_q, full_q

    def _eval_rule_merge(
        self, state: EngineState, r, rule: Rule, k: int, anchor: int, stats
    ):
        """Merge-targeted evaluation of one rewritten rule — the
        forward-side analogue of the delete side's head-bound rederivation
        (:meth:`_eval_rule_rederive`): one plan anchored at the changed
        body atom against the pre-merge store, remaining atoms chained
        through the live store via the persistent index.  Runs at the
        narrow active delta buffers — the join width scales with the
        merged cliques' footprint, never the arena.
        """
        atom_consts = np.zeros((len(rule.body), 3), np.int32)
        for j, atom in enumerate(rule.body):
            for pos, t in enumerate(atom):
                atom_consts[j, pos] = 0 if is_var(t) else t
        head_consts = np.asarray(
            [0 if is_var(t) else t for t in rule.head], np.int32
        )
        head_slots = tuple(t if is_var(t) else None for t in rule.head)
        plan_t = tuple(build_merge_plan(rule, anchor))
        bind_cap, out_cap = self._active_bind, self._active_delta_out
        fn = self._get_plan_fn(
            ("mplan", k, anchor, plan_t, head_slots,
             ("bind", bind_cap), ("out", out_cap)),
            plan_t, head_slots, bind_cap, out_cap,
        )
        heads, valid, n_d, n_a, ov_bind, ov_out = fn(
            state.spo, state.epoch, state.marked, state.tomb,
            state.sorted_keys, state.sort_perm, state.pos_keys, state.pos_perm,
            jnp.asarray(r, I32),
            jnp.asarray(atom_consts), jnp.asarray(head_consts),
        )
        count_joins(stats, [plan_t])
        if bool(np.asarray(ov_bind).any()):
            raise CapacityError(self._active_bind_kind)
        if bool(np.asarray(ov_out).any()):
            raise CapacityError(self._active_delta_kind)
        stats.derivations += int(np.asarray(n_d).sum())
        stats.rule_applications += int(np.asarray(n_a).sum())
        stats.remerge_targeted += 1
        return [(heads, valid)]

    # -- driver --------------------------------------------------------------
    def _forward(
        self,
        state: EngineState,
        cands,
        cand_valid,
        requeued: list[int],
        max_rounds: int,
    ) -> None:
        """The shared bulk-synchronous round loop, resuming from ``state``.

        Used by the base fixpoint (seeded with the explicit facts), additions
        (seeded with the delta batch) and the delete path's rederive/forward
        pass (seeded with the rederivation candidates + a requeue of every
        rule whose head can restore an overdeleted fact).  ``state.r`` keeps
        increasing across invocations so the epoch discipline is preserved:
        the first round here inserts at a fresh epoch, and the next round's
        delta plans match exactly those rows.
        """
        stats = state.stats
        requeued = list(requeued)
        rounds_here = 0
        first = True
        have_cands = True
        while first or have_cands or requeued:
            first = False
            # fused fixpoint: while the stream sits at the active delta
            # width and no full-plan requeue is pending, run the whole
            # inner loop as ONE compiled lax.while_loop.  Requeued rules
            # and post-requeue WIDE streams (squeezed to out_cap) take the
            # host-orchestrated round below — delta plans narrow the
            # stream back within one round, and the fused loop resumes.
            if (
                self.fuse_rounds
                and not requeued
                and int(cands.shape[0]) == self._active_delta_out * self.n_shards
            ):
                if rounds_here >= max_rounds:
                    raise RuntimeError("did not converge")
                iters, cands, cand_valid, have_cands = self._fused_forward(
                    state, cands, cand_valid, max_rounds - rounds_here
                )
                rounds_here += iters
                continue
            state.r += 1
            r = state.r
            stats.rounds += 1
            rounds_here += 1
            if rounds_here > max_rounds:
                raise RuntimeError("did not converge")
            proc = self._get_process_fn(int(cands.shape[0]))
            (spo, epoch, marked, n_used, rep_new, state.sort_perm,
             state.sorted_keys, state.pos_perm, state.pos_keys, flags) = proc(
                state.spo, state.epoch, state.marked, state.n_used, state.rep,
                state.sort_perm, state.sorted_keys,
                state.pos_perm, state.pos_keys,
                cands, cand_valid, jnp.asarray(r, I32),
            )
            state.spo, state.epoch, state.marked, state.n_used = (
                spo, epoch, marked, n_used,
            )
            for kind in ("store", "rewrite", "route", "pair"):
                if bool(np.asarray(flags["ov_" + kind]).any()):
                    raise CapacityError(
                        self._active_rewrite_kind if kind == "rewrite" else kind
                    )
            if bool(np.asarray(flags["contradiction"]).reshape(-1)[0]):
                from .materialise import Contradiction

                raise Contradiction("owl:differentFrom violation")
            stats.sameas_pairs += int(np.asarray(flags["n_pairs"]).reshape(-1)[0])
            n_refl = int(np.asarray(flags["n_reflexive"]).sum())
            stats.reflexive_added += n_refl
            stats.derivations += n_refl

            rep_changed = bool(np.asarray(flags["rep_changed"]).reshape(-1)[0])
            state.rep = rep_new
            merge_q: list[tuple[int, int]] = []
            if rep_changed:
                mq, full_q = self._rewrite_program(state, stats)
                merge_q.extend(mq)
                requeued.extend(full_q)

            # evaluate plans for the new delta, skipping plans whose delta
            # atom is incompatible with the fresh rows' resource masks
            bufs = []
            had_full = False
            n_new = int(np.asarray(flags["n_new"]).sum())
            if n_new > 0:
                # per-position resource masks of the fresh delta, derived on
                # the host from the compacted delta rows (all shards' rows
                # arrive concatenated, so this is the global delta).  The
                # device truncates the window per shard; if the fresh rows
                # did not all fit, fall back to all-True masks — a superset,
                # so plan skipping stays sound
                d_rows = np.asarray(flags["delta_rows"])
                d_rows = d_rows[np.asarray(flags["delta_valid"])]
                if d_rows.shape[0] < n_new:
                    stats.delta_mask_fallbacks += 1
                    delta_masks = np.ones((3, state.n_res), dtype=bool)
                else:
                    delta_masks = np.zeros((3, state.n_res), dtype=bool)
                    for pos in range(3):
                        delta_masks[pos][d_rows[:, pos]] = True
                for k, rule in enumerate(state.program.rules):
                    bufs += self._eval_rule(
                        state, r + 1, rule, k, "delta", stats,
                        delta_masks=delta_masks,
                    )
            for k, anchor in merge_q:
                bufs += self._eval_rule_merge(
                    state, r + 1, state.program.rules[k], k, anchor, stats
                )
            for k in sorted(set(requeued)):
                bufs += self._eval_rule(
                    state, r + 1, state.program.rules[k], k, "full", stats
                )
                had_full = True
            requeued = []
            if bufs:
                cands, cand_valid = self._bucket_cands(bufs)
                # rounds that evaluated requeued FULL plans can emit
                # store-sized candidate sets — squeeze those to the wide
                # out_cap (whose process fn the base run compiled) instead
                # of forcing the narrow delta width into a growth retry
                target = self.out_cap if had_full else self._active_delta_out
                kind = "out" if had_full else self._active_delta_kind
                rows_global = target * self.n_shards
                if int(cands.shape[0]) > rows_global:
                    sq = self._get_squeeze_fn(int(cands.shape[0]), target)
                    cands, cand_valid, sq_ov = sq(cands, cand_valid)
                    if bool(np.asarray(sq_ov).any()):
                        raise CapacityError(kind)
                have_cands = bool(cand_valid.any())
            else:
                have_cands = False

    def _get_fused_forward_fn(self, n_cand_rows: int, plans_sig: tuple):
        key = (
            "fforward", n_cand_rows, plans_sig,
            ("bind", self._active_bind), ("out", self._active_delta_out),
            ("rewrite", self._active_rewrite), ("route", self.route_cap),
            ("pair", self.pair_cap),
        )
        if key not in self._fns:
            from .fused import fused_forward_rounds

            a = self.axis
            fn = partial(
                fused_forward_rounds,
                plans=plans_sig,
                rewrite_cap=self._active_rewrite,
                bind_cap=self._active_bind,
                plan_out_cap=self._active_delta_out,
                pair_cap=self.pair_cap,
                route_cap=self.route_cap if a is not None else None,
                axis=a,
                n_shards=self.n_shards,
                use_kernel=self.use_kernel,
            )
            d = P(a) if a else None
            rpl = P() if a else None
            flag_specs = {
                "iters": rpl, "have_cands": rpl, "n_new": rpl,
                "n_pairs": rpl,
                "n_reflexive": d, "n_deriv": d, "n_appl": d,
                "ov_store": rpl, "ov_rewrite": rpl, "ov_route": rpl,
                "ov_pair": rpl, "ov_bind": rpl, "ov_out": rpl,
                "ov_squeeze": rpl,
                "contradiction": rpl, "consts_changed": rpl,
            }
            self._jit_fn(
                key, fn,
                in_specs=(
                    d, d, d, d, d, rpl, d, d, d, d, d, d,
                    rpl, rpl, rpl, rpl, rpl, rpl,
                ),
                out_specs=(d, d, d, d, rpl, d, d, d, d, d, d, flag_specs),
            )
        return self._fns[key]

    def _fused_forward(self, state: EngineState, cands, cand_valid,
                       rounds_left: int):
        """Run forward rounds as one fused on-device fixpoint.

        Returns ``(iters, cands, cand_valid, have_cands)``.  Healthy
        convergence returns an empty stream; a rho-reaches-a-rule-constant
        exit rewrites the program on the host, re-evaluates the exit
        round's plans with the new constants (the device nullified its own
        evaluation of that round) and hands the resulting stream back to
        the driver loop.  Capacity overflow and contradiction raise exactly
        what the per-round host loop would have raised — the snapshot
        rollback upstream makes the committed post-overflow state moot.
        """
        from .fused import forward_plan_signature, program_tables

        stats = state.stats
        plans_sig = forward_plan_signature(state.program)
        fn = self._get_fused_forward_fn(int(cands.shape[0]), plans_sig)
        ac, hc, cv, cvd = program_tables(state.program)
        (state.spo, state.epoch, state.marked, state.n_used, state.rep,
         state.sort_perm, state.sorted_keys, state.pos_perm, state.pos_keys,
         cands, cand_valid, fl) = fn(
            state.spo, state.epoch, state.marked, state.tomb, state.n_used,
            state.rep, state.sort_perm, state.sorted_keys,
            state.pos_perm, state.pos_keys, cands, cand_valid,
            jnp.asarray(state.r, I32), jnp.asarray(rounds_left, I32),
            ac, hc, cv, cvd,
        )

        def flag(name: str) -> bool:
            return bool(np.asarray(fl[name]).reshape(-1)[0])

        iters = int(np.asarray(fl["iters"]).reshape(-1)[0])
        state.r += iters
        stats.rounds += iters
        # every iteration evaluates every plan (the exit one at a nullified
        # round, still on the device)
        count_joins(stats, [plan for _k, plan, _h in plans_sig], times=iters)
        stats.sameas_pairs += int(np.asarray(fl["n_pairs"]).reshape(-1)[0])
        n_refl = int(np.asarray(fl["n_reflexive"]).sum())
        stats.reflexive_added += n_refl
        stats.derivations += n_refl + int(np.asarray(fl["n_deriv"]).sum())
        stats.rule_applications += int(np.asarray(fl["n_appl"]).sum())

        for kind in ("store", "rewrite", "route", "pair"):
            if flag("ov_" + kind):
                raise CapacityError(
                    self._active_rewrite_kind if kind == "rewrite" else kind
                )
        if flag("contradiction"):
            from .materialise import Contradiction

            raise Contradiction("owl:differentFrom violation")
        if flag("ov_bind"):
            raise CapacityError(self._active_bind_kind)
        if flag("ov_out") or flag("ov_squeeze"):
            raise CapacityError(self._active_delta_kind)

        if flag("consts_changed"):
            merge_q, full_q = self._rewrite_program(state, stats)
            r = state.r
            bufs = []
            had_full = False
            if int(np.asarray(fl["n_new"]).reshape(-1)[0]) > 0:
                # the exit round's fresh delta was committed on device but
                # its window never crossed to the host — evaluate every
                # delta plan (a sound superset of the mask-filtered set;
                # impossible plans match zero rows and count nothing)
                for k, rule in enumerate(state.program.rules):
                    bufs += self._eval_rule(
                        state, r + 1, rule, k, "delta", stats,
                        delta_masks=None,
                    )
            for k, anchor in merge_q:
                bufs += self._eval_rule_merge(
                    state, r + 1, state.program.rules[k], k, anchor, stats
                )
            for k in sorted(set(full_q)):
                bufs += self._eval_rule(
                    state, r + 1, state.program.rules[k], k, "full", stats
                )
                had_full = True
            if bufs:
                cands, cand_valid = self._bucket_cands(bufs)
                target = self.out_cap if had_full else self._active_delta_out
                kind = "out" if had_full else self._active_delta_kind
                rows_global = target * self.n_shards
                if int(cands.shape[0]) > rows_global:
                    sq = self._get_squeeze_fn(int(cands.shape[0]), target)
                    cands, cand_valid, sq_ov = sq(cands, cand_valid)
                    if bool(np.asarray(sq_ov).any()):
                        raise CapacityError(kind)
                return iters, cands, cand_valid, bool(cand_valid.any())
            return iters, cands, cand_valid, False

        if flag("have_cands"):
            # round budget exhausted with candidates still flowing
            raise RuntimeError("did not converge")
        return iters, cands, cand_valid, False

    @staticmethod
    def _atom_may_match(atom, masks: np.ndarray) -> bool:
        """False iff a constant position of ``atom`` misses the delta masks
        (so the plan's delta atom cannot bind any fresh/frontier row).  A
        per-position relaxation of the numpy engine's ``_const_filter`` — a
        superset of its keep-set, hence sound to skip on False."""
        for pos, t in enumerate(atom):
            if not is_var(t) and not masks[pos][t]:
                return False
        return True

    def _eval_rule(
        self, state: EngineState, r, rule: Rule, k: int, mode: str, stats,
        delta_masks: np.ndarray | None = None,
    ):
        """Evaluate one rule's plans; ``mode`` in {"delta", "full", "tomb"}.

        "tomb" evaluates the overdelete variants (Delta = last tombstone
        wave, everything else = pre-deletion store) with ``r`` = the wave
        number; stats are not counted for those (mirroring the host path,
        which discards overdelete derivation counts).  ``delta_masks``
        (3, n_res) skips delta/tomb plans whose delta atom cannot match the
        current delta — skipped plans would contribute nothing (and count
        nothing: their delta atom matches zero rows).
        """
        atom_consts = np.zeros((len(rule.body), 3), np.int32)
        for j, atom in enumerate(rule.body):
            for pos, t in enumerate(atom):
                atom_consts[j, pos] = 0 if is_var(t) else t
        head_consts = np.asarray([0 if is_var(t) else t for t in rule.head], np.int32)
        head_slots = tuple(t if is_var(t) else None for t in rule.head)
        plans = build_plans(rule, full=(mode == "full"), tombstone=(mode == "tomb"))
        # full-evaluation plans keep the wide buffers (their bindings can be
        # store-sized); delta/tomb plans use whichever narrow buffers the
        # running operation activated — joins then sort/pad with the delta
        full_plan = mode == "full"
        out_cap = self.out_cap if full_plan else self._active_delta_out
        bind_cap = self.bind_cap if full_plan else self._active_bind
        out = []
        for i, plan in enumerate(plans):
            if (
                delta_masks is not None
                and mode in ("delta", "tomb")
                and not self._atom_may_match(rule.body[i], delta_masks)
            ):
                continue
            plan_t = tuple(plan)
            fn = self._get_plan_fn(
                ("plan", k, i, mode, plan_t, head_slots,
                 ("bind", bind_cap), ("out", out_cap)),
                plan_t, head_slots, bind_cap, out_cap,
            )
            heads, valid, n_d, n_a, ov_bind, ov_out = fn(
                state.spo, state.epoch, state.marked, state.tomb,
                state.sorted_keys, state.sort_perm,
                state.pos_keys, state.pos_perm,
                jnp.asarray(r, I32),
                jnp.asarray(atom_consts), jnp.asarray(head_consts),
            )
            count_joins(state.stats, [plan_t])
            if bool(np.asarray(ov_bind).any()):
                raise CapacityError(
                    "bind" if full_plan else self._active_bind_kind
                )
            if bool(np.asarray(ov_out).any()):
                # full plans always emit into out_cap; delta/tomb plans into
                # whichever buffer is active (the kind label, not a value
                # comparison — the two caps may coincide in size)
                raise CapacityError(
                    "out" if mode == "full" else self._active_delta_kind
                )
            if stats is not None:
                stats.derivations += int(np.asarray(n_d).sum())
                stats.rule_applications += int(np.asarray(n_a).sum())
                if full_plan:
                    stats.full_plan_evals += 1
            out.append((heads, valid))
        return out

    def _get_rederive_fn(self, key, plan, head_slots, seed_vars, bind_cap, out_cap):
        if key not in self._fns:
            a = self.axis
            fn = partial(
                eval_plan_rederive,
                plan=plan,
                head_var_slots=head_slots,
                seed_vars=seed_vars,
                bind_cap=bind_cap,
                out_cap=out_cap,
                axis=a,
                use_kernel=self.use_kernel,
            )
            d = P(a) if a else None
            rpl = P() if a else None
            self._jit_fn(
                key, fn,
                in_specs=(d, d, d, d, d, d, d, d, rpl, rpl, rpl, rpl),
                out_specs=(d, d, d, d, d),
            )
        return self._fns[key]

    def _eval_rule_rederive(self, state: EngineState, k: int, rule: Rule, seeds):
        """Backward-chained, head-bound evaluation of one rule — the
        delete-side targeted rederivation step.

        ``seeds`` is the (m, n_head_vars) host table of head-variable
        bindings extracted from the overdeleted instances
        (``incremental_spmd._head_bindings``, column order =
        :func:`build_rederive_plan`'s ``head_vars``).  The body joins run
        against the surviving live store through the persistent sorted
        index, so join width scales with the overdelete delta — never the
        arena.  Returns the restored instances as host (n, 3) rows.
        """
        plan, seed_vars = build_rederive_plan(rule)
        atom_consts = np.zeros((len(rule.body), 3), np.int32)
        for j, atom in enumerate(rule.body):
            for pos, t in enumerate(atom):
                atom_consts[j, pos] = 0 if is_var(t) else t
        head_consts = np.asarray(
            [0 if is_var(t) else t for t in rule.head], np.int32
        )
        head_slots = tuple(t if is_var(t) else None for t in rule.head)
        seeds = np.asarray(seeds, np.int32)
        if seeds.ndim != 2 or seeds.shape[1] != len(seed_vars):
            raise ValueError(
                f"seed table shape {seeds.shape} does not match the head's "
                f"variable order {seed_vars} (see build_rederive_plan)"
            )
        cap = max(64, _pow2(seeds.shape[0]))
        pad = cap - seeds.shape[0]
        seeds_j = jnp.asarray(np.pad(seeds, ((0, pad), (0, 0))), I32)
        valid_j = jnp.asarray(np.arange(cap) < seeds.shape[0])
        bind_cap, out_cap = self._active_bind, self._active_delta_out
        stats = state.stats
        stats.rederive_seed_rows += int(seeds.shape[0])
        stats.rederive_join_width = max(stats.rederive_join_width, cap)
        fn = self._get_rederive_fn(
            ("rplan", k, tuple(plan), head_slots, seed_vars,
             ("bind", bind_cap), ("out", out_cap), cap),
            tuple(plan), head_slots, seed_vars, bind_cap, out_cap,
        )
        out, valid, n_d, ov_bind, ov_out = fn(
            state.spo, state.epoch, state.marked, state.tomb,
            state.sorted_keys, state.sort_perm, state.pos_keys, state.pos_perm,
            jnp.asarray(atom_consts), jnp.asarray(head_consts),
            seeds_j, valid_j,
        )
        count_joins(stats, [plan], seeded=True)
        if bool(np.asarray(ov_bind).any()):
            raise CapacityError(self._active_bind_kind)
        if bool(np.asarray(ov_out).any()):
            raise CapacityError(self._active_delta_kind)
        stats.derivations += int(np.asarray(n_d).sum())
        return np.asarray(out).reshape(-1, 3)[np.asarray(valid).reshape(-1)]

    # -- public API ----------------------------------------------------------
    def materialise_state(
        self, facts, program: Program, max_rounds: int = 10_000
    ) -> EngineState:
        """Base REW fixpoint returning a maintainable device-resident state."""
        import time

        t0 = time.perf_counter()
        facts = np.asarray(facts, np.int32).reshape(-1, 3)
        while True:
            try:
                # the base run's early deltas are dataset-sized: delta plans
                # use the full out_cap here, the narrow delta_out on updates
                self._set_update_buffers(False)
                with enable_x64():
                    state = self._fresh_state(program)
                    state.stats.triples_explicit = facts.shape[0]
                    cands, cand_valid = self._pad_cands(facts)
                    self._forward(state, cands, cand_valid, [], max_rounds)
                break
            except CapacityError as e:
                self._grow_for(str(e))
        from .triples import dedup_rows

        state.explicit = dedup_rows(facts)
        self._refresh_stats(state)
        state.stats.wall_seconds += time.perf_counter() - t0
        return state

    def add_facts(
        self, state: EngineState, delta, max_rounds: int = 10_000, retry: bool = True
    ) -> EngineState:
        """Add explicit triples and maintain the store on the accelerator."""
        return self._apply_update(state, "add", delta, max_rounds, retry)

    def delete_facts(
        self, state: EngineState, delta, max_rounds: int = 10_000, retry: bool = True
    ) -> EngineState:
        """Retract explicit triples via the sharded overdelete/rederive pass."""
        return self._apply_update(state, "delete", delta, max_rounds, retry)

    def _apply_update(self, state, op, delta, max_rounds, retry):
        import time

        from .incremental_spmd import spmd_add_facts, spmd_delete_facts

        t0 = time.perf_counter()
        self._maybe_reset_fallback(state)
        while True:
            snap = self._snapshot(state)
            try:
                self._set_update_buffers(True)
                with enable_x64():
                    if op == "add":
                        spmd_add_facts(self, state, delta, max_rounds)
                    else:
                        spmd_delete_facts(self, state, delta, max_rounds)
                break
            except CapacityError as e:
                if not retry:
                    raise
                self._recover_capacity(state, snap, e)
        self._barrier(state)
        state.stats.wall_seconds += time.perf_counter() - t0
        return state

    def materialise_incremental(
        self,
        facts,
        program: Program,
        updates,
        max_rounds: int = 10_000,
        on_device: bool = True,
    ):
        """Base REW materialisation on the accelerator, then maintain the
        result through an update stream without re-running from scratch.

        ``updates`` is an iterable of ``("add" | "delete", delta)`` pairs
        (each delta an (n, 3) int array of explicit triples, original IDs).
        By default both the base fixpoint and the maintenance rounds run on
        this engine (:mod:`repro.core.incremental_spmd`: epoch-tagged
        tombstones + owner-routed delta exchange).  ``on_device=False``
        replays the updates through the host subsystem
        (:mod:`repro.core.incremental`) instead — the reference oracle and
        the baseline bench_incremental compares against.  Returns
        ``(spo, rep, stats)`` like :meth:`materialise`.
        """
        if on_device:
            state = self.materialise_state(facts, program, max_rounds)
            for op, delta in updates:
                if op == "add":
                    self.add_facts(state, delta, max_rounds)
                elif op in ("delete", "del"):
                    self.delete_facts(state, delta, max_rounds)
                else:
                    raise ValueError(f"unknown update op {op!r}")
            return self.state_triples(state), self.state_rep(state), state.stats

        from .incremental import IncrementalState, add_facts, delete_facts
        from .triples import TripleArena, dedup_rows

        spo, rep, stats = self.materialise(facts, program, max_rounds)
        arena = TripleArena()
        arena.add_batch(spo)
        p_cur, _ = program.rewrite(rep)
        host_state = IncrementalState(
            arena=arena,
            rep=rep.astype(np.int32),
            program=p_cur,
            base_program=program,
            explicit=dedup_rows(facts),
            n_resources=self.n_resources,
            stats=stats,
        )
        for op, delta in updates:
            if op == "add":
                add_facts(host_state, delta, max_rounds)
            elif op in ("delete", "del"):
                delete_facts(host_state, delta, max_rounds)
            else:
                raise ValueError(f"unknown update op {op!r}")
        host_state.result()  # refresh triple/memory counters on stats
        return host_state.triples(), host_state.rep, host_state.stats

    def materialise(self, facts, program: Program, max_rounds: int = 10_000):
        """REW materialisation with automatic capacity growth."""
        state = self.materialise_state(facts, program, max_rounds)
        spo = self.state_triples(state)
        return spo, self.state_rep(state), state.stats


# -- audit trace builders (repro.analysis) ----------------------------------
#
# Builders trace each fn family at the CALLER's probe geometry (the supplied
# engine/state), single-device and un-jitted — jaxpr-level invariants are
# about which primitives the fn binds at which shapes, not about how XLA
# compiles them, and the SPMD wrappers only add shard_map plumbing around
# the same body.

def _trace_rule_plans(engine, state, rule, k):
    atom_consts = jnp.zeros((len(rule.body), 3), I32)
    head_consts = jnp.zeros((3,), I32)
    head_slots = tuple(t if is_var(t) else None for t in rule.head)
    for mode, full, tomb in (
        ("delta", False, False), ("full", True, False), ("tomb", False, True),
    ):
        for i, plan in enumerate(build_plans(rule, full=full, tombstone=tomb)):
            fn = partial(
                eval_plan, plan=tuple(plan), head_var_slots=head_slots,
                bind_cap=engine.bind_cap, out_cap=engine.out_cap, axis=None,
                use_kernel=engine.use_kernel,
            )
            jx = jax.make_jaxpr(fn)(
                state.spo, state.epoch, state.marked, state.tomb,
                state.sorted_keys, state.sort_perm,
                state.pos_keys, state.pos_perm,
                jnp.asarray(1, I32), atom_consts, head_consts,
            )
            yield f"plan:rule{k}:{mode}:{i}", jx


@register_auditable("plan")
def _audit_plan(engine, state):
    for k, rule in enumerate(state.program.rules):
        yield from _trace_rule_plans(engine, state, rule, k)


@register_auditable("rplan")
def _audit_rplan(engine, state):
    for k, rule in enumerate(state.program.rules):
        plan, seed_vars = build_rederive_plan(rule)
        if not seed_vars:
            continue  # variable-free head: whole-rule requeue fallback
        head_slots = tuple(t if is_var(t) else None for t in rule.head)
        fn = partial(
            eval_plan_rederive, plan=tuple(plan), head_var_slots=head_slots,
            seed_vars=seed_vars, bind_cap=engine.bind_cap,
            out_cap=engine.out_cap, axis=None, use_kernel=engine.use_kernel,
        )
        jx = jax.make_jaxpr(fn)(
            state.spo, state.epoch, state.marked, state.tomb,
            state.sorted_keys, state.sort_perm, state.pos_keys, state.pos_perm,
            jnp.zeros((len(rule.body), 3), I32), jnp.zeros((3,), I32),
            jnp.zeros((64, len(seed_vars)), I32), jnp.zeros((64,), bool),
        )
        yield f"rplan:rule{k}", jx


@register_auditable("mplan")
def _audit_mplan(engine, state):
    # one trace per (rule, anchor) the forward-side targeted re-merge can
    # dispatch: any body atom with a variable can be the changed anchor
    # (ground anchors fall back to the whole-rule "plan" full mode)
    for k, rule in enumerate(state.program.rules):
        head_slots = tuple(t if is_var(t) else None for t in rule.head)
        for anchor in range(len(rule.body)):
            if not any(is_var(t) for t in rule.body[anchor]):
                continue
            plan = build_merge_plan(rule, anchor)
            fn = partial(
                eval_plan, plan=tuple(plan), head_var_slots=head_slots,
                bind_cap=engine.bind_cap, out_cap=engine.out_cap, axis=None,
                use_kernel=engine.use_kernel,
            )
            jx = jax.make_jaxpr(fn)(
                state.spo, state.epoch, state.marked, state.tomb,
                state.sorted_keys, state.sort_perm,
                state.pos_keys, state.pos_perm, jnp.asarray(1, I32),
                jnp.zeros((len(rule.body), 3), I32), jnp.zeros((3,), I32),
            )
            yield f"mplan:rule{k}:anchor{anchor}", jx


@register_auditable("process")
def _audit_process(engine, state):
    fn = partial(
        process_candidates, rewrite_cap=engine.rewrite_cap, axis=None,
        n_shards=1, route_cap=None, pair_cap=engine.pair_cap,
        use_kernel=engine.use_kernel,
    )
    cands = jnp.zeros((engine.out_cap, 3), I32)
    cv = jnp.zeros((engine.out_cap,), bool)
    jx = jax.make_jaxpr(fn)(
        state.spo, state.epoch, state.marked, state.n_used, state.rep,
        state.sort_perm, state.sorted_keys, state.pos_perm, state.pos_keys,
        cands, cv, jnp.asarray(1, I32),
    )
    yield "process", jx


@register_auditable("squeeze")
def _audit_squeeze(engine, state):
    wide = 2 * engine.out_cap
    fn = partial(_squeeze_stream, target=engine.out_cap)
    jx = jax.make_jaxpr(fn)(
        jnp.zeros((wide, 3), I32), jnp.zeros((wide,), bool),
    )
    yield "squeeze", jx


@register_auditable("rebuild_index", skip_passes=("NoArenaSort",))
def _audit_rebuild_index(engine, state):
    # the ONE allowed arena argsort, once per index order (<= once per
    # mutation epoch, counted by stats.index_rebuilds) — exempt from
    # NoArenaSort by design
    jx = jax.make_jaxpr(_rebuild_index)(state.spo, state.epoch, state.marked)
    yield "rebuild_index", jx


@register_auditable("snapshot", skip_passes=("NoArenaSort",))
def _audit_snapshot(engine, state):
    # the per-barrier publication step of the serving tier: derives the
    # secondary (p,o,s)-ordered snapshot view with one argsort — a counted
    # per-epoch cost OFF the query path (docs/serving.md), exempt from
    # NoArenaSort exactly like the index rebuild it mirrors
    jx = jax.make_jaxpr(_publish_snapshot)(
        state.spo, state.sort_perm, state.sorted_keys
    )
    yield "snapshot", jx

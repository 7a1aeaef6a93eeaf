"""Batched (vmapped) BGP execution against device-resident snapshots.

The serving tier's query path.  The host executor answers one query at a
time through a numpy join; a standing service drains a *queue* of queries
per epoch, and most of them share a handful of BGP shapes (the
DaRLing-style workload :mod:`repro.data.generator` models).  This module
groups queued queries by **shape signature** — the BGP with variables
canonically renumbered and constants abstracted to slots — and evaluates
each group in ONE compiled call: the per-query matcher is built once per
shape and ``jax.vmap`` runs it over the batch axis of constant bindings,
the batch-many-small-state-machines idiom the ROADMAP names.

The matcher itself is the engine's index-probe join
(:func:`repro.core.engine_jax._expand_join_index`,
:func:`repro.kernels.bsearch.prefix_range_bounds`) re-targeted at a
published :class:`~repro.core.engine_jax.StoreSnapshot`: the snapshot keeps
the live rows in two sorted packed-key orders — ``(s,p,o)`` and
``(p,o,s)`` — so every atom whose bound positions form a prefix of either
order is two ``jnp.searchsorted`` calls plus a cumsum-enumerated gather,
never an arena-length scan or sort.  Atoms with no bound prefix under
either order make the whole query **non-batchable**: it falls back to the
host matcher against the snapshot's lazy host copy (correctness never
depends on batchability).  Per-query width overflow likewise falls back —
the flag rides out of the compiled call, so a pathological query can never
silently truncate its answer bag.

Everything *after* the BGP match — FILTER/BIND steps, projection
multiplicities, clique expansion — is the host executor's
:func:`repro.sparql.executor._finish`, shared verbatim, so the batched and
scalar paths can only differ in how solution rows are produced (the
differential tests pin that they don't differ at all).

Dispatches are tagged under the ``"query"`` phase and the compiled matcher
registers with the trace-audit inventory as the ``"bgp"`` family.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.compat import enable_x64
from repro.core.engine_jax import I32, register_auditable
from repro.core.seminaive import Bindings
from repro.core.terms import is_var

from .algebra import Query
from .executor import _Solutions, _finish, _normalise_query, evaluate_at

_MAXID = (1 << 21) - 1

# the two published key orders: position scan sequences matching the packing
# of StoreSnapshot.d_keys ((s<<42)|(p<<21)|o) and d_keys_pos ((p<<42)|(o<<21)|s)
_ORDERS = (("spo", (0, 1, 2)), ("pos", (1, 2, 0)))


# ---------------------------------------------------------------------------
# shape signatures and probe plans (static, per shape)
# ---------------------------------------------------------------------------

def shape_signature(patterns) -> tuple[tuple, dict[int, int]]:
    """Canonical BGP shape: vars renumbered by first occurrence, constants
    abstracted to occurrence slots.

    Queries sharing a signature share one compiled matcher; their constants
    become the vmapped batch axis.  Returns ``(sig, varmap)`` where
    ``varmap`` maps the query's actual var ids to canonical ids.
    """
    varmap: dict[int, int] = {}
    sig = []
    for atom in patterns:
        parts = []
        for t in atom:
            if is_var(t):
                if t not in varmap:
                    varmap[t] = len(varmap)
                parts.append(("v", varmap[t]))
            else:
                parts.append("c")
        sig.append(tuple(parts))
    return tuple(sig), varmap


@dataclass(frozen=True)
class _Probe:
    """One planned atom: a range probe against one key order + post-filters."""

    order: str          # "spo" | "pos" — which snapshot view to probe
    atom: int           # original atom index (labels only)
    prefix: tuple       # leading key positions: ("const", slot) | ("var", cv)
    post_consts: tuple  # ((triple_pos, slot), ...) consts outside the prefix
    post_bound: tuple   # ((triple_pos, cv), ...) bound vars outside the prefix
    eq_pairs: tuple     # ((pos_a, pos_b), ...) repeated vars within the atom
    free: tuple         # ((cv, triple_pos), ...) vars first bound here


@dataclass(frozen=True)
class BatchPlan:
    sig: tuple
    probes: tuple
    n_consts: int
    var_order: tuple    # canonical var ids in binding order


def build_plan(sig) -> BatchPlan | None:
    """Greedy longest-bound-prefix atom ordering over the two key orders.

    At each step pick the remaining atom with the longest prefix of bound
    positions (const or already-bound var) under either published order —
    ties break to the earlier atom and the primary ``(s,p,o)`` order.  BGP
    join bags are atom-order independent (each solution row is one choice
    of matching triple per atom), so reordering is free; an atom with no
    bound prefix at its turn makes the shape non-batchable (``None``) —
    the batched path has no cartesian/scan fallback by design.
    """
    const_slot: dict[tuple[int, int], int] = {}
    for i, atom in enumerate(sig):
        for pos, t in enumerate(atom):
            if t == "c":
                const_slot[(i, pos)] = len(const_slot)
    remaining = list(range(len(sig)))
    bound: set[int] = set()
    var_order: list[int] = []
    probes = []
    while remaining:
        best = None  # (prefix_len, atom, order_name, scan_seq)
        for i in remaining:
            for name, seq in _ORDERS:
                plen = 0
                for pos in seq:
                    t = sig[i][pos]
                    if t == "c" or t[1] in bound:
                        plen += 1
                    else:
                        break
                if best is None or plen > best[0]:
                    best = (plen, i, name, seq)
        plen, i, name, seq = best
        if plen == 0:
            return None
        atom = sig[i]
        prefix_pos = set(seq[:plen])
        prefix = tuple(
            ("const", const_slot[(i, pos)]) if atom[pos] == "c"
            else ("var", atom[pos][1])
            for pos in seq[:plen]
        )
        post_consts, post_bound, eq_pairs, free = [], [], [], []
        first_pos: dict[int, int] = {}
        for pos in (0, 1, 2):
            t = atom[pos]
            if t == "c":
                if pos not in prefix_pos:
                    post_consts.append((pos, const_slot[(i, pos)]))
            else:
                cv = t[1]
                if cv in first_pos:
                    eq_pairs.append((first_pos[cv], pos))
                else:
                    first_pos[cv] = pos
                    if cv in bound:
                        if pos not in prefix_pos:
                            post_bound.append((pos, cv))
                    else:
                        free.append((cv, pos))
        probes.append(_Probe(
            name, i, prefix,
            tuple(post_consts), tuple(post_bound), tuple(eq_pairs),
            tuple(free),
        ))
        for cv, _ in free:
            bound.add(cv)
            var_order.append(cv)
        remaining.remove(i)
    return BatchPlan(sig, tuple(probes), len(const_slot), tuple(var_order))


# ---------------------------------------------------------------------------
# the compiled matcher (one query; vmapped over the batch axis)
# ---------------------------------------------------------------------------

def _pack_parts(parts) -> jnp.ndarray:
    key = jnp.zeros(parts[0].shape, dtype=jnp.int64)
    for c in parts:
        key = (key << 21) | c.astype(jnp.int64)
    return key


def _bgp_one(probes, var_order, W: int,
             d_tri, d_keys, d_tri_pos, d_keys_pos, consts):
    """Match one query's BGP against a published snapshot; width-``W`` table.

    The binding table starts as the single empty substitution and each probe
    expands it like :func:`repro.core.engine_jax._expand_join_index`: pack
    per-row lo/hi prefix keys (zeros / MAXID beyond the prefix), two
    ``searchsorted`` range probes, a cumsum-enumerated gather of the
    matching rows, then mask-level post-filters for non-prefix constants,
    bound vars and repeated-var equality.  KEY_MAX padding rows sort behind
    every real key, so live-row bounds need no explicit ``n_live`` argument.
    A step whose true output exceeds ``W`` raises the per-query overflow
    flag — the caller falls back to the host matcher, never truncates.

    Two cost cuts versus the naive form (they set the batched-vs-scalar
    throughput ratio):

      * the FIRST probe's prefix is all constants by construction (nothing
        is bound yet), so its range is found by two *scalar* binary
        searches and enumerated by a plain range gather — no W-point
        searchsorted against the key array;
      * later probes assign output slots to binding rows with a
        scatter+cumsum over the exclusive offsets (``seg = cumsum(marks)-1``)
        instead of a W-point binary search into ``cum`` — O(W) work, and
        empty rows are skipped because their mark lands on the next row's
        start offset.
    """
    j = jnp.arange(W)
    cols: dict[int, jnp.ndarray] = {}
    overflow = jnp.zeros((), bool)

    pr0 = probes[0]
    keys = d_keys if pr0.order == "spo" else d_keys_pos
    tri = d_tri if pr0.order == "spo" else d_tri_pos
    lo_parts = [consts[ref].astype(jnp.int64) for _k, ref in pr0.prefix]
    hi_parts = list(lo_parts)
    for _ in range(3 - len(pr0.prefix)):
        lo_parts.append(jnp.zeros((), jnp.int64))
        hi_parts.append(jnp.full((), _MAXID, jnp.int64))
    lo0 = jnp.searchsorted(keys, _pack_parts(lo_parts), side="left")
    hi0 = jnp.searchsorted(keys, _pack_parts(hi_parts), side="right")
    n0 = jnp.maximum(hi0 - lo0, 0)
    src = jnp.clip(lo0 + j, 0, keys.shape[0] - 1)
    rows = tri[src]
    ok = j < n0
    for pos, slot in pr0.post_consts:
        ok = ok & (rows[:, pos] == consts[slot])
    for a, b in pr0.eq_pairs:
        ok = ok & (rows[:, a] == rows[:, b])
    for cv, pos in pr0.free:
        cols[cv] = jnp.where(ok, rows[:, pos], 0)
    overflow = overflow | (n0 > W)
    valid = ok

    for pr in probes[1:]:
        keys = d_keys if pr.order == "spo" else d_keys_pos
        tri = d_tri if pr.order == "spo" else d_tri_pos
        lo_parts, hi_parts = [], []
        for kind, ref in pr.prefix:
            col = (jnp.broadcast_to(consts[ref].astype(jnp.int64), (W,))
                   if kind == "const" else cols[ref].astype(jnp.int64))
            lo_parts.append(col)
            hi_parts.append(col)
        for _ in range(3 - len(pr.prefix)):
            lo_parts.append(jnp.zeros((W,), jnp.int64))
            hi_parts.append(jnp.full((W,), _MAXID, jnp.int64))
        lo = jnp.searchsorted(keys, _pack_parts(lo_parts), side="left")
        hi = jnp.searchsorted(keys, _pack_parts(hi_parts), side="right")
        counts = jnp.where(valid, jnp.maximum(hi - lo, 0), 0)
        cum = jnp.cumsum(counts) - counts  # exclusive
        total = counts.sum()
        marks = jnp.zeros((W,), I32).at[cum].add(
            1, mode="drop", indices_are_sorted=True
        )
        seg = jnp.cumsum(marks) - 1
        within = j - cum[seg]
        src = jnp.clip(lo[seg] + within, 0, keys.shape[0] - 1)
        rows = tri[src]
        ok = (j < total) & valid[seg]
        for pos, slot in pr.post_consts:
            ok = ok & (rows[:, pos] == consts[slot])
        for pos, cv in pr.post_bound:
            ok = ok & (rows[:, pos] == cols[cv][seg])
        for a, b in pr.eq_pairs:
            ok = ok & (rows[:, a] == rows[:, b])
        new_cols = {cv: jnp.where(ok, c[seg], 0) for cv, c in cols.items()}
        for cv, pos in pr.free:
            new_cols[cv] = jnp.where(ok, rows[:, pos], 0)
        overflow = overflow | (total > W)
        cols, valid = new_cols, ok
    if var_order:
        out = jnp.stack([cols[cv] for cv in var_order])
    else:
        out = jnp.zeros((1, W), I32)  # all-const BGP: validity carries it
    return out.astype(I32), valid, overflow


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


# ---------------------------------------------------------------------------
# the batch executor (host orchestration)
# ---------------------------------------------------------------------------

class BatchedExecutor:
    """Drain a query list against one snapshot in grouped vmapped dispatches.

    Owns the per-shape plan cache and the policy knobs; the compiled
    matchers live in the *engine's* fn cache (keys
    ``("bgp", sig, B_pad, W, N)``) under normal dispatch accounting, tagged
    with the ``"query"`` phase.  ``run`` preserves input order and returns
    ``(answers, epoch)`` per query, exactly like
    :func:`repro.sparql.executor.evaluate_at` — host fallback (non-batchable
    shape, short group, width overflow, host-only snapshot) is invisible in
    the results.  Thread-wise ``run`` is called by one drain at a time (the
    scheduler serialises query drains); the stats dict is advisory.
    ``last_wait_ms`` is how long the calling thread's last ``run`` waited
    for its matchers' answers to come back from the device.
    """

    def __init__(self, engine, width: int = 4096, min_batch: int = 2,
                 max_batch: int = 256):
        self.engine = engine
        self.width = width
        self.min_batch = max(int(min_batch), 1)
        self.max_batch = max(int(max_batch), 1)
        self._plans: dict[tuple, BatchPlan | None] = {}
        self.stats = {"batched": 0, "fallback": 0, "overflow": 0, "groups": 0}
        self._last = threading.local()

    def _plan(self, sig) -> BatchPlan | None:
        if sig not in self._plans:
            self._plans[sig] = build_plan(sig)
        return self._plans[sig]

    @property
    def last_wait_ms(self) -> float:
        """The ms this thread's last ``run`` spent waiting for the device's
        answers (the host copies of its groups' outputs)."""
        return getattr(self._last, "wait_ms", 0.0)

    def run(self, queries: list[Query], snapshot, dic) -> list:
        self._last.wait_ms = 0.0
        results: list = [None] * len(queries)
        if not queries:
            return results
        if not getattr(snapshot, "on_device", False):
            for i, q in enumerate(queries):
                results[i] = evaluate_at(q, snapshot, dic)
            self.stats["fallback"] += len(queries)
            return results
        rep = snapshot.rho.rep
        prepared: list = [None] * len(queries)
        groups: dict[tuple, list[int]] = {}
        host: list[int] = []
        for i, q in enumerate(queries):
            qn = _normalise_query(q, rep)
            sig, varmap = shape_signature(qn.patterns)
            if self._plan(sig) is None:
                host.append(i)
                continue
            prepared[i] = (qn, varmap)
            groups.setdefault(sig, []).append(i)
        for sig, idxs in list(groups.items()):
            if len(idxs) < self.min_batch:  # batching buys nothing; skip compile
                host.extend(idxs)
                del groups[sig]
        for i in host:
            results[i] = evaluate_at(queries[i], snapshot, dic)
            self.stats["fallback"] += 1
        for sig, idxs in groups.items():
            for at in range(0, len(idxs), self.max_batch):
                self._last.wait_ms += self._run_group(
                    sig, idxs[at:at + self.max_batch], prepared,
                    queries, snapshot, dic, results,
                )
        return results

    def _run_group(self, sig, idxs, prepared, queries, snapshot, dic,
                   results) -> float:
        """Answer one shape group into ``results``; returns the ms spent
        waiting for the device."""
        plan = self._plans[sig]
        B_pad = _pow2(len(idxs))
        consts = np.zeros((B_pad, max(plan.n_consts, 1)), np.int32)
        for row, i in enumerate(idxs):
            qn, _ = prepared[i]
            cs = [t for atom in qn.patterns for t in atom if not is_var(t)]
            if cs:
                consts[row] = cs
        eng = self.engine
        key = ("bgp", sig, B_pad, self.width, int(snapshot.d_keys.shape[0]))
        with eng.dispatches.in_phase("query"):
            with enable_x64():
                if key not in eng._fns:
                    eng._register_fn(key, jax.jit(jax.vmap(
                        partial(_bgp_one, plan.probes, plan.var_order,
                                self.width),
                        in_axes=(None, None, None, None, 0),
                    )))
                out, valid, overflow = eng._fns[key](
                    snapshot.d_triples, snapshot.d_keys,
                    snapshot.d_triples_pos, snapshot.d_keys_pos,
                    jnp.asarray(consts),
                )
            # the host copy waits for the matcher, and for whatever the
            # device runs ahead of it (a maintenance program)
            t0 = time.perf_counter()
            out = np.asarray(out)
            valid = np.asarray(valid)
            overflow = np.asarray(overflow)
            wait_ms = (time.perf_counter() - t0) * 1e3
        col_of = {cv: k for k, cv in enumerate(plan.var_order)}
        for row, i in enumerate(idxs):
            if overflow[row]:
                results[i] = evaluate_at(queries[i], snapshot, dic)
                self.stats["overflow"] += 1
                continue
            qn, varmap = prepared[i]
            sel = np.flatnonzero(valid[row])
            cols = {
                v: out[row, col_of[cv]][sel].astype(np.int32)
                for v, cv in varmap.items()
            }
            sol = _Solutions(Bindings(cols, int(sel.shape[0])))
            results[i] = (
                _finish(queries[i], qn, sol, snapshot.rho, dic),
                snapshot.epoch,
            )
            self.stats["batched"] += 1
        self.stats["groups"] += 1
        return wait_ms


# ---------------------------------------------------------------------------
# trace-audit inventory (repro.analysis)
# ---------------------------------------------------------------------------

# representative shapes covering the serving workload's query kinds
# (repro.data.generator): single-predicate scan, object-join pair, and
# bound-object lookup — between them they exercise both key orders, free-var
# binding, bound-var post-filters and non-prefix constants.
_AUDIT_SIGS = (
    ((("v", 0), "c", ("v", 1)),),
    ((("v", 0), "c", ("v", 1)), (("v", 2), "c", ("v", 1))),
    ((("v", 0), "c", "c"),),
)


@register_auditable("bgp")
def _audit_bgp(engine, state):
    # traced at the probe arena's geometry: "arena-length" thresholds apply
    # to the snapshot views exactly as to the live arena they were gathered
    # from.  searchsorted's default scan method binds no sort primitive, so
    # the matcher passes NoArenaSort *without* an exemption — the one
    # publication argsort lives in the "snapshot" family, off this path.
    n = int(state.spo.shape[0])
    tri = jax.ShapeDtypeStruct((n, 3), jnp.int32)
    keys = jax.ShapeDtypeStruct((n,), jnp.int64)
    for si, sig in enumerate(_AUDIT_SIGS):
        plan = build_plan(sig)
        fn = partial(_bgp_one, plan.probes, plan.var_order, 256)
        jx = jax.make_jaxpr(fn)(
            tri, keys, tri, keys,
            jax.ShapeDtypeStruct((max(plan.n_consts, 1),), jnp.int32),
        )
        yield f"bgp:shape{si}", jx

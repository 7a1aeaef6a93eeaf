"""Plain reference of the store's semantics, and the comparison with it.

It imports nothing of the program.  From an explicit fact set and the rule
texts it computes, from scratch, what a REW store must hold at that epoch
(Motik et al. 2015, Theorem 1):

* ``rep``: each resource's representative, the least ID of its
  ``owl:sameAs`` clique, where the cliques are the connected components of
  the sameAs facts of the closure;
* ``rows``: the closure with every resource replaced by its
  representative, which includes ``<r, owl:sameAs, r>`` for every resource
  ``r`` of a stored row (and for ``owl:sameAs`` itself).

The fixpoint is naive: every round re-evaluates every rule over the whole
store, after the sameAs facts have been merged and the store renormalised.
A lookup's answer is evaluated over the expansion of that store: each
binding of a selected variable is replaced by every member of its clique,
which is the answer over the store an axiomatised closure would hold.
"""

from __future__ import annotations

import itertools
from collections import Counter

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components

from .data import SAME_AS, Lookup, Rule

_MASK = (1 << 21) - 1


def pack(rows: np.ndarray) -> np.ndarray:
    """(n, 3) rows to int64 keys, 21 bits per position."""
    r = rows.astype(np.int64)
    return (r[:, 0] << 42) | (r[:, 1] << 21) | r[:, 2]


def unpack(keys: np.ndarray) -> np.ndarray:
    return np.stack(
        [(keys >> 42) & _MASK, (keys >> 21) & _MASK, keys & _MASK], axis=1
    ).astype(np.int32)


def apply_op(explicit: np.ndarray, op: str, delta: np.ndarray) -> np.ndarray:
    """The explicit set after an add or a delete, as sorted distinct keys."""
    d = np.unique(pack(np.asarray(delta, np.int32).reshape(-1, 3)))
    if op == "add":
        return np.union1d(explicit, d)
    return np.setdiff1d(explicit, d, assume_unique=True)


def _components(n: int, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Least member of each node's connected component."""
    g = coo_matrix((np.ones(a.shape[0], np.int8), (a, b)), shape=(n, n))
    _, label = connected_components(g, directed=False)
    least = np.full(label.max() + 1, n, np.int64)
    np.minimum.at(least, label, np.arange(n))
    return least[label].astype(np.int32)


def _match(atom: tuple, rows: np.ndarray) -> dict[int, np.ndarray]:
    """Bindings of an atom's variables over ``rows``."""
    keep = np.ones(rows.shape[0], bool)
    first: dict[int, int] = {}
    for pos, t in enumerate(atom):
        if t >= 0:
            keep &= rows[:, pos] == t
        elif t in first:
            keep &= rows[:, pos] == rows[:, first[t]]
        else:
            first[t] = pos
    hit = rows[keep]
    return {v: hit[:, pos] for v, pos in first.items()}


def _join(left: dict, right: dict) -> dict:
    """Natural join of two binding tables (columns keyed by variable)."""
    shared = [v for v in left if v in right]
    n_l = len(next(iter(left.values()))) if left else 0
    n_r = len(next(iter(right.values()))) if right else 0
    if not shared:
        li = np.repeat(np.arange(n_l), n_r)
        ri = np.tile(np.arange(n_r), n_l)
    else:
        def key(t):
            k = np.zeros(len(t[shared[0]]), np.int64)
            for v in shared:
                k = (k << 21) | t[v].astype(np.int64)
            return k
        kl, kr = key(left), key(right)
        order = np.argsort(kr, kind="stable")
        kr_sorted = kr[order]
        lo = np.searchsorted(kr_sorted, kl, "left")
        hi = np.searchsorted(kr_sorted, kl, "right")
        cnt = hi - lo
        li = np.repeat(np.arange(n_l), cnt)
        offs = np.arange(li.shape[0]) - np.repeat(np.cumsum(cnt) - cnt, cnt)
        ri = order[np.repeat(lo, cnt) + offs]
    out = {v: c[li] for v, c in left.items()}
    out.update({v: c[ri] for v, c in right.items() if v not in out})
    return out


def _fire(rule: Rule, rep: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Head rows of one rule over ``rows``, its constants under ``rep``."""
    def norm(atom):
        return tuple(int(rep[t]) if t >= 0 else t for t in atom)

    table = None
    for atom in rule.body:
        b = _match(norm(atom), rows)
        table = b if table is None else _join(table, b)
        if len(next(iter(table.values()), ())) == 0:
            return np.zeros((0, 3), np.int32)
    n = len(next(iter(table.values())))
    head = norm(rule.head)
    return np.stack(
        [table[t] if t < 0 else np.full(n, t, np.int32) for t in head], axis=1
    ).astype(np.int32)


class Epoch:
    """The reference store of one explicit set: ``keys`` (sorted packed
    normal-form rows) and ``rep``."""

    def __init__(self, explicit_keys: np.ndarray, rules: list[Rule],
                 n_resources: int) -> None:
        rep = np.arange(n_resources, dtype=np.int32)
        keys = np.asarray(explicit_keys, np.int64)
        while True:
            rows = unpack(keys)
            same = rows[(rows[:, 1] == SAME_AS) & (rows[:, 0] != rows[:, 2])]
            if same.shape[0]:
                # rows are normal, so the merge is over representatives
                merge = _components(n_resources, same[:, 0], same[:, 2])
                rep = merge[rep]
                keys = np.unique(pack(merge[rows]))
                continue
            if rows.shape[0]:
                res = np.union1d(np.unique(rows), [SAME_AS])
                refl = np.stack([res, np.full_like(res, SAME_AS), res], axis=1)
                keys = np.union1d(keys, pack(refl))
                rows = unpack(keys)
            heads = [_fire(r, rep, rows) for r in rules]
            heads.append(np.zeros((0, 3), np.int32))
            new = np.unique(pack(rep[np.concatenate(heads)]))
            new = np.setdiff1d(new, keys, assume_unique=True)
            if new.shape[0] == 0:
                break
            keys = np.union1d(keys, new)
        self.keys, self.rep = keys, rep
        self._rows: np.ndarray | None = None
        self._pos: tuple | None = None
        self._members: dict[int, np.ndarray] | None = None

    @property
    def rows(self) -> np.ndarray:
        if self._rows is None:
            self._rows = unpack(self.keys)
        return self._rows

    def members(self, r: int) -> np.ndarray:
        if self._members is None:
            order = np.argsort(self.rep, kind="stable")
            bounds = np.flatnonzero(np.diff(self.rep[order])) + 1
            self._members = {
                int(self.rep[g[0]]): g
                for g in np.split(order, bounds) if g.shape[0] > 1
            }
        return self._members.get(int(r), np.asarray([r]))

    def _candidates(self, atom: tuple) -> np.ndarray:
        """The rows that can match ``atom``: a key range of the (s, p, o)
        order when the subject is bound, of a (p, o, s) order when the
        predicate and object are, else every row."""
        s, p, o = atom
        if s >= 0:
            lo = (s << 42) | (max(p, 0) << 21)
            hi = lo + (1 << 21 if p >= 0 else 1 << 42)
            i, j = np.searchsorted(self.keys, [lo, hi])
            return self.rows[i:j]
        if p >= 0 and o >= 0:
            if self._pos is None:
                r = self.rows.astype(np.int64)
                k = (r[:, 1] << 42) | (r[:, 2] << 21) | r[:, 0]
                order = np.argsort(k, kind="stable")
                self._pos = (k[order], self.rows[order])
            keys, rows = self._pos
            lo = (p << 42) | (o << 21)
            i, j = np.searchsorted(keys, [lo, lo + (1 << 21)])
            return rows[i:j]
        return self.rows

    def answer(self, q: Lookup, names: list) -> Counter:
        """The bag of ``q``'s answers, as tuples of resource names."""
        atom = tuple(int(self.rep[t]) if t >= 0 else t for t in q.atom)
        binding = _match(atom, self._candidates(atom))
        out: Counter = Counter()
        n = len(next(iter(binding.values()))) if binding else 0
        for i in range(n):
            out.update(itertools.product(*(
                [names[int(m)] for m in self.members(binding[v][i])]
                for v in q.select
            )))
        return out


def store_differs(rows: np.ndarray, want: Epoch) -> str | None:
    """Why ``rows`` is not the reference store, or None when it is."""
    keys = np.sort(pack(np.asarray(rows, np.int32).reshape(-1, 3)))
    n_dup = int((keys[1:] == keys[:-1]).sum())
    if n_dup:
        return f"{n_dup} rows held twice"
    if not np.array_equal(keys, want.keys):
        extra = np.setdiff1d(keys, want.keys).shape[0]
        missing = np.setdiff1d(want.keys, keys).shape[0]
        return f"{extra} rows the reference lacks, {missing} rows missing"
    return None


def rho_differs(rep: np.ndarray, want: Epoch) -> str | None:
    rep = np.asarray(rep)[: want.rep.shape[0]]
    if rep.shape != want.rep.shape:
        return f"rho covers {rep.shape[0]} of {want.rep.shape[0]} resources"
    bad = int((rep != want.rep).sum())
    return f"{bad} resources with another representative" if bad else None

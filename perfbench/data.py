"""The benchmark's data and traffic, made from a seed.

A copy, kept with the benchmark so that a change to the program cannot move
the yardstick, of three pieces of the program's own tooling:

* ``generate``: the clique-injected knowledge graphs that stand in for the
  rows of Table 2 of Motik et al., *Handling owl:sameAs via Rewriting*
  (AAAI 2015).  Duplicate groups share an ``:idProp`` value, and the
  inverse-functional rule ``(?x, owl:sameAs, ?y) <- (?x, :idProp, ?v) &
  (?y, :idProp, ?v)`` merges each group into a clique while the store
  materialises.
* ``sample_update_stream``: an add/delete stream, consistent as a sequence.
  Adds may join two existing entities through a fresh ``:idProp`` value
  (a new merge); deletes of ``:idProp`` rows split cliques.
* ``point_queries``: selective single-atom lookups with at most 32
  answers, and the spoke lookup of a clique member, which the store
  rewrites to the clique's representative and expands back.

Everything here is plain Python and numpy.  Resources are named strings,
interned to dense IDs in first-use order by :class:`Names`; IDs 1 and 2
are ``owl:sameAs`` and ``owl:differentFrom``, as in the program's own
dictionary.  Variables are negative integers.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

SAME_AS = 1
RESERVED = (None, "owl:sameAs", "owl:differentFrom")


class Names:
    """Resource names interned to dense IDs, in first-use order."""

    def __init__(self) -> None:
        self.names: list[str | None] = list(RESERVED)
        self._ids = {n: i for i, n in enumerate(RESERVED) if n is not None}

    def __len__(self) -> int:
        return len(self.names)

    def intern(self, name: str) -> int:
        rid = self._ids.get(name)
        if rid is None:
            rid = self._ids[name] = len(self.names)
            self.names.append(name)
        return rid

    def intern_many(self, names) -> list[int]:
        return [self.intern(n) for n in names]

    def id_of(self, name: str) -> int:
        return self._ids[name]

    def pad_to(self, n: int) -> None:
        """Intern unused names until there are ``n``, so that the resource
        space has one size whatever the seed drew."""
        if n < len(self.names):
            raise ValueError(f"{len(self.names)} names already, more than {n}")
        for i in range(len(self.names), n):
            self.intern(f":unused{i}")


@dataclass(frozen=True)
class Rule:
    """``head <- body``: atoms are (s, p, o) with variables < 0."""

    text: str
    head: tuple
    body: tuple


def parse_rule(text: str, names: Names) -> Rule:
    """Parse ``(h) <- (b1) & (b2)``; ``?x`` terms are variables."""
    def atoms(part: str) -> list[tuple]:
        out = []
        for chunk in part.split("&"):
            terms = [t.strip() for t in chunk.strip().strip("()").split(",")]
            out.append(tuple(
                -(varmap.setdefault(t, len(varmap) + 1)) if t.startswith("?")
                else names.intern(t)
                for t in terms
            ))
        return out

    varmap: dict[str, int] = {}
    head_txt, _, body_txt = text.partition("<-")
    (head,) = atoms(head_txt)
    return Rule(text, head, tuple(atoms(body_txt)))


@dataclass
class Graph:
    facts: np.ndarray          # (n, 3) int32 explicit facts
    rules: list[Rule]
    names: Names


def generate(
    n_groups: int = 200,
    group_size: int = 4,
    n_spokes_per: int = 3,
    n_plain: int = 2000,
    n_classes: int = 12,
    hierarchy_depth: int = 3,
    hometown_groups: int = 0,
    hometown_size: int = 0,
    chain_rules: bool = False,
    seed: int = 0,
) -> Graph:
    """The explicit facts, the rules and the names of one deployment."""
    rng = np.random.default_rng(seed)
    names = Names()
    texts = ["(?x, owl:sameAs, ?y) <- (?x, :idProp, ?v) & (?y, :idProp, ?v)"]
    for lvl in range(hierarchy_depth):
        texts.append(f"(?x, rdf:type, :C{lvl + 1}) <- (?x, rdf:type, :C{lvl})")
    if hometown_groups > 0:
        texts += [
            "(?y, :sameHomeTown, ?x) <- (?x, :sameHomeTown, ?y)",
            "(?x, :sameHomeTown, ?z) <- (?x, :sameHomeTown, ?y) & "
            "(?y, :sameHomeTown, ?z)",
        ]
    if chain_rules:
        texts += [
            "(?x, :colleagueOf, ?z) <- (?x, :worksAt, ?y) & (?z, :worksAt, ?y)",
            "(?x, :related, ?y) <- (?x, :colleagueOf, ?y)",
        ]
    rules = [parse_rule(t, names) for t in texts]

    id_prop = names.intern(":idProp")
    rdf_type = names.intern("rdf:type")
    spoke = names.intern(":spoke")
    works_at = names.intern(":worksAt")
    home = names.intern(":sameHomeTown")
    classes = names.intern_many([f":C{i}" for i in range(hierarchy_depth + 1)])

    rows: list[tuple[int, int, int]] = []
    for g in range(n_groups):
        vid = names.intern(f":idval{g}")
        members = names.intern_many([f":e{g}_{i}" for i in range(group_size)])
        for m in members:
            rows.append((m, id_prop, vid))
            rows.append((m, rdf_type, classes[0]))
        for j in range(n_spokes_per):
            s = names.intern(f":spoke{g}_{j}")
            rows.append((s, spoke, members[j % group_size]))

    ents = names.intern_many([f":p{i}" for i in range(max(n_plain // 4, 1))])
    orgs = names.intern_many([f":org{i}" for i in range(max(n_plain // 40, 1))])
    props = names.intern_many([":knows", ":near", ":partOf"])
    for _ in range(n_plain):
        s = ents[rng.integers(len(ents))]
        p = props[rng.integers(len(props))]
        o = ents[rng.integers(len(ents))]
        rows.append((s, p, o))
    if chain_rules:
        for e in ents:
            rows.append((e, works_at, orgs[rng.integers(len(orgs))]))

    for hg in range(hometown_groups):
        ppl = names.intern_many([f":ht{hg}_{i}" for i in range(hometown_size)])
        for i in range(hometown_size - 1):
            rows.append((ppl[i], home, ppl[i + 1]))

    return Graph(np.asarray(rows, dtype=np.int32), rules, names)


def sample_update_stream(
    facts: np.ndarray,
    names: Names,
    n_events: int,
    batch: int,
    p_delete: float = 0.5,
    p_merge_add: float = 0.4,
    op_order: list[str] | None = None,
    plan: list | None = None,
    restore: bool = False,
    seed: int = 0,
) -> list[tuple[str, np.ndarray]]:
    """``[(op, rows), ...]`` with ``op`` "add" or "delete".

    Deletes take ``batch`` rows explicit at that point.  An add draws
    ``batch`` rows: with probability ``p_merge_add`` a fresh ``:idProp``
    value shared by two existing subjects (two rows, a new merge),
    otherwise an existing (predicate, object) under an existing subject.
    ``plan``, when given, fixes the op and the batch of the first events as
    ``[(op, batch), ...]``; after it, ``op_order``, when given, fixes the
    ops cyclically, so that every seed gets the same sequence of ops;
    otherwise each op is a delete with probability ``p_delete``.  Fresh
    values are interned into ``names``: at most ``batch`` of them an event.
    With ``restore``, an add that follows a delete puts back that delete's
    rows instead, so the explicit set returns to what it was and every
    delete draws from the same set: the work of an update does not drift
    as the stream goes on.
    """
    rng = np.random.default_rng(seed)
    current: list[tuple[int, int, int]] = [tuple(map(int, r)) for r in facts]
    id_prop = names.id_of(":idProp")
    events: list[tuple[str, np.ndarray]] = []
    n_upd_vals = 0
    deleted = None  # the last delete's rows, while restore may put them back
    plan = list(plan or [])
    for ev in range(n_events):
        size = plan[ev][1] if ev < len(plan) else batch
        if ev < len(plan):
            do_delete = bool(current) and plan[ev][0] == "delete"
        elif op_order:
            k = ev - len(plan)
            do_delete = bool(current) and op_order[k % len(op_order)] == "delete"
        else:
            do_delete = bool(current) and rng.random() < p_delete
        if do_delete:
            m = min(size, len(current))
            idx = rng.choice(len(current), size=m, replace=False)
            delta = np.asarray([current[i] for i in idx], dtype=np.int32)
            keep = np.ones(len(current), dtype=bool)
            keep[idx] = False
            current = [row for row, k in zip(current, keep) if k]
            events.append(("delete", delta))
            deleted = delta if restore else None
            continue
        if deleted is not None:
            current.extend(tuple(map(int, r)) for r in deleted)
            events.append(("add", deleted))
            deleted = None
            continue
        subjects = sorted({r[0] for r in current})
        if len(subjects) < 2:
            subjects += names.intern_many([f":seed{ev}_{i}" for i in range(2)])
        rows: list[tuple[int, int, int]] = []
        for _ in range(size):
            if not current or rng.random() < p_merge_add:
                a, b = rng.choice(len(subjects), size=2, replace=False)
                vid = names.intern(f":updval{n_upd_vals}")
                n_upd_vals += 1
                rows.append((subjects[a], id_prop, vid))
                rows.append((subjects[b], id_prop, vid))
            else:
                src = current[rng.integers(len(current))]
                s = subjects[rng.integers(len(subjects))]
                rows.append((s, src[1], src[2]))
        delta = np.unique(np.asarray(rows, dtype=np.int32), axis=0)
        current.extend(tuple(map(int, r)) for r in delta)
        events.append(("add", delta))
    return events


@dataclass(frozen=True)
class Lookup:
    """One single-atom query: (s, p, o) with variables < 0, all selected."""

    kind: str
    atom: tuple
    select: tuple


def point_queries(
    facts: np.ndarray, names: Names, kinds: list[str], seed: int,
    max_answers: int = 32,
) -> list[Lookup]:
    """One lookup per entry of ``kinds``, constants drawn from ``facts``.

    ``s_p`` is ``(s, p, ?x)``, ``s`` is ``(s, ?x, ?y)`` and ``p_o`` is
    ``(?x, p, o)``, each drawn from explicit facts whose subject
    out-degree (for ``s_p``, ``s``) or (p, o) fan-in (for ``p_o``) is at
    most ``max_answers``.  ``spoke_member`` is ``(?x, :spoke, c)`` for a
    clique member ``c`` (a subject of an ``:idProp`` fact).
    """
    rng = np.random.default_rng(seed)
    key_po = facts[:, 1].astype(np.int64) << 32 | facts[:, 2].astype(np.int64)
    _, inv, cnt = np.unique(key_po, return_inverse=True, return_counts=True)
    _, inv_s, cnt_s = np.unique(facts[:, 0], return_inverse=True,
                                return_counts=True)
    pools = {
        "p_o": np.flatnonzero(cnt[inv] <= max_answers),
        "s": np.flatnonzero(cnt_s[inv_s] <= max_answers),
    }
    spoke = names.id_of(":spoke")
    members = np.unique(facts[facts[:, 1] == names.id_of(":idProp"), 0])
    out = []
    for kind in kinds:
        if kind == "spoke_member":
            c = int(members[rng.integers(members.shape[0])])
            out.append(Lookup(kind, (-1, spoke, c), (-1,)))
            continue
        pool = pools["p_o" if kind == "p_o" else "s"]
        s, p, o = (int(t) for t in facts[pool[rng.integers(pool.shape[0])]])
        if kind == "s_p":
            out.append(Lookup(kind, (s, p, -1), (-1,)))
        elif kind == "s":
            out.append(Lookup(kind, (s, -1, -2), (-1, -2)))
        elif kind == "p_o":
            out.append(Lookup(kind, (-1, p, o), (-1,)))
        else:
            raise ValueError(f"unknown query kind {kind!r}")
    return out

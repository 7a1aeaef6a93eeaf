"""Jaxpr traversal + the invariant passes of the trace-audit subsystem.

Each pass encodes one hot-path contract of the engine as a predicate over
the *compiled* representation — the jaxpr — rather than over the source:
refactors cannot silently reintroduce an arena-length sort or an int32 key
truncation without the audit (CI-gated via ``python -m repro.analysis
--check``) catching it at the primitive level.  See docs/analysis.md for
the contract each pass encodes and how to add a new one.
"""

from __future__ import annotations

from dataclasses import asdict, dataclass

from repro.compat import ClosedJaxpr, Jaxpr


# ---------------------------------------------------------------------------
# generic traversal
# ---------------------------------------------------------------------------

def sub_jaxprs(params: dict):
    """Every (sub)jaxpr reachable from an eqn's params, with its param key.

    Handles the shapes the engine's fns actually produce — ``pjit`` /
    ``closed_call`` (a single ClosedJaxpr under ``jaxpr``), ``scan`` /
    ``while`` (``jaxpr`` / ``cond_jaxpr`` + ``body_jaxpr``), ``cond``
    (a *tuple* of branch ClosedJaxprs), ``shard_map`` / ``custom_*`` calls
    — plus arbitrary list/tuple/dict nesting, which the historical ad-hoc
    helper (``tests/test_index_invariant._sub_jaxprs``) missed.  Yields
    ``(key, jaxpr)`` pairs with ClosedJaxprs unwrapped.
    """

    def visit(key, v):
        if isinstance(v, ClosedJaxpr):
            yield key, v.jaxpr
        elif isinstance(v, Jaxpr):
            yield key, v
        elif isinstance(v, (list, tuple)):
            for i, x in enumerate(v):
                yield from visit(f"{key}[{i}]", x)
        elif isinstance(v, dict):
            for k, x in v.items():
                yield from visit(f"{key}.{k}", x)

    for key, v in params.items():
        yield from visit(key, v)


def jaxpr_walk(jaxpr, path: tuple = ()):
    """Yield ``(eqn, path)`` for every eqn of ``jaxpr`` and all sub-jaxprs.

    ``path`` is the nesting trail of ``primitive[param_key]`` strings — a
    human-readable location for violation reports (and precise enough to
    find the eqn again).  Accepts a ClosedJaxpr or a Jaxpr.
    """
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn, path
        for key, sub in sub_jaxprs(eqn.params):
            yield from jaxpr_walk(sub, path + (f"{eqn.primitive.name}[{key}]",))


def _fmt_path(path: tuple) -> str:
    return "/".join(path) if path else "<top>"


def _leading_dim(aval) -> int:
    shape = getattr(aval, "shape", ())
    return int(shape[0]) if shape else 0


def count_sorts_at_least(jaxpr, n_rows: int) -> int:
    """Count sort eqns (recursively) whose operands reach ``n_rows`` rows.

    The shared replacement for the historical per-test helper: the count
    the no-arena-sort budget tests pin, expressed over :func:`jaxpr_walk`
    so nested ``cond`` branches / ``shard_map`` bodies are covered too.
    """
    return sum(
        1
        for eqn, _path in jaxpr_walk(jaxpr)
        if eqn.primitive.name == "sort"
        and any(_leading_dim(v.aval) >= n_rows for v in eqn.invars)
    )


# ---------------------------------------------------------------------------
# pass framework
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Violation:
    """One invariant violation found in a traced fn."""

    pass_name: str
    fn: str          # label of the audited fn (registry name + variant)
    primitive: str   # offending primitive name
    path: str        # nesting trail inside the jaxpr ("<top>" if top-level)
    detail: str      # human-readable explanation with the relevant shapes

    def as_dict(self) -> dict:
        return asdict(self)

    def __str__(self) -> str:  # the CLI's one-line form
        return (
            f"[{self.pass_name}] {self.fn}: {self.primitive} at {self.path}"
            f" — {self.detail}"
        )


class AnalysisPass:
    """Base class: subclasses set ``name`` and implement :meth:`run`.

    ``run(fn_label, jaxpr, arena_rows)`` returns the violations found;
    ``arena_rows`` is the traced state's arena length — the threshold the
    length-sensitive passes compare leading dimensions against (the probe
    geometry keeps it strictly larger than every other buffer, so crossing
    it is unambiguous).
    """

    name: str = "base"

    def run(self, fn: str, jaxpr, arena_rows: int) -> list[Violation]:
        raise NotImplementedError

    def _v(self, fn, eqn, path, detail) -> Violation:
        return Violation(self.name, fn, eqn.primitive.name, _fmt_path(path), detail)


class NoArenaSort(AnalysisPass):
    """No sort/argsort over arena-length operands in delta-path fns.

    The persistent sorted index (PR 4) exists precisely so that membership
    probes and joins never re-sort the arena; the only allowed full argsort
    lives in the explicit rebuild fn (registered with this pass skipped).
    jnp.argsort lowers to the same ``sort`` primitive (keys + iota
    operands), so one check covers both.
    """

    name = "NoArenaSort"

    def run(self, fn, jaxpr, arena_rows):
        out = []
        for eqn, path in jaxpr_walk(jaxpr):
            if eqn.primitive.name != "sort":
                continue
            dims = [_leading_dim(v.aval) for v in eqn.invars]
            if any(d >= arena_rows for d in dims):
                out.append(self._v(
                    fn, eqn, path,
                    f"sort over {max(dims)} rows >= arena ({arena_rows}) — "
                    "hot-path joins must sort binding tables, never the arena",
                ))
        return out


class NoArenaScatter(AnalysisPass):
    """No scatter with arena-length updates/indices in delta-path fns.

    Swept/finalised rows leave the index by stable partition (cumsum +
    binary-searched gather) and fresh rows rank-merge in; a scatter whose
    updates stream reaches arena length would reintroduce the per-round
    arena-proportional write traffic those replaced.  The per-``n_res``
    mask reductions of the DRed wave fns scatter arena-length updates by
    design and register with this pass skipped.
    """

    name = "NoArenaScatter"

    def run(self, fn, jaxpr, arena_rows):
        out = []
        for eqn, path in jaxpr_walk(jaxpr):
            if not eqn.primitive.name.startswith("scatter"):
                continue
            # invars = (operand, scatter_indices, updates): the *stream*
            # side is what must stay delta-width — an arena-sized operand
            # being updated in place (insertion) is fine
            dims = [_leading_dim(v.aval) for v in eqn.invars[1:]]
            if any(d >= arena_rows for d in dims):
                out.append(self._v(
                    fn, eqn, path,
                    f"scatter updates {max(dims)} rows >= arena "
                    f"({arena_rows}) — delta-path scatters must scale with "
                    "the update stream",
                ))
        return out


class DtypeSafety(AnalysisPass):
    """Packed int64 keys must never be truncated to a narrower dtype.

    Packed triple keys need 63 bits (3 x 21-bit IDs); a silent
    ``astype(int32)`` of a pack product corrupts every comparison
    downstream while staying bit-identical on small test IDs — the exact
    class of bug a unit test won't catch.  Implemented as a per-jaxpr
    taint analysis: any int64 ``shift_left`` seeds a taint (the packing
    idiom), taint propagates through value-preserving primitives (or/and,
    select, gather, sort, concatenate, ...), and a ``convert_element_type``
    to a narrower dtype on a tainted value is flagged.  Each sub-jaxpr is
    analysed independently (fresh seeds), so nested packing is covered
    without cross-call dataflow.
    """

    name = "DtypeSafety"

    # primitives through which a packed key flows unchanged in value-width
    _PROPAGATE = frozenset({
        "or", "and", "xor", "add", "sub", "max", "min", "select_n",
        "gather", "slice", "dynamic_slice", "squeeze", "reshape",
        "broadcast_in_dim", "concatenate", "transpose", "rev", "pad",
        "expand_dims", "copy", "clamp", "where",
    })

    def run(self, fn, jaxpr, arena_rows):
        out = []
        self._scan(fn, jaxpr, (), out)
        return out

    def _scan(self, fn, jaxpr, path, out):
        if isinstance(jaxpr, ClosedJaxpr):
            jaxpr = jaxpr.jaxpr
        taint: set = set()

        def tainted(v):
            # literals are never tainted; vars hash by identity
            return not hasattr(v, "val") and v in taint

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "shift_left" and any(
                str(getattr(o.aval, "dtype", "")) == "int64"
                for o in eqn.outvars
            ):
                taint.update(eqn.outvars)
            elif name == "convert_element_type" and any(map(tainted, eqn.invars)):
                src = eqn.invars[0].aval.dtype
                dst = eqn.params.get("new_dtype", src)
                if dst.itemsize < src.itemsize:
                    out.append(Violation(
                        self.name, fn, name, _fmt_path(path),
                        f"packed {src} key truncated to {dst} — 63-bit "
                        "packed triple keys must stay int64 end to end",
                    ))
                else:
                    taint.update(eqn.outvars)
            elif name == "sort" and any(map(tainted, eqn.invars)):
                # operand-wise: the sorted key column stays tainted, the
                # co-sorted iota/index columns do not
                for iv, ov in zip(eqn.invars, eqn.outvars):
                    if tainted(iv):
                        taint.add(ov)
            elif name in self._PROPAGATE and any(map(tainted, eqn.invars)):
                if name == "gather":
                    if tainted(eqn.invars[0]):
                        taint.update(eqn.outvars)
                else:
                    taint.update(eqn.outvars)
            for key, sub in sub_jaxprs(eqn.params):
                self._scan(fn, sub, path + (f"{name}[{key}]",), out)


class NoHostCallback(AnalysisPass):
    """No host callback primitives inside hot compiled fns.

    ``io_callback`` / ``debug_callback`` / ``pure_callback`` force a
    device-to-host round trip per invocation — inside a maintenance round
    fn that multiplies straight into the per-event dispatch floor the
    ROADMAP is trying to kill.  Debug prints left behind in a hot fn are
    the common offender.
    """

    name = "NoHostCallback"

    _CALLBACKS = frozenset({"io_callback", "debug_callback", "pure_callback"})

    def run(self, fn, jaxpr, arena_rows):
        out = []
        for eqn, path in jaxpr_walk(jaxpr):
            if eqn.primitive.name in self._CALLBACKS:
                out.append(self._v(
                    fn, eqn, path,
                    "host callback inside a compiled hot fn — one "
                    "device-to-host round trip per dispatch",
                ))
        return out


ALL_PASSES: tuple[AnalysisPass, ...] = (
    NoArenaSort(),
    NoArenaScatter(),
    DtypeSafety(),
    NoHostCallback(),
)

"""Materialisation statistics mirroring the paper's Table 2 columns."""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from collections import Counter

from jax.profiler import TraceAnnotation


class DispatchCounter:
    """Runtime side of the dispatch auditor (``JaxEngine.dispatches``).

    Every call through the engine's fn cache records one dispatch under
    its *family* (the cache-key head: "plan", "process", "seed_tombs", ...)
    and, when a maintenance generator has tagged the current phase with
    :meth:`in_phase`, under that ``(phase, family)`` pair.  ``in_phase``
    also opens a profiler span ``store.<phase>``, so a device trace shows
    which phase launched each program.  First-time
    cache fills are tallied separately in ``compiles`` so steady-state
    dispatch rates can be read net of compilation.  The static half lives
    in :func:`repro.core.incremental_spmd.static_dispatch_profile`;
    :func:`repro.analysis.dispatch_crosscheck` reconciles the two.

    **Thread safety** (the serving tier runs maintenance on a worker thread
    while reader threads dispatch batched query fns): ``phase`` is
    *thread-local* — the maintenance generators' tags can never leak onto a
    concurrent reader's ``"query"`` dispatches or vice versa — and the
    counter increments take a lock so totals stay exact under concurrency
    (a bare ``Counter[k] += 1`` is a read-modify-write that can drop
    increments between threads).
    """

    def __init__(self) -> None:
        self.by_family: Counter = Counter()
        self.by_phase: Counter = Counter()   # keyed (phase, family)
        self.compiles: Counter = Counter()   # first-time cache fills
        self._phase = threading.local()      # set by the phase generators
        self._lock = threading.Lock()

    @property
    def phase(self) -> str | None:
        return getattr(self._phase, "value", None)

    @phase.setter
    def phase(self, value: str | None) -> None:
        self._phase.value = value

    @contextlib.contextmanager
    def in_phase(self, phase: str):
        """Tag this thread's dispatches with ``phase`` inside the block, in
        a ``jax.profiler.TraceAnnotation`` named ``store.<phase>``; the
        previous tag comes back on exit.  Inside a generator the tag and
        the span stay open across its ``yield``s."""
        prev = self.phase
        self.phase = phase
        try:
            with TraceAnnotation(f"store.{phase}"):
                yield
        finally:
            self.phase = prev

    @property
    def total(self) -> int:
        return sum(self.by_family.values())

    def record(self, family: str) -> None:
        with self._lock:
            self.by_family[family] += 1
            self.by_phase[(self.phase, family)] += 1

    def record_compile(self, family: str) -> None:
        with self._lock:
            self.compiles[family] += 1

    def snapshot(self) -> dict:
        """Immutable totals for delta-ing around a timed region."""
        return {
            "by_family": dict(self.by_family),
            "total": self.total,
        }

    def reset(self) -> None:
        self.by_family.clear()
        self.by_phase.clear()
        self.compiles.clear()


@dataclasses.dataclass
class MatStats:
    """Counters collected during materialisation.

    ``derivations`` counts (rule, substitution) pairs that produce a head fact
    (duplicates included) — the paper's 'Derivations' column.  ``rule_applications``
    counts (rule, body-position, delta-fact) partial instantiations attempted —
    the paper's 'Rule appl.' column.  ``triples_total`` / ``triples_unmarked``
    mirror 'Triples after (total / unmarked)'.
    """

    mode: str = "REW"
    derivations: int = 0
    rule_applications: int = 0
    merged_resources: int = 0
    sameas_pairs: int = 0
    reflexive_added: int = 0
    rounds: int = 0
    rule_rewrites: int = 0          # how many times P' := rho(P) changed P'
    rules_requeued: int = 0         # rules placed on the R queue analogue
    od_waves: int = 0               # overdelete waves (incremental deletes)
    index_rebuilds: int = 0         # full argsorts of the arena index (<=1/epoch)
    overdeleted: int = 0            # rows tombstoned across deletes
    suspects_split: int = 0         # sameAs cliques split + re-merged
    rederive_targeted: int = 0      # delete-side rules evaluated head-bound
    rederive_full_fallback: int = 0 # delete-side whole-rule requeues (const heads)
    rederive_seed_rows: int = 0     # overdeleted head instances joined backward
    rederive_join_width: int = 0    # widest padded rederive seed table
    full_plan_evals: int = 0        # unconstrained full-plan rule evaluations
    remerge_targeted: int = 0       # forward-side rules evaluated merge-anchored
    remerge_full_fallback: int = 0  # forward-side whole-rule requeues (ground atoms)
    delta_mask_fallbacks: int = 0   # delta windows that overflowed to all-True masks
    index_joins: int = 0            # join steps run as index range scans
    arena_probe_joins: int = 0      # join steps run as the generic arena probe
    capacity_retries: int = 0       # mid-operation rollback+grow restarts
    wide_growth_restarts: int = 0   # retries that grew a wide (base-run) cap
    triples_total: int = 0          # arena rows used (marked + unmarked)
    triples_unmarked: int = 0
    triples_explicit: int = 0
    wall_seconds: float = 0.0
    contradiction: bool = False
    memory_bytes: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def factor_over(self, other: "MatStats") -> dict:
        """Ratios AX/REW as in the paper's 'factor' rows."""

        def ratio(a, b):
            return float(a) / float(b) if b else float("inf")

        return {
            "triples": ratio(other.triples_unmarked, self.triples_unmarked),
            "rule_applications": ratio(other.rule_applications, self.rule_applications),
            "derivations": ratio(other.derivations, self.derivations),
            "time": ratio(other.wall_seconds, self.wall_seconds),
        }

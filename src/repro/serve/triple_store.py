"""Live SPARQL serving over incremental maintenance (epoch-snapshot reads).

The paper's payoff is that rewriting keeps the materialisation small enough
to *query* quickly; this module is where that payoff is served.  A
:class:`TripleStore` owns a device-resident materialised
:class:`~repro.core.engine_jax.EngineState` and admits two workloads against
it: add/delete batches (maintained through the sharded incremental rounds of
:mod:`repro.core.incremental_spmd`) and SPARQL queries (answered against
published snapshots — batched on device by
:mod:`repro.sparql.batched`, scalar on host by
:mod:`repro.sparql.executor`).

**Epoch-snapshot consistency** (the serving contract, docs/serving.md):
every query is answered against the fixpoint of some *completed* maintenance
epoch — never a mid-round state where tombstoned facts await rederivation or
a clique split is half-applied — and its answers are expanded through that
epoch's rho (the paper's rewriting contract: match over representatives,
expand answers to cliques).  Concretely:

  * maintenance operations advance through the resumable *phases* of
    :func:`~repro.core.incremental_spmd.spmd_add_phases` /
    :func:`~repro.core.incremental_spmd.spmd_delete_phases`
    (adds: ``prepared``; deletes: ``seeded`` / ``wave``... /
    ``overdeleted`` / ``split`` / ``rederive``);
  * a :class:`~repro.core.engine_jax.StoreSnapshot` is published eagerly at
    every epoch barrier (:meth:`~repro.core.engine_jax.JaxEngine.publish_snapshot`):
    device-resident, double-buffered — publication is a reference swap plus
    an incremental :meth:`~repro.core.uf.FrozenRho.refreshed` rho refresh,
    and the build cost is charged to the barrier, never to the first read;
  * queries — whenever admitted, including between an overdelete wave and
    its rederivation — read the *published* snapshot, whose
    :class:`~repro.core.uf.FrozenRho` caches the clique expansion tables
    across all of the epoch's queries;
  * each answer carries ``epoch`` so callers (and the differential test
    harness in tests/test_serve_triple_store.py) can hold the store to the
    oracle: answer == evaluating the same query over the from-scratch
    materialisation of the explicit set as of that epoch.

**Two schedulers.**  The default (``threaded=False``) is the cooperative
deterministic loop — ``step()`` drains queued reads against the published
snapshot, then advances the in-flight update by exactly one phase — so
tests can construct any interleaving of queries racing maintenance rounds
and replay it exactly.  With ``threaded=True`` maintenance runs on a
:class:`~repro.serve.scheduler.MaintenanceWorker` thread instead:
admission and reads never block on maintenance (reads touch only the
published snapshot; the swap at the barrier is atomic), which is what the
epoch-snapshot discipline was buying all along — the cooperative mode
remains as the differential/test scheduler.  :class:`CapacityError`
retries (either mode) roll the state back to the pre-update snapshot, grow
the exhausted buffer, and restart the update's phases; readers keep being
served from the published snapshot throughout, so retries are invisible to
them.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import Counter, deque
from dataclasses import dataclass

import numpy as np

from repro.compat import enable_x64
from repro.core.engine_jax import (
    CapacityError,
    EngineState,
    JaxEngine,
    StoreSnapshot,
)
from repro.core.incremental_spmd import spmd_add_phases, spmd_delete_phases
from repro.core.rules import Program
from repro.sparql.algebra import Query
from repro.sparql.batched import BatchedExecutor
from repro.sparql.executor import evaluate_at

from .scheduler import MaintenanceWorker

__all__ = ["TripleStore", "UpdateTicket", "QueryTicket"]


@dataclass
class UpdateTicket:
    """An admitted add/delete batch.

    ``epoch`` is assigned at the epoch barrier: the first snapshot whose
    fixpoint includes this batch.  ``wall_s`` is admission-to-barrier
    latency (in cooperative mode it includes any reads interleaved between
    the phases).  ``publish_ms`` is the snapshot publication cost paid at
    this ticket's barrier — reported separately so query latency columns
    measure queries (the BENCH_serve attribution fix).
    """

    uid: int
    op: str  # "add" | "delete"
    delta: np.ndarray
    status: str = "queued"  # queued | running | done | failed
    epoch: int | None = None
    wall_s: float = 0.0
    publish_ms: float = 0.0


@dataclass
class QueryTicket:
    """An admitted SPARQL query; ``epoch`` is the completed maintenance
    epoch whose snapshot the ``answer`` bag was evaluated against."""

    uid: int
    query: Query
    status: str = "queued"  # queued | done
    epoch: int | None = None
    answer: Counter | None = None
    wall_s: float = 0.0


class TripleStore:
    """A standing triple store serving SPARQL against a mutating store.

    Parameters
    ----------
    facts, program, dic:
        The explicit fact set, Datalog+sameAs program and dictionary —
        materialised to the base fixpoint (epoch 0) at construction.
    engine:
        A :class:`~repro.core.engine_jax.JaxEngine` (single-device or SPMD).
        When omitted one is sized to the workload the way bench_incremental
        does (~4x the explicit set, targeted retry growth absorbing
        misestimates).
    threaded:
        False (default): cooperative deterministic scheduler
        (``step``/``drain`` on the caller's thread).  True: maintenance
        runs on a background :class:`~repro.serve.scheduler.MaintenanceWorker`;
        ``step()`` is disabled, ``drain()`` waits for the worker while
        answering queued reads, and admission/reads never block on
        maintenance.
    batch_queries:
        Drain queued queries through the vmapped batched executor
        (:class:`repro.sparql.batched.BatchedExecutor`) when the published
        snapshot is device-resident; ``False`` forces the scalar host path
        (the differential baseline).  ``query_width`` / ``min_batch`` are
        the executor's knobs.

    The public surface is ``submit_update`` / ``submit_query`` /
    ``query_now`` (admission), ``step`` / ``drain`` (the scheduler),
    ``snapshot`` / ``epoch`` (the published read view) and ``close`` (stop
    the worker; also a context manager).
    """

    def __init__(
        self,
        facts: np.ndarray,
        program: Program,
        dic,
        engine: JaxEngine | None = None,
        max_rounds: int = 10_000,
        threaded: bool = False,
        batch_queries: bool = True,
        query_width: int = 4096,
        min_batch: int = 2,
        **engine_kw,
    ) -> None:
        facts = np.asarray(facts, np.int32).reshape(-1, 3)
        if engine is not None and engine_kw:
            raise TypeError(
                "engine_kw only applies when the store builds its own "
                f"engine; got an explicit engine AND {sorted(engine_kw)}"
            )
        if engine is None:
            cap = 1 << max(12, int(np.ceil(np.log2(max(4 * facts.shape[0], 2)))))
            kw = dict(
                capacity=cap, bind_cap=cap // 2, out_cap=cap // 2,
                rewrite_cap=cap // 4, seed_chunk=2048,
            )
            kw.update(engine_kw)
            engine = JaxEngine(dic.n_resources, **kw)
        self.engine = engine
        self.dic = dic
        self.max_rounds = max_rounds
        self.state: EngineState = engine.materialise_state(
            facts, program, max_rounds
        )
        self.inflight_phase: str | None = None
        self._uids = itertools.count()
        # deques: admission appends right, the scheduler pops left — O(1)
        # at both ends (the old list.pop(0) drain was O(n^2) per burst)
        self._uqueue: deque[UpdateTicket] = deque()
        self._qqueue: deque[QueryTicket] = deque()
        self._inflight: UpdateTicket | None = None
        self._gen = None
        self._snap: dict | None = None
        self._t_start = 0.0
        # one lock guards admission/queues/pending; the condition on it is
        # the worker's wakeup.  Published-snapshot reads are lock-free
        # (atomic reference load); publication swaps the reference at the
        # barrier.
        self._lock = threading.RLock()
        self._work = threading.Condition(self._lock)
        self._batched = (
            BatchedExecutor(engine, width=query_width, min_batch=min_batch)
            if batch_queries else None
        )
        self.publish_ms: list[float] = []
        self._published: StoreSnapshot = self._publish()
        self.threaded = bool(threaded)
        self._worker = MaintenanceWorker(self) if threaded else None

    # -- read view -----------------------------------------------------------
    @property
    def epoch(self) -> int:
        """The published (last completed) maintenance epoch."""
        return self._published.epoch

    @property
    def snapshot(self) -> StoreSnapshot:
        """The published read view — eagerly built at each epoch barrier.

        Between updates it is the live state's fixpoint; while an update is
        mid-phase it is still the *previous* barrier's snapshot — NEVER a
        view of the live mid-round arrays.  Safe to read from any thread:
        publication replaces the reference, it never mutates a snapshot.
        """
        return self._published

    @property
    def inflight(self) -> UpdateTicket | None:
        return self._inflight

    @property
    def dispatch_counts(self) -> dict:
        """Runtime compiled-call dispatch totals of the serving engine.

        ``by_phase`` attributes dispatches to the maintenance phase that
        issued them (the generators tag ``engine.dispatches``; scheduler
        retries restart the generator, so retried phases count twice — the
        real cost).  Snapshot publication dispatches under ``"publish"``
        and batched query execution under ``"query"``.  The static half
        lives in :func:`repro.core.incremental_spmd.static_dispatch_profile`.
        """
        d = self.engine.dispatches
        return {
            "total": d.total,
            "by_family": dict(d.by_family),
            "by_phase": {
                f"{ph}/{fam}": n
                for (ph, fam), n in d.by_phase.items()
                if ph is not None
            },
            "compiles_by_family": dict(d.compiles),
        }

    @property
    def query_stats(self) -> dict:
        """The batched executor's counters (``batched`` / ``fallback`` /
        ``overflow`` / ``groups``); empty when batching is off."""
        return dict(self._batched.stats) if self._batched is not None else {}

    def audit(self) -> list[str]:
        """Cross-check this store's observed dispatches against the static
        per-phase profile (the serving half of ``repro.analysis``'s
        DispatchAuditor).  Returns problem strings; empty means every
        (phase, family) dispatch pair was declared."""
        from repro.analysis import dispatch_crosscheck  # lazy: serving core

        return dispatch_crosscheck(
            self.engine.dispatches, self.state.base_program
        )

    def pending(self) -> int:
        """Queued + in-flight work items (0 means ``drain`` would be a no-op).

        Safe to call concurrently with the worker thread: the queues are
        read under the admission lock, and an update the worker has popped
        but not finished still counts via the worker's busy flag.
        """
        with self._lock:
            n = len(self._uqueue) + len(self._qqueue)
            busy = self._worker is not None and self._worker.busy
            if self._inflight is not None or busy:
                n += 1
            return n

    # -- admission -----------------------------------------------------------
    def submit_update(self, op: str, delta) -> UpdateTicket:
        if op == "del":
            op = "delete"
        if op not in ("add", "delete"):
            raise ValueError(f"unknown update op {op!r}")
        t = UpdateTicket(
            next(self._uids), op, np.asarray(delta, np.int32).reshape(-1, 3)
        )
        with self._work:
            self._uqueue.append(t)
            self._work.notify()
        return t

    def submit_query(self, q: Query) -> QueryTicket:
        t = QueryTicket(next(self._uids), q)
        with self._lock:
            self._qqueue.append(t)
        return t

    def query_now(self, q: Query) -> QueryTicket:
        """Admit and answer immediately against the published snapshot.

        Safe at any point — including while an update is mid-phase on the
        worker thread — because reads never touch the live state.
        """
        t = self.submit_query(q)
        self._drain_queries()
        return t

    # -- scheduler -----------------------------------------------------------
    def step(self) -> bool:
        """One cooperative scheduler tick: answer queued reads at the
        published snapshot, then advance the in-flight maintenance operation
        by one phase (admitting the next queued update if none is in
        flight).  Returns True iff any work was done.  Disabled in threaded
        mode — the worker owns maintenance there."""
        if self.threaded:
            raise RuntimeError(
                "step() is the cooperative scheduler; this store runs "
                "threaded=True — use drain() / query_now()"
            )
        progressed = bool(self._qqueue)
        self._drain_queries()
        if self._inflight is None and self._uqueue:
            with self._lock:
                t = self._uqueue.popleft()
            self._begin(t)
        if self._inflight is not None:
            self._advance()
            progressed = True
        return progressed

    def drain(self, max_ticks: int = 100_000) -> "TripleStore":
        """Run until all queues are empty and no update is in flight; the
        published snapshot is then the newest epoch's.  Cooperative mode
        ticks the scheduler; threaded mode answers queued reads on THIS
        thread while waiting for the worker to reach its barrier(s), and
        re-raises any exception a background update died with."""
        if self.threaded:
            ticks = 0
            while True:
                self._drain_queries()
                self._worker.check()
                if self._worker.wait_idle(timeout=0.05):
                    self._drain_queries()
                    self._worker.check()
                    if not self.pending():
                        return self
                ticks += 1
                if ticks > max_ticks:
                    raise RuntimeError("drain did not converge")
        ticks = 0
        while self.pending():
            self.step()
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError("drain did not converge")
        return self

    def close(self) -> None:
        """Stop the worker thread (threaded mode); idempotent."""
        if self._worker is not None:
            self._worker.stop()
            self._worker.check()
            self._worker = None
            self.threaded = False

    def __enter__(self) -> "TripleStore":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- internals -----------------------------------------------------------
    def _publish(self) -> StoreSnapshot:
        """Publish the current barrier's snapshot (timed, double-buffered).

        The host ``triples`` copy is materialised here too: scalar-fallback
        readers (non-batchable shapes, singleton drains) must not pay a
        lazy device->host copy on the first read after a barrier — ALL
        snapshot build cost belongs to the barrier (``publish_ms``), on
        every query path.
        """
        t0 = time.perf_counter()
        snap = self.engine.publish_snapshot(
            self.state, prev=getattr(self, "_published", None)
        )
        snap.triples  # noqa: B018  — eager host copy, charged to the barrier
        snap.rho.members, snap.rho.sizes, snap.rho._csr()  # expansion tables too
        ms = (time.perf_counter() - t0) * 1e3
        self.publish_ms.append(ms)
        return snap

    def _drain_queries(self) -> None:
        """Answer every queued query against one consistent snapshot.

        Grabs the whole queue in one locked pop, then evaluates the batch
        — vmapped by shape groups when the snapshot is device-resident —
        entirely outside the lock.  Concurrent callers pop disjoint
        batches, so this is safe from any thread.
        """
        while True:
            with self._lock:
                batch = list(self._qqueue)
                self._qqueue.clear()
            if not batch:
                return
            snap = self.snapshot
            if self._batched is not None:
                t0 = time.perf_counter()
                res = self._batched.run(
                    [t.query for t in batch], snap, self.dic
                )
                per = (time.perf_counter() - t0) / len(batch)
                for t, (ans, ep) in zip(batch, res):
                    t.answer, t.epoch = ans, ep
                    t.wall_s, t.status = per, "done"
            else:
                for t in batch:
                    t0 = time.perf_counter()
                    t.answer, t.epoch = evaluate_at(t.query, snap, self.dic)
                    t.wall_s = time.perf_counter() - t0
                    t.status = "done"

    def _run_one_update(self, t: UpdateTicket) -> None:
        """Begin an admitted update and advance it to its epoch barrier —
        the worker thread's unit of work (threaded mode only).

        A failed update must not wedge the scheduler: the state rolls back
        to the pre-update snapshot (readers were on the published snapshot
        all along, so nothing they saw ever included the aborted work) and
        the in-flight slot clears before the exception is parked for the
        caller's ``drain()``.
        """
        try:
            self._begin(t)
            while self._inflight is not None:
                self._advance()
        except BaseException:
            if self._snap is not None:
                self.engine._restore(self.state, self._snap)
            self._inflight, self._gen, self._snap = None, None, None
            self.inflight_phase = None
            raise

    def _make_gen(self, t: UpdateTicket):
        fn = spmd_add_phases if t.op == "add" else spmd_delete_phases
        return fn(self.engine, self.state, t.delta, self.max_rounds)

    def _begin(self, t: UpdateTicket) -> None:
        self._inflight = t
        t.status = "running"
        self._t_start = time.perf_counter()
        self.engine._maybe_reset_fallback(self.state)
        self._snap = self.engine._snapshot(self.state)
        self._gen = self._make_gen(t)
        self.inflight_phase = "admitted"

    def _advance(self) -> None:
        """Advance the in-flight operation by one phase, with capacity retry.

        On :class:`CapacityError` the state rolls back to the pre-update
        snapshot, exactly the exhausted capacity doubles (arena re-layout if
        the store itself grew), and the operation restarts from its first
        phase in the same tick — the published snapshot, and hence every
        reader, is unaffected.

        ``stats.wall_seconds`` accumulates only the time spent in here
        (maintenance phases + retries), matching its meaning on the direct
        engine API — reads interleaved between phases are not charged.
        """
        eng = self.engine
        t0 = time.perf_counter()
        try:
            while True:
                try:
                    eng._set_update_buffers(True)
                    with enable_x64():
                        self.inflight_phase = next(self._gen)
                    return
                except StopIteration:
                    self._finish()
                    return
                except CapacityError as e:
                    eng._recover_capacity(self.state, self._snap, e)
                    self._snap = eng._snapshot(self.state)
                    self._gen = self._make_gen(self._inflight)
                    self.inflight_phase = "admitted"
        finally:
            self.state.stats.wall_seconds += time.perf_counter() - t0

    def _finish(self) -> None:
        """Cross the epoch barrier and publish the new epoch's snapshot.

        Publication happens HERE, eagerly — a buffer swap visible to
        readers the moment the barrier completes — so the build cost lands
        on the update that caused it (``ticket.publish_ms``), never on the
        first unlucky read (the BENCH_serve ``busy_over_idle`` attribution
        fix).
        """
        t = self._inflight
        self.engine._barrier(self.state)
        self._published = self._publish()
        t.publish_ms = self.publish_ms[-1]
        t.epoch = self.state.update_epoch
        t.status = "done"
        t.wall_s = time.perf_counter() - self._t_start
        self._inflight, self._gen, self._snap = None, None, None
        self.inflight_phase = None

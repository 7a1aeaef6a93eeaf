"""The two clients of a run: closed-loop ingest and open-loop lookups.

Both drive only a store's public surface, so the same code drives the
system under test and the control.  Each call into the store sits in a
``jax.profiler.TraceAnnotation`` span named ``bench.<what>``, which the
trace reduction uses to label the device's idle gaps.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import numpy as np
from jax.profiler import TraceAnnotation

POLL_S = 0.002  # how often the ingest client looks for its publication


@dataclass
class Update:
    op: str
    rows: int
    submitted: float          # perf_counter seconds
    published: float | None   # when the client saw the new epoch, or None
    epoch: int | None
    status: str
    view: tuple | None = None  # (epoch, host rows, rho) published by it
    counters: dict | None = None  # the store's counters once it was seen


@dataclass
class Answer:
    index: int                # into the run's lookups
    due: float                # perf_counter seconds
    submitted: float
    answered: float | None
    epoch: int | None = None
    bag: object = None
    acked_epoch: int = 0      # newest epoch acknowledged when submitted


@dataclass
class Ingest:
    """Closed loop, one client: the next batch is sent once the previous
    one is published."""

    store: object
    view: callable            # store -> (epoch, rows, rho)
    probe: callable           # store -> counters
    updates: list = field(default_factory=list)
    acked_epoch: int = 0

    def apply(self, op: str, rows: np.ndarray, span: str = "bench.update") -> Update:
        with TraceAnnotation(span):
            t0 = time.perf_counter()
            ticket = self.store.submit_update(op, rows)
            with TraceAnnotation("bench.wait_publish"):
                while ticket.status not in ("done", "failed"):
                    time.sleep(POLL_S)
            t1 = time.perf_counter()
        u = Update(op, int(rows.shape[0]), t0, t1, ticket.epoch, ticket.status)
        if ticket.status == "done":
            u.view = self.view(self.store)
            u.counters = self.probe(self.store)
            self.acked_epoch = max(self.acked_epoch, ticket.epoch)
        self.updates.append(u)
        return u

    def run(self, events, until: float) -> None:
        """Send ``events`` in order while the clock is before ``until``;
        the update in flight at ``until`` is waited for."""
        for op, rows in events:
            if time.perf_counter() >= until:
                return
            if self.apply(op, rows).status != "done":
                return


class Lookups(threading.Thread):
    """Open loop: lookups are sent when due, whatever the store is doing.

    At each wake-up the client sends every lookup that is due and answers
    them as one drain of the store's queue (``query_now`` on the last one),
    the way a reader of the published snapshot serves a burst.  A lookup's
    latency runs from when it was due to when its drain returned.
    """

    def __init__(self, store, queries, due_s, start: float,
                 ingest: Ingest) -> None:
        super().__init__(name="bench-lookups", daemon=True)
        self.store, self.queries, self.ingest = store, queries, ingest
        self.due = start + np.asarray(due_s, np.float64)
        self.answers: list[Answer] = []
        self.error: BaseException | None = None

    def run(self) -> None:
        try:
            self._loop()
        except BaseException as e:  # surfaced by the caller after join
            self.error = e

    def _loop(self) -> None:
        i, n = 0, len(self.queries)
        while i < n:
            now = time.perf_counter()
            if now < self.due[i]:
                time.sleep(self.due[i] - now)
                now = time.perf_counter()
            j = int(np.searchsorted(self.due, now, side="right"))
            j = max(j, i + 1)
            acked = self.ingest.acked_epoch
            with TraceAnnotation("bench.lookup_submit"):
                tickets = [self.store.submit_query(q) for q in self.queries[i:j - 1]]
            with TraceAnnotation("bench.lookup_answer"):
                tickets.append(self.store.query_now(self.queries[j - 1]))
            done = time.perf_counter()
            for k, t in zip(range(i, j), tickets):
                ok = t.status == "done"
                self.answers.append(Answer(
                    k, float(self.due[k]), now, done if ok else None,
                    t.epoch, t.answer, acked))
            i = j


def warm_lookups(store, batches) -> None:
    """Answer each batch as one drain, so every batch shape compiles."""
    for batch in batches:
        with TraceAnnotation("bench.warm_lookups"):
            for q in batch[:-1]:
                store.submit_query(q)
            store.query_now(batch[-1])


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))

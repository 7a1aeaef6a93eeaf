"""The control: the reference in the store's place, one guarantee broken.

The configurations state epoch-snapshot consistency: an acknowledged update
is visible in every later snapshot.  The tempting way to cut update latency
is to acknowledge a batch before its snapshot is rebuilt.  The control does
just that: ``StaleStore`` acknowledges update ``k`` as epoch ``k`` but keeps
serving the store of epoch ``k - 1`` until the next update comes, and it
answers lookups from that store.  Run through the same clients and the same
check as the system under test, it has to come out not correct
(``python3 perfbench/seeds.py --control ...``).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from perfbench import reference


@dataclass
class _Ticket:
    status: str = "done"
    epoch: int | None = None
    answer: object = None


class StaleStore:
    """The reference store, published one epoch late."""

    def __init__(self, graph) -> None:
        self.graph = graph
        self.explicit = np.unique(reference.pack(graph.facts))
        self.latest = self._epoch()
        self.served = self.latest
        self.epoch = 0
        self._queue: list = []

    def _epoch(self) -> reference.Epoch:
        return reference.Epoch(self.explicit, self.graph.rules, len(self.graph.names))

    def submit_update(self, op: str, rows) -> _Ticket:
        self.explicit = reference.apply_op(self.explicit, op, rows)
        self.served, self.latest = self.latest, self._epoch()
        self.epoch += 1
        return _Ticket(epoch=self.epoch)

    def submit_query(self, q) -> _Ticket:
        t = _Ticket(status="queued")
        self._queue.append((q, t))
        return t

    def query_now(self, q) -> _Ticket:
        t = self.submit_query(q)
        queue, self._queue = self._queue, []
        for query, ticket in queue:
            ticket.answer = self.served.answer(query, self.graph.names.names)
            ticket.epoch, ticket.status = self.epoch, "done"
        return t

    def drain(self) -> "StaleStore":
        return self

    def close(self) -> None:
        pass


class Control:
    """The system protocol of ``run.run_cell``, served by ``StaleStore``."""

    @staticmethod
    def make_store(graph, engine_kw) -> StaleStore:
        return StaleStore(graph)

    @staticmethod
    def to_query(q):
        return q

    @staticmethod
    def counters(store) -> dict:
        return {"publishes": store.epoch, "publish_ms": 0.0, "dispatches": 0,
                "query_dispatches": 0, "engine_compiles": 0, "query_stats": {},
                "capacity_retries": 0}

    @staticmethod
    def snapshot_view(store):
        return store.epoch, store.served.rows, store.served.rep

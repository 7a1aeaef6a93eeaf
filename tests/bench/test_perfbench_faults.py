"""``correct`` comes out false for the control and for each fault the
cells can have, planted underneath a run that is otherwise whole."""

from __future__ import annotations

from collections import Counter

from bench_helpers import tiny_run
from perfbench.control import Control

LOOKUPS = "opencyc_x8.lookups_under_updates"


def _failed(res, *names):
    assert not res["correct"]
    assert any(res["checks"][n]["value"] > res["checks"][n]["limit"] for n in names)


def test_control_serving_one_epoch_late_is_not_correct():
    res = tiny_run(LOOKUPS, system=Control)
    _failed(res, "epochs_rows_differ", "epochs_rho_differ")


def test_an_update_that_leaves_the_state_unchanged_is_caught(monkeypatch):
    import repro.serve.triple_store as ts

    monkeypatch.setattr(ts, "spmd_add_phases", lambda *a, **k: iter(()))
    monkeypatch.setattr(ts, "spmd_delete_phases", lambda *a, **k: iter(()))
    _failed(tiny_run(LOOKUPS), "epochs_rows_differ")


def test_half_of_each_batch_left_out_is_caught(monkeypatch):
    from repro.serve.triple_store import TripleStore

    submit = TripleStore.submit_update

    def half(self, op, delta):
        return submit(self, op, delta[: max(len(delta) // 2, 1)])

    monkeypatch.setattr(TripleStore, "submit_update", half)
    _failed(tiny_run(LOOKUPS), "epochs_rows_differ")


def test_an_answer_altered_where_it_is_produced_is_caught(monkeypatch):
    from repro.sparql.batched import BatchedExecutor

    answer = BatchedExecutor.run

    def altered(self, queries, snapshot, dic):
        out = answer(self, queries, snapshot, dic)
        bag, epoch = out[0]
        out[0] = (Counter(bag) + Counter({(":altered",): 1}), epoch)
        return out

    monkeypatch.setattr(BatchedExecutor, "run", altered)
    _failed(tiny_run(LOOKUPS), "answers_wrong")

#!/usr/bin/env python3
"""Smoke run of the sameAs store on a TPU, checked against the numpy reference.

    python chip_smoke.py              # one chip: kernels, then the serving store
    python chip_smoke.py --chips 4    # only the sharded engine on a 4-chip mesh

The data is the ``opencyc_like`` profile (equality-dense: cliques of 8
duplicates found through an inverse-functional ``:idProp`` rule, a 3-level
class hierarchy) with ``n_groups`` and ``n_plain`` multiplied by ``SCALE``
= 8 (84,000 explicit facts over about 47k resources).  The scale is cut
from 128 (about 1.34M facts) so that a cold run, compiles included, ends
well within 20 minutes on one v5e: at x128 the base materialisation alone
took over 18 minutes.  On it the one-chip run

  1. runs the ``dedup_order`` and ``rewrite_triples`` Pallas kernels compiled
     for the chip (``interpret=False``) against ``repro.kernels.ref``;
  2. builds a :class:`~repro.serve.triple_store.TripleStore` (base
     materialisation), applies an 8-event add/delete stream, and at every
     epoch answers 8 point-lookup queries through the batched executor;
  3. holds every epoch's store and rho, and every answer bag, to a
     from-scratch :func:`~repro.core.materialise.materialise_rew` plus
     :func:`~repro.sparql.executor.evaluate`.

``--chips 4`` runs step 2, at the same scale and without queries, on
``JaxEngine(mesh=...)`` over four chips and checks each epoch the same way.
Any mismatch, or a row the store holds twice, raises.  Timings and counters
printed on the way are information, not metrics.  The last line of standard
output is ``{"ok": true, "device": {...}}``; without a TPU the script exits
non-zero before doing any work.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

PROFILE = "opencyc_like"
SCALE = 8  # multiplier of the profile's n_groups and n_plain
N_EVENTS = 8
BATCH = 24
SPOKE_QUERIES = 7  # per epoch, plus one class-membership query


def log(msg: str) -> None:
    print(msg, flush=True)


# seconds the backend spent compiling, summed over every program
compile_s = 0.0


def _on_duration(event: str, secs: float, **_) -> None:
    global compile_s
    if event == "/jax/core/compile/backend_compile_duration":
        compile_s += secs


def make_workload(scale: float, seed: int):
    """The scaled profile, its update stream and the IDs the queries use."""
    from repro.data.generator import PROFILES, generate, sample_update_stream

    kw = dict(PROFILES[PROFILE])
    kw["n_groups"] = max(int(kw["n_groups"] * scale), 2)
    kw["n_plain"] = max(int(kw["n_plain"] * scale), 4)
    facts, program, dic = generate(**kw, seed=seed)
    # sampled before the store is built: the stream interns fresh :idProp
    # values, and the engine's resource count must cover them
    events = sample_update_stream(
        facts, dic, n_events=N_EVENTS, batch=BATCH, seed=seed
    )
    return facts, program, dic, events, kw


class Reference:
    """From-scratch REW materialisation of each epoch's explicit set."""

    def __init__(self, facts, program, dic):
        self.program, self.dic = program, dic
        self.explicit = np.asarray(facts, np.int32)
        self.mat = None
        self.seconds = 0.0
        self._refresh()

    def _refresh(self) -> None:
        from repro.core.materialise import materialise_rew

        t0 = time.perf_counter()
        self.mat = materialise_rew(
            self.explicit, self.program, self.dic.n_resources
        )
        self.seconds += time.perf_counter() - t0

    def apply(self, op: str, delta) -> None:
        from repro.core.triples import apply_op

        self.explicit = apply_op(self.explicit, op, delta)
        self._refresh()

    def answer(self, q):
        from repro.sparql.executor import evaluate

        return evaluate(q, self.mat.triples(), self.mat.rep, self.dic)


def _sorted_keys(spo, who: str, epoch: int) -> np.ndarray:
    """Sorted packed keys of the rows; a row held twice raises."""
    from repro.core.triples import pack

    keys = np.sort(pack(np.asarray(spo, np.int32).reshape(-1, 3)))
    n_dup = int((keys[1:] == keys[:-1]).sum())
    if n_dup:
        raise AssertionError(f"epoch {epoch}: {who} holds {n_dup} duplicate rows")
    return keys


def check_epoch(epoch: int, triples, rep, ref: Reference) -> None:
    """The epoch's normal-form store equals the reference's row for row
    (no row twice), and so does its rho."""
    want = ref.mat
    got_keys = _sorted_keys(triples, "store", epoch)
    want_keys = _sorted_keys(want.triples(), "reference", epoch)
    if not np.array_equal(got_keys, want_keys):
        raise AssertionError(
            f"epoch {epoch}: store has {got_keys.shape[0]} rows, reference "
            f"{want_keys.shape[0]}, or they differ"
        )
    rep = np.asarray(rep)[: want.rep.shape[0]]
    if not np.array_equal(rep, want.rep):
        raise AssertionError(f"epoch {epoch}: rho differs from the reference")


def splits_a_clique(op: str, delta, ref: Reference) -> bool:
    """Whether a delete removes an ``:idProp`` edge of a clique member."""
    if op != "delete":
        return False
    id_prop = ref.dic.intern(":idProp")
    subj = np.asarray(delta)[np.asarray(delta)[:, 1] == id_prop, 0]
    rep = ref.mat.rep
    sizes = np.bincount(rep, minlength=rep.shape[0])
    return bool((sizes[rep[subj]] > 1).any())


def epoch_queries(rng, dic, members: np.ndarray, n_classes: int):
    """Point lookups: the spokes of a random clique member (rewritten to
    its representative), and the members of one class."""
    from repro.sparql.algebra import Query

    spoke, rdf_type = dic.intern(":spoke"), dic.intern("rdf:type")
    qs = [
        Query([(-1, spoke, int(c))], [], [-1], False)
        for c in rng.choice(members, size=SPOKE_QUERIES, replace=False)
    ]
    cls = dic.intern(f":C{int(rng.integers(n_classes))}")
    qs.append(Query([(-1, rdf_type, cls)], [], [-1], False))
    return qs


def run_kernels(interpret: bool, n_keys: int, n_triples: int,
                n_resources: int, seed: int) -> None:
    """``dedup_order`` and ``rewrite_triples`` against the jnp references."""
    import jax.numpy as jnp

    from repro.compat import enable_x64
    from repro.kernels import dedup, ref, rewrite_triples

    rng = np.random.default_rng(seed)
    with enable_x64():
        # packed-key magnitudes with many duplicates (stability matters)
        keys = rng.integers(0, 1 << 62, n_keys // 4, dtype=np.int64)
        keys = keys[rng.integers(0, keys.shape[0], n_keys)]
        t0 = time.perf_counter()
        order = np.asarray(dedup.dedup_order(jnp.asarray(keys), interpret=interpret))
        dt = time.perf_counter() - t0
        if not np.array_equal(order, np.asarray(ref.dedup_order_ref(keys))):
            raise AssertionError("dedup_order differs from dedup_order_ref")
    log(f"kernel dedup_order: {n_keys} keys, interpret={interpret}, "
        f"matches ref, first call {dt:.3f}s")

    rho = np.arange(n_resources, dtype=np.int32)
    merged = rng.integers(0, n_resources, n_resources // 8)
    rho[merged] = rng.integers(0, n_resources, merged.shape[0])
    rho = np.minimum(rho, np.arange(n_resources, dtype=np.int32))
    spo = rng.integers(0, n_resources, (n_triples, 3)).astype(np.int32)
    t0 = time.perf_counter()
    out, changed = rewrite_triples.rewrite_triples(
        jnp.asarray(spo), jnp.asarray(rho), interpret=interpret
    )
    out, changed = np.asarray(out), np.asarray(changed)
    dt = time.perf_counter() - t0
    ref_out, ref_changed = ref.rewrite_triples_ref(jnp.asarray(spo), jnp.asarray(rho))
    if not (np.array_equal(out, np.asarray(ref_out))
            and np.array_equal(changed, np.asarray(ref_changed))):
        raise AssertionError("rewrite_triples differs from rewrite_triples_ref")
    log(f"kernel rewrite_triples: {n_triples} triples x {n_resources} "
        f"resources, interpret={interpret}, matches ref, first call {dt:.3f}s")


def run_store(seed: int, n_chips: int = 1, scale: float = SCALE) -> dict:
    """Base materialisation, update stream and (one chip) queries, each
    epoch checked against the reference.  Returns the counters.  ``scale``
    is cut only to rehearse on the CPU."""
    import jax

    from repro.serve.triple_store import TripleStore

    t0 = time.perf_counter()
    facts, program, dic, events, kw = make_workload(scale, seed)
    log(f"data: {PROFILE} x{scale}: {facts.shape[0]} explicit facts, "
        f"{dic.n_resources} resources, {len(events)} update events "
        f"({time.perf_counter() - t0:.1f}s to generate)")
    ref = Reference(facts, program, dic)
    log(f"reference: base store {ref.mat.triples().shape[0]} rows "
        f"({ref.seconds:.1f}s)")

    engine_kw = {}
    if n_chips > 1:
        from repro.launch.mesh import make_engine_mesh

        engine_kw["mesh"] = make_engine_mesh(n_chips)
    c0, t0 = compile_s, time.perf_counter()
    store = TripleStore(facts, program, dic, **engine_kw)
    base_s = time.perf_counter() - t0
    eng = store.engine
    log(f"base materialisation: {base_s:.3f}s ({compile_s - c0:.3f}s of it "
        f"compiling), arena "
        f"{eng.capacity} rows per shard x {eng.n_shards} shard(s), "
        f"snapshot on device: {store.snapshot.on_device}")
    if n_chips > 1:
        log("sharded store: queries would be answered on the host "
            "(publish_snapshot falls back to read_snapshot when n_shards > 1)")
    check_epoch(0, store.snapshot.triples, store.snapshot.rho.rep, ref)

    rng = np.random.default_rng(seed)
    id_prop = dic.intern(":idProp")
    members = np.unique(facts[facts[:, 1] == id_prop, 0])
    n_queries, splits, update_s = 0, 0, []

    def serve(epoch):
        nonlocal n_queries
        tickets = [store.submit_query(q) for q in
                   epoch_queries(rng, dic, members, kw["hierarchy_depth"] + 1)]
        store.drain()
        for t in tickets:
            if t.epoch != epoch or t.answer != ref.answer(t.query):
                raise AssertionError(f"query {t.query} diverged at epoch {epoch}")
        n_queries += len(tickets)

    if n_chips == 1:
        serve(0)
    for i, (op, delta) in enumerate(events, start=1):
        splits += splits_a_clique(op, delta, ref)
        c0 = compile_s
        ticket = store.submit_update(op, delta)
        store.drain()
        if ticket.status != "done" or ticket.epoch != i:
            raise AssertionError(f"update {i} ended {ticket.status} at {ticket.epoch}")
        update_s.append(ticket.wall_s)
        ref.apply(op, delta)
        snap = store.snapshot
        check_epoch(i, snap.triples, snap.rho.rep, ref)
        log(f"epoch {i}: {op} {delta.shape[0]} rows in {ticket.wall_s:.3f}s "
            f"({compile_s - c0:.3f}s compiling), "
            f"store {snap.triples.shape[0]} rows, matches reference")
        if n_chips == 1:
            serve(i)
    if not splits:
        raise AssertionError("no delete in the stream split a clique")
    if n_chips == 1:
        qs = store.query_stats
        # host fallbacks are by design (short groups, unbatchable shapes);
        # they are printed, but the device matchers must have run
        log(f"queries: {qs}")
        if not qs.get("batched"):
            raise AssertionError(f"no query ran on the device matchers: {qs}")

    stats = {
        "explicit_facts": int(facts.shape[0]),
        "resources": int(dic.n_resources),
        "base_materialise_s": base_s,
        "update_s": update_s,
        "clique_splitting_deletes": int(splits),
        "queries_checked": n_queries,
        "compiles": int(sum(eng.dispatches.compiles.values())),
        "backend_compile_s": compile_s,
        "query_stats": store.query_stats,
        "reference_s": ref.seconds,
    }
    mem = jax.devices()[0].memory_stats() or {}
    stats["peak_hbm_bytes"] = mem.get("peak_bytes_in_use")
    store.close()
    return stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.compat import use_compilation_cache

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"compile cache {use_compilation_cache()}")

    if args.chips == 1:
        run_kernels(False, n_keys=1 << 14, n_triples=1 << 14,
                    n_resources=1 << 20, seed=args.seed)
    stats = run_store(args.seed, n_chips=args.chips)
    log("store: " + json.dumps(stats))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips,
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

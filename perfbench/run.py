#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration (``configs/<name>.json``)
and a traffic mix (``traffic/<name>.json``).  A run

1. refuses to start unless JAX's first device is a TPU and there are as
   many as the cell's chips, and its kind is in ``peaks.json``;
2. makes the graph, the update stream and the lookups from ``--seed``;
3. builds the threaded store (base materialisation), applies the warm-up
   updates and answers one batch of each lookup shape at every batch size
   the window can form: all of this is set-up, ``setup_s``;
4. measures for ``--seconds``: one closed-loop ingest client and, where
   the traffic has lookups, one open-loop reader at the traffic's rate;
5. once the window has closed and the store is freed, holds every epoch it
   published, and every answer, to the plain reference
   (``reference.py``).

Standard error carries the set-up split, the clients' timing and, last,
each number compared beside its limit.  The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics, each read by ``metrics/<name>.py``; a metric split by cell,
``<name>.<part>``, is read by ``metrics/<name>.py`` unless it has a reader
of its own), ``device``, with ``--trace
1`` a ``breakdown``, and last ``checks``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from perfbench import cell, loops, reference, trace  # noqa: E402

HERE = ROOT / "perfbench"
CACHE_DIR = ROOT / ".jax_cache"
ANSWER_WAIT_S = 60.0  # how long past the window's close a lookup may take


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Run:
    """Everything a metric reader may read of one run."""

    t0: float
    window_start: float = 0.0
    window_end: float = 0.0
    updates: list = field(default_factory=list)   # loops.Update, window only
    answers: list = field(default_factory=list)   # loops.Answer
    before: dict = field(default_factory=dict)    # store counters at start
    after: dict = field(default_factory=dict)     # at the last publication
    lookups_after: dict = field(default_factory=dict)  # once lookups ended
    compiles: list = field(default_factory=list)  # (t, kind) of the window
    trace: dict | None = None

    @property
    def completed(self) -> list:
        """Updates published inside the window."""
        return [u for u in self.updates
                if u.status == "done" and u.published <= self.window_end]


class CompileLog:
    """Programs built by the backend, with their times: compiled, or loaded
    from the persistent compile cache.  JAX times both as one backend
    compile; a cache hit, recorded first on the same thread, tells them
    apart."""

    def __init__(self) -> None:
        self.events: list = []   # (perf_counter, "compile" | "cache_load")
        self.seconds = {"compile": 0.0, "cache_load": 0.0}
        self._hit = threading.local()

    def __enter__(self) -> "CompileLog":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._hit.value = True

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            kind = "cache_load" if getattr(self._hit, "value", False) else "compile"
            self._hit.value = False
            self.seconds[kind] += secs
            self.events.append((time.perf_counter(), kind))

    def between(self, lo: float, hi: float) -> list:
        return [e for e in self.events if lo <= e[0] <= hi]


def configure_jax() -> str:
    """Persistent compile cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def find_device(chips: int, require_tpu: bool):
    """The device, or the reason there is none to measure on."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu:
        if dev.platform != "tpu":
            return None, f"needs a TPU, JAX found {dev.platform!r}"
        if len(devices) < chips:
            return None, f"the cell needs {chips} chips, JAX found {len(devices)}"
        peaks = cell.load_json(HERE / "peaks.json")["devices"]
        if dev.device_kind not in peaks:
            return None, f"device kind {dev.device_kind!r} is not in peaks.json"
    return dev, None


def load_reader(name: str):
    """``metrics/<name>.py``'s ``read``; for ``<base>.<part>`` without a
    file of its own, ``metrics/<base>.py``'s."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metric_entries(workload: str, traced: bool, root: Path = ROOT) -> list:
    bench = cell.load_json(root / "BENCHMARK.json")
    entries = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in entries if workload in m.get("workloads", [workload])]


def check(run: Run, inputs, start_view) -> dict:
    """Every published epoch of the run and every answer, against the
    reference.  Returns ``{name: [value, limit]}``."""
    g = inputs.graph
    names = g.names.names
    explicit = np.unique(reference.pack(g.facts))
    for op, rows in inputs.warmup:
        explicit = reference.apply_op(explicit, op, rows)
    epochs = {}
    views = [start_view] + [u.view for u in run.updates]
    stream = iter(inputs.events)
    rows_bad = rho_bad = 0
    for k, view in enumerate(views):
        if k:
            u = run.updates[k - 1]
            op, rows = next(stream)
            if view is None:
                continue
            explicit = reference.apply_op(explicit, op, rows)
        epoch, got_rows, got_rho = view
        want = reference.Epoch(explicit, g.rules, len(names))
        epochs[epoch] = want
        why = reference.store_differs(got_rows, want)
        if why:
            rows_bad += 1
            log(f"epoch {epoch}: store differs: {why}")
        why = reference.rho_differs(got_rho, want)
        if why:
            rho_bad += 1
            log(f"epoch {epoch}: rho differs: {why}")
        if k and u.epoch != epoch:
            rows_bad += 1
            log(f"update {k} acknowledged epoch {u.epoch}, published {epoch}")
    lookups = inputs.lookups
    wrong = stale = 0
    missing = len(lookups) - len(run.answers)
    for a in run.answers:
        if a.answered is None:
            missing += 1
            continue
        if a.epoch < a.acked_epoch:
            stale += 1
        want = epochs.get(a.epoch)
        if want is None or a.bag != want.answer(lookups[a.index], names):
            wrong += 1
            if wrong <= 3:
                log(f"lookup {lookups[a.index]} at epoch {a.epoch}: answer differs")
    failed = sum(u.status != "done" for u in run.updates)
    return {
        "updates_failed": [failed, 0],
        "epochs_rows_differ": [rows_bad, 0],
        "epochs_rho_differ": [rho_bad, 0],
        "answers_wrong": [wrong, 0],
        "answers_missing": [missing, 0],
        "answers_stale": [stale, 0],
    }


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             require_tpu: bool = True, spec: cell.Spec | None = None,
             system=None, root: Path = ROOT) -> dict | None:
    """One run; returns the result line's object, or None when there is no
    device to measure on.  ``spec`` and ``system`` stand in for the cell's
    files and the system under test (the tests and the control use them)."""
    spec = spec or cell.load(workload, root)
    cache = configure_jax()
    dev, why = find_device(spec.chips, require_tpu)
    if dev is None:
        log(f"perfbench: {why}")
        return None
    if system is None:
        from perfbench import system
    with CompileLog() as compiles:
        return _run(spec, seed, seconds, traced, dev, cache, system,
                    compiles, root)


def _run(spec, seed, seconds, traced, dev, cache, system, compiles, root):
    t_init = time.perf_counter()

    inputs = cell.build(spec, seed, seconds)
    t_data = time.perf_counter()
    c0 = compiles.seconds["compile"]
    store = system.make_store(inputs.graph, spec.config.get("engine", {}))
    t_base = time.perf_counter()
    c_base = compiles.seconds["compile"] - c0
    run = Run(T0)
    ingest = loops.Ingest(store, system.snapshot_view, system.counters)
    warm_log = []
    for op, rows in inputs.warmup:
        n0 = len(compiles.events)
        u = ingest.apply(op, rows, span="bench.warm_update")
        if u.status != "done":
            store.drain()
        warm_log.append(f"{op} {u.rows} {(u.published - u.submitted) * 1e3:.1f}ms "
                        f"{len(compiles.events) - n0} programs")
    queries = [system.to_query(q) for q in inputs.lookups]
    loops.warm_lookups(store, [[system.to_query(q) for q in b]
                               for b in inputs.warm_lookups])
    ingest.updates.clear()
    gc.collect()
    t_warm = time.perf_counter()
    log(f"setup: init {t_init - run.t0:.3f}s, data {t_data - t_init:.3f}s, "
        f"base materialisation {t_base - t_data:.3f}s "
        f"({c_base:.3f}s compiling), warm-up {t_warm - t_base:.3f}s, "
        f"compile {compiles.seconds['compile']:.3f}s in all "
        f"({sum(k == 'compile' for _, k in compiles.events)} programs), "
        f"{sum(k == 'cache_load' for _, k in compiles.events)} programs "
        f"loaded in {compiles.seconds['cache_load']:.3f}s from the cache at {cache}")
    log("warm-up updates (programs compiled or loaded by each): " + ", ".join(warm_log))
    log(f"data: {inputs.graph.facts.shape[0]} explicit facts, "
        f"{len(inputs.graph.names)} resources, {len(inputs.warmup)} warm-up "
        f"and {len(inputs.events)} window updates, {len(queries)} lookups")

    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if traced else None
    with trace.capture(trace_dir) if traced else contextlib.nullcontext():
        start_view = system.snapshot_view(store)
        ingest.acked_epoch = start_view[0]
        run.before = system.counters(store)
        run.window_start = time.perf_counter()
        run.window_end = run.window_start + seconds
        marker = threading.Thread(target=_window_span, args=(seconds,), daemon=True)
        marker.start()
        reader = None
        if queries:
            reader = loops.Lookups(store, queries, inputs.due, run.window_start, ingest)
            reader.start()
        ingest.run(inputs.events, run.window_end)
        run.after = system.counters(store)
        if reader is not None:
            reader.join(seconds + ANSWER_WAIT_S)
            run.answers = reader.answers
            if reader.error is not None:
                log(f"lookups stopped: {reader.error!r}")
        run.lookups_after = system.counters(store)
        marker.join()
    run.updates = list(ingest.updates)
    run.compiles = compiles.between(run.window_start, run.window_end)
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    if any(u.status != "done" for u in run.updates):
        try:
            store.drain()
        except Exception as e:  # the failure is counted by the check
            log(f"update failed: {e!r}")
    store.close()
    del store
    gc.collect()

    if traced:
        path = trace.xplane_path(trace_dir)
        if path:
            run.trace = trace.reduce(trace.load_extract(path), trace.layer_map())
        shutil.rmtree(trace_dir, ignore_errors=True)

    _log_clients(run)
    t_ref = time.perf_counter()
    checks = check(run, inputs, start_view)
    log(f"reference: {len(run.updates) + 1} epochs and {len(run.answers)} "
        f"answers checked in {time.perf_counter() - t_ref:.3f}s")

    metrics = {}
    for m in metric_entries(spec.name, traced, root):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": spec.chips, "memory_peak_bytes": int(mem)}
    if run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    result = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": len(run.updates) + len(queries),
        "failed": checks["updates_failed"][0] + checks["answers_missing"][0],
        "metrics": metrics,
        "device": device,
    }
    if run.trace:
        result["breakdown"] = {k: run.trace[k] for k in ("device_ops", "idle_gaps")}
    for name, (v, lim) in checks.items():
        log(f"check {name}: {v} (limit {lim})")
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result


def _window_span(seconds: float) -> None:
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("bench.window"):
        time.sleep(seconds)


def _log_clients(run: Run) -> None:
    done = run.completed
    log(f"ingest: {len(run.updates)} updates sent in the window, "
        f"{len(done)} published in it: " + ", ".join(
            f"{u.op} {u.rows} {(u.published - u.submitted) * 1e3:.1f}ms"
            for u in run.updates if u.published is not None))
    if run.answers:
        late = [a.submitted - a.due for a in run.answers]
        log(f"lookups: {len(run.answers)} due; the reader sent them late by "
            f"p50 {loops.percentile(late, 50) * 1e3:.3f}ms, p95 "
            f"{loops.percentile(late, 95) * 1e3:.3f}ms, max "
            f"{max(late) * 1e3:.3f}ms; {run.lookups_after.get('query_stats')}")
        lat = [a.answered - a.due for a in run.answers if a.answered is not None]
        if lat:
            log("lookup latency ms: " + ", ".join(
                f"p{q} {loops.percentile(lat, q) * 1e3:.3f}"
                for q in (10, 25, 50, 75, 90, 95, 99, 100))
                + f"; {sum(x < 0.01 for x in lat)} of {len(lat)} under 10 ms")
    log(f"compiles in the window: {len(run.compiles)} "
        f"({[k for _, k in run.compiles]}); engine programs built "
        f"{run.after.get('engine_compiles', 0) - run.before.get('engine_compiles', 0)}"
        f"; capacity retries {run.after.get('capacity_retries', 0) - run.before.get('capacity_retries', 0)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    if result is None:
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

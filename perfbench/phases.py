#!/usr/bin/env python3
"""Device time per phase of the store, read from a traced run.

The store opens a span ``store.<phase>`` (``DispatchCounter.in_phase``)
around each phase of an update, around its publication and around each
lookup drain's matchers, and JAX records every jitted call as a host event
``PjitFunction(<name>)`` on the calling thread.  The engine names each
program after its fn-cache family, and a device runs programs in the order
they were launched, so the k-th launch of ``X`` on the host is the k-th
execution of ``jit_X`` on each device, and the span open around a launch
on its thread names the phase that its device time belongs to.

``extract`` reads the spans and the launches from a profile, to go beside
``trace.extract``'s dict; ``reduce`` gives, per update published in the
traced window (a ``bench.update`` span that ended in it):

* ``phase_device_s_per_update``: device time by phase, and
  ``program_device_s_per_update`` by phase and program;
* ``maint_idle_s_per_update``: time in which the device was idle while the
  worker had a ``store.*`` span other than ``store.query`` open;
* ``span_cover_min``: the least share of an update, from its
  ``store.begin`` to the end of its ``store.publish_host``, that the
  worker's spans cover;
* ``idle_gaps``: the longest idle gaps, each labelled by the ``bench.*``
  and ``store.*`` spans open across most of it;
* ``longest_launches``: the calls that held the host longest (a compile
  shows here, under its program and phase).

Pairing drops the executions at the trace's start whose launch came before
it, and the launches at its end whose execution came after it, and counts
them in ``unmatched``.  Where a program is left with more than one, or a
pairing would put an execution before its launch (by more than the
clocks' skew, ``SKEW_NS``), the device times read None: they are not
guessed.  A program launched but never executed on the
device (one JAX answered without it) is left out.

Run as a script, it runs one cell traced, as ``run.py --trace 1`` does,
and prints the run's result line, then a last line with this reduction::

    python3 perfbench/phases.py --workload <cell> --seed <n> --seconds <s>
"""

from __future__ import annotations

import argparse
import gzip
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from perfbench import trace  # noqa: E402

STORE, CALL = "store.", "PjitFunction("
QUERY = "store.query"  # a reader's span; every other store span is the worker's
# how far an execution may seem to start before its launch: in traces of
# one TPU v5e the device's clock reads 0.3-1.2 ms behind the host's
SKEW_NS = 5e6


def extract(profile) -> dict:
    """``store_spans`` as ``[name, start_ns, dur_ns, thread]`` and
    ``launches`` as ``[program, start_ns, dur_ns, thread]``.  A call
    records two nested ``PjitFunction`` events, and a call that traces a
    program records the calls traced inside it, so only the outermost
    event of a call counts.  ``thread`` is the line's index in its plane:
    every Python thread's line is named alike."""
    spans, launches = [], []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):
            calls = []
            for e in line.events:
                if e.name.startswith(STORE):
                    spans.append([e.name, e.start_ns, e.duration_ns, thread])
                elif e.name.startswith(CALL):
                    calls.append((e.start_ns, -e.duration_ns, e.name[len(CALL):-1]))
            end = -np.inf
            for start, neg_dur, program in sorted(calls):
                if start >= end:
                    launches.append([program, start, -neg_dur, thread])
                    end = start - neg_dur
    return {"store_spans": spans, "launches": launches}


def _iv(rows) -> np.ndarray:
    return np.asarray(rows, np.float64).reshape(-1, 2)


def _busy(u: np.ndarray):
    """``busy(lo, hi)``: the length of ``[lo, hi]`` that the disjoint
    sorted intervals ``u`` cover, for arrays of bounds."""
    if not len(u):
        return lambda lo, hi: np.zeros(np.shape(lo))
    starts, ends = u[:, 0], u[:, 1]
    cum = np.r_[0.0, np.cumsum(ends - starts)]

    def before(t):
        i = np.searchsorted(starts, t, side="right")
        over = np.where(i > 0, np.maximum(ends[np.maximum(i - 1, 0)] - t, 0), 0)
        return cum[i] - over

    return lambda lo, hi: np.maximum(before(hi) - before(lo), 0)


def _phases_of(launches: list, spans: list) -> list:
    """The innermost ``store.*`` span open on its thread at each launch,
    without its prefix; "none" where there is none."""
    t = np.asarray([l[1] for l in launches], np.float64)
    th = np.asarray([l[3] for l in launches])
    best = np.full(t.shape, -np.inf)
    phase = np.full(t.shape, "none", dtype=object)
    for name, s, d, thread in spans:
        inner = (th == thread) & (t >= s) & (t < s + d) & (s > best)
        best[inner] = s
        phase[inner] = name[len(STORE):]
    return list(phase)


def _match(launch: np.ndarray, execs: np.ndarray):
    """Indices pairing one program's launch times with its executions
    (``[start, end]``, in order) on one device, and how many of either
    were left over; None where a pair would run before its launch, by
    more than the clocks' skew."""
    extra = len(execs) - len(launch)
    n = min(len(launch), len(execs))
    li, ei = np.arange(n), np.arange(n) + max(extra, 0)
    if np.any(execs[ei, 0] < launch[li] - SKEW_NS):
        return None
    return li, ei, abs(extra)


def reduce(ex: dict, n_gaps: int = 10, n_longest: int = 5) -> dict | None:
    """The phase reduction of ``trace.extract``'s dict with ``extract``'s
    keys; None when there is nothing to read (no window, no device, or a
    program that opened no ``store.*`` span)."""
    windows = [s for s in ex["spans"] if s[0] == "bench.window"]
    store = ex.get("store_spans") or []
    if not windows or not ex["devices"] or not store:
        return None
    _, w0, wd, _ = windows[0]
    w1 = w0 + wd
    done = _iv([[s, s + d] for n, s, d, _ in ex["spans"]
                if n == "bench.update" and s >= w0 and s + d <= w1])
    worker = trace._union(_iv([[s, s + d] for n, s, d, _ in store if n != QUERY]))

    launches = sorted(ex.get("launches") or [], key=lambda l: l[1])
    phases = _phases_of(launches, store)
    by_program: dict[str, list[int]] = {}
    for k, l in enumerate(launches):
        by_program.setdefault(l[0], []).append(k)
    t_launch = np.asarray([l[1] for l in launches], np.float64)
    in_update = np.zeros(len(launches), bool)
    for a, b in done:
        in_update |= (t_launch >= a) & (t_launch <= b)

    n_dev = len(ex["devices"])
    unmatched: dict[str, int] = {}
    misaligned: list[str] = []
    attributed: dict[tuple, float] = {}
    idle = 0.0
    for _name, dev in sorted(ex["devices"].items()):
        u = trace._union(trace._clip(_iv([[s, s + d] for s, d in dev["ops"]]), w0, w1))
        busy = _busy(u)
        for a, b in done:
            m = trace._clip(worker, a, b)
            idle += float((m[:, 1] - m[:, 0]).sum() - busy(m[:, 0], m[:, 1]).sum())
        execs: dict[str, list] = {}
        for mname, s, d in dev["modules"]:
            execs.setdefault(trace.program_name(mname), []).append([s, s + d])
        for program, ev in execs.items():
            ev = _iv(sorted(ev))
            ks = np.asarray(by_program.get(program, []), int)
            pairing = _match(t_launch[ks], ev)
            if pairing is None:
                misaligned.append(program)
                continue
            li, ei, left = pairing
            if left:
                unmatched[program] = max(unmatched.get(program, 0), left)
            keep = in_update[ks[li]]
            ks, ev = ks[li][keep], ev[ei][keep]
            t = busy(np.maximum(ev[:, 0], w0), np.minimum(ev[:, 1], w1))
            for k, dt in zip(ks, t):
                key = (phases[k], program)
                attributed[key] = attributed.get(key, 0.0) + float(dt)

    n_done = len(done)
    per = n_dev * n_done * 1e9
    matched = not misaligned and all(v <= 1 for v in unmatched.values())
    phase_s = program_s = None
    if matched and n_done:
        phase_s, program_s = {}, {}
        for (phase, program), t in sorted(attributed.items(), key=lambda kv: -kv[1]):
            phase_s[phase] = phase_s.get(phase, 0.0) + t / per
            program_s[f"{phase} {program}"] = t / per

    # trace.reduce labels each gap by every span it is given
    labelled = trace.reduce({**ex, "spans": ex["spans"] + store},
                            trace.layer_map(), n_gaps)

    ends = [(s, s + d, th) for n, s, d, th in store if n == "store.publish_host"]
    covers = []
    for n, s, _d, th in store:
        if n != "store.begin" or s < w0:
            continue
        end = min((e for s2, e, th2 in ends if th2 == th and s2 >= s), default=None)
        if end is not None and end <= w1:
            covers.append(trace._overlap(worker, s, end) / (end - s))

    in_window = [k for k, l in enumerate(launches) if w0 <= l[1] <= w1]
    slow = sorted(in_window, key=lambda k: -launches[k][2])[:n_longest]
    return {
        "updates_traced": n_done,
        "unmatched": unmatched,
        "misaligned": misaligned,
        "phase_device_s_per_update": phase_s,
        "program_device_s_per_update": program_s,
        "maint_idle_s_per_update": idle / n_dev / n_done / 1e9 if n_done else None,
        "span_cover_min": min(covers) if covers else None,
        "updates_spanned": len(covers),
        "idle_gaps": labelled["idle_gaps"] if labelled else [],
        "longest_launches": [[launches[k][0], phases[k], launches[k][2] / 1e9]
                             for k in slow],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", help="also write the extract, gzipped JSON, here")
    args = ap.parse_args(argv)

    from jax.profiler import ProfileData

    from perfbench import run

    read = trace.load_extract
    out = {}

    def read_with_phases(path: str) -> dict:
        # the run reads its own trace once through here, so the phase keys
        # come from the same file as every other traced metric
        ex = read(path)
        ex.update(extract(ProfileData.from_file(path)))
        out["phases"] = reduce(ex)
        if args.keep:
            Path(args.keep).write_bytes(gzip.compress(json.dumps(ex).encode()))
        return ex

    trace.load_extract = read_with_phases
    result = run.run_cell(args.workload, args.seed, args.seconds, True)
    if result is None:
        return 2
    phases = out.get("phases")
    if phases:
        run.log(f"phases: unmatched {phases['unmatched']}, misaligned "
                f"{phases['misaligned']}")
    print(json.dumps(result), flush=True)
    print(json.dumps({"phases": phases}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

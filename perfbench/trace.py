"""Device trace of the window, and its reduction to metrics.

``capture`` wraps the window in a JAX profiler trace (host spans and device
operations; no Python tracer).  ``extract`` reads the ``.xplane.pb`` it
writes into plain lists of intervals, and ``reduce`` turns those into:

* device busy time: the union of the device's operation intervals inside
  the ``bench.window`` span, and the idle share that is left;
* device busy time per completed update: the busy time inside each
  ``bench.update`` span that ended in the window, per such update;
* device time per program and per layer, through ``layers.json``, which
  maps a program's exact name (the jit name in the trace, without its
  ``jit_`` prefix) to the layer it belongs to; a name it does not list
  goes to "other";
* the longest idle gaps, each labelled by the ``bench.*`` spans that the
  benchmark's own clients had open across most of it.

Timestamps are the profiler's, in nanoseconds on one clock for host and
device planes.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace host spans and device operations into ``log_dir``."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_path(log_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def extract(profile) -> dict:
    """Plain intervals from a ``jax.profiler.ProfileData``.

    ``devices`` maps each device plane to its operation intervals
    ``[start_ns, dur_ns]`` and its program intervals ``[name, start_ns,
    dur_ns]``; ``spans`` lists the host's ``bench.*`` spans as ``[name,
    start_ns, dur_ns, thread]``.
    """
    devices, spans = {}, []
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [[e.start_ns, e.duration_ns] for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [[e.name, e.start_ns, e.duration_ns]
                               for e in line.events]
            if ops or modules:
                devices[plane.name] = {"ops": ops, "modules": modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns, line.name]
                          for e in line.events if e.name.startswith("bench.")]
    return {"devices": devices, "spans": spans}


def load_extract(path: str) -> dict:
    """``extract`` of an ``.xplane.pb`` (``.gz`` allowed) or a saved JSON."""
    from jax.profiler import ProfileData

    raw = Path(path).read_bytes()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
        path = path[:-3]
    if path.endswith(".json"):
        return json.loads(raw)
    return extract(ProfileData.from_serialized_xspace(raw))


def program_name(module: str) -> str:
    """``jit_fused_forward_rounds(123)`` -> ``fused_forward_rounds``."""
    name = re.sub(r"\(.*\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def layer_map(path: Path = HERE / "layers.json") -> dict:
    with open(path) as fh:
        return json.load(fh)["layers"]


def layer_of(program: str, layers: dict) -> str:
    """The layer that lists ``program`` by its exact name; "other"."""
    for layer, names in layers.items():
        if program in names:
            return layer
    return "other"


def _union(iv: np.ndarray) -> np.ndarray:
    """Disjoint sorted [start, end] intervals covering ``iv``."""
    if iv.shape[0] == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(iv.shape[0], bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.r_[np.flatnonzero(new)[1:] - 1, iv.shape[0] - 1]
    return np.stack([starts, ends[last]], axis=1)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _overlap(iv: np.ndarray, lo: float, hi: float) -> float:
    if iv.shape[0] == 0:
        return 0.0
    return float(np.clip(np.minimum(iv[:, 1], hi) - np.maximum(iv[:, 0], lo),
                         0, None).sum())


def reduce(ex: dict, layers: dict, n_gaps: int = 10) -> dict | None:
    """Metrics of one traced window; None when there is nothing to read
    (no ``bench.window`` span, or no device operation in it)."""
    windows = [s for s in ex["spans"] if s[0] == "bench.window"]
    if not windows or not ex["devices"]:
        return None
    _, w0, wd, _ = windows[0]
    w1 = w0 + wd
    spans = [s for s in ex["spans"] if s[0] != "bench.window"]

    done = [s for s in spans if s[0] == "bench.update" and s[1] >= w0
            and s[1] + s[2] <= w1]
    busy, busy_in_updates, programs = [], [], {}
    gaps = np.zeros((0, 2))
    for i, (_name, dev) in enumerate(sorted(ex["devices"].items())):
        ops = np.asarray([[s, s + d] for s, d in dev["ops"]], np.float64)
        u = _union(_clip(ops.reshape(-1, 2), w0, w1))
        busy.append(float((u[:, 1] - u[:, 0]).sum()))
        busy_in_updates.append(sum(_overlap(u, s[1], s[1] + s[2]) for s in done))
        if i == 0:
            edges = np.r_[w0, u.ravel(), w1].reshape(-1, 2)
            gaps = edges[edges[:, 1] > edges[:, 0]]
        for mname, s, d in dev["modules"]:
            lo, hi = max(s, w0), min(s + d, w1)
            if hi > lo:
                prog = program_name(mname)
                programs[prog] = programs.get(prog, 0.0) + (hi - lo) / len(ex["devices"])
    if not any(busy):
        return None
    per_layer: dict[str, float] = {}
    for prog, t in programs.items():
        layer = layer_of(prog, layers)
        per_layer[layer] = per_layer.get(layer, 0.0) + t

    span_iv = {}
    for name, s, d, _thread in spans:
        span_iv.setdefault(name, []).append([s, s + d])
    span_iv = {k: _union(np.asarray(v, np.float64)) for k, v in span_iv.items()}
    longest = gaps[np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")][:n_gaps]
    labelled = []
    for lo, hi in longest:
        names = sorted(k for k, iv in span_iv.items()
                       if _overlap(iv, lo, hi) >= 0.5 * (hi - lo))
        labelled.append(["+".join(names) or "no bench span", (hi - lo) / 1e9])

    window_s = wd / 1e9
    busy_s = float(np.mean(busy)) / 1e9
    top = sorted(programs.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "layer_device_s": {k: v / 1e9 for k, v in per_layer.items()},
        "device_s_per_update": (float(np.mean(busy_in_updates)) / len(done) / 1e9
                                if done else None),
        "updates_traced": len(done),
        "device_ops": [[f"{k} [{layer_of(k, layers)}]", v / 1e9] for k, v in top],
        "idle_gaps": labelled,
    }

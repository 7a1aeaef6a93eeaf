"""Helpers of the benchmark's CPU tests: cells cut to a tiny scale."""

from __future__ import annotations

from perfbench import cell, run


def tiny(spec: cell.Spec) -> cell.Spec:
    """``spec`` at a scale the CPU runs in seconds: the same shapes and
    guarantees, a few dozen groups, the store's default engine sizing."""
    g = spec.config["generator"]
    g["n_groups"] = max(g["n_groups"] // 200, 8)
    g["n_plain"] = max(g["n_plain"] // 200, 40)
    spec.config["engine"] = {}
    up = spec.traffic["updates"]
    up["batch"] = min(up["batch"], 8)
    up["warmup"] = [[op, min(b, 8)] for op, b in up["warmup"]]
    up["events"] = 12
    if "queries" in spec.traffic:
        spec.traffic["queries"]["rate_per_s"] = 20
        spec.traffic["queries"]["warm_batches"] = [1, 2]
    return spec


def tiny_run(workload: str, seconds: float = 1.5, traced: bool = False,
             **kw) -> dict:
    """One run of ``workload`` at the tiny scale, on the CPU."""
    spec = kw.pop("spec", None) or tiny(cell.load(workload))
    return run.run_cell(workload, 1234567890123, seconds, traced,
                        require_tpu=False, spec=spec, **kw)

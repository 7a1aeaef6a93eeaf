"""Pallas kernel microbench: wall time vs the jnp oracle.

The kernels run as Mosaic on a TPU and interpreted elsewhere
(``repro.kernels.ops.interpret_mode``).  Interpreted timings do NOT reflect
TPU performance (each grid step runs the kernel body in Python-driven XLA);
off the TPU the numbers are a correctness + plumbing check.  The
TPU-relevant analysis of these kernels is the BlockSpec/VMEM sizing in each
kernel file and the §Roofline terms.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _time(fn, *args, repeats=3):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(repeats):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / repeats * 1e3


def main() -> list[dict]:
    rng = np.random.default_rng(0)
    rows = []

    # pointer_jump: rho-closure (interpret mode => small shapes; TPU shapes in kernel docstrings)
    table = np.arange(1 << 13, dtype=np.int32)
    table[1:] = rng.integers(0, np.arange(1, 1 << 13))  # random forest
    idx = rng.integers(0, 1 << 13, (1 << 12,)).astype(np.int32)
    rows.append({
        "kernel": "pointer_jump",
        "pallas_ms": _time(lambda a, b: ops.pointer_jump(a, b), idx, table),
        "ref_ms": _time(ref.pointer_jump_ref, idx, table),
    })

    # rewrite_triples: 64k-triple arena sweep
    spo = rng.integers(0, 1 << 13, (1 << 13, 3)).astype(np.int32)
    rho = np.arange(1 << 13, dtype=np.int32)
    rho[rng.integers(0, 1 << 13, 1 << 10)] = 0
    rows.append({
        "kernel": "rewrite_triples",
        "pallas_ms": _time(lambda a, b: ops.rewrite_triples(a, b), spo, rho),
        "ref_ms": _time(ref.rewrite_triples_ref, spo, rho),
    })

    # embedding_bag: 4k bags x 16 ids from a 1M x 64 table
    table_f = rng.normal(size=(1 << 14, 64)).astype(np.float32)
    ids = rng.integers(0, 1 << 14, (1 << 10, 16)).astype(np.int32)
    rows.append({
        "kernel": "embedding_bag",
        "pallas_ms": _time(lambda a, b: ops.embedding_bag(a, b), ids, table_f),
        "ref_ms": _time(ref.embedding_bag_ref, ids, table_f),
    })

    # fm_interact: 8k x 39 x 16 sum-square interaction
    emb = rng.normal(size=(1 << 10, 39, 16)).astype(np.float32)
    rows.append({
        "kernel": "fm_interact",
        "pallas_ms": _time(lambda a: ops.fm_interact(a), emb),
        "ref_ms": _time(ref.fm_interact_ref, emb),
    })

    print("kernel                 pallas_ms     ref_ms")
    for r in rows:
        print(f"{r['kernel']:17s} {r['pallas_ms']:14.2f} {r['ref_ms']:10.2f}")
    return rows


if __name__ == "__main__":
    from repro.compat import use_compilation_cache

    use_compilation_cache()
    main()

"""Table 3 analogue: materialisation wall time, AX vs REW, across shard
counts.

The paper scales threads on one shared-memory node; our SPMD adaptation
scales mesh shards.  This container has ONE physical core, so multi-shard
wall times measure partitioning overhead, not speedup — the honest scaling
signal on real hardware comes from the dry-run collective analysis
(EXPERIMENTS.md §Roofline).  What IS real on CPU and mirrors the paper's
Table 3 is the AX/REW wall-time factor per dataset at each shard count
(every shard count is a subprocess with that many fake devices).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

_SCRIPT = textwrap.dedent(
    """
    import json, sys, time
    import numpy as np, jax
    from repro.data.generator import generate, PROFILES
    from repro.core.materialise import materialise
    from repro.core.engine_jax import JaxEngine

    profile, n_dev = sys.argv[1], int(sys.argv[2])
    facts, prog, dic = generate(**PROFILES[profile])

    t0 = time.time(); materialise(facts, prog, dic.n_resources, mode="AX")
    ax_s = time.time() - t0
    t0 = time.time(); materialise(facts, prog, dic.n_resources, mode="REW")
    rew_np_s = time.time() - t0

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((n_dev,), ("data",))
    cap = 1 << 17
    eng = JaxEngine(dic.n_resources, capacity=cap // n_dev, bind_cap=1 << 14,
                    out_cap=1 << 14, rewrite_cap=1 << 14, mesh=mesh)
    t0 = time.time()
    spo, rep, stats = eng.materialise(facts, prog)
    rew_jax_s = time.time() - t0
    print(json.dumps({
        "profile": profile, "n_dev": n_dev, "ax_s": ax_s,
        "rew_np_s": rew_np_s, "rew_jax_s": rew_jax_s,
        "derivations": int(stats.derivations), "rounds": int(stats.rounds),
    }))
    """
)


def run_cell(profile: str, n_dev: int) -> dict:
    env = dict(os.environ)
    # fake CPU devices measure partitioning; pinning the child to the CPU
    # also keeps it off an accelerator the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, profile, str(n_dev)],
        capture_output=True, text=True, env=env, timeout=1800,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"bench_scaling {profile} on {n_dev} devices exited "
            f"{out.returncode}:\n{out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def main(profiles=("claros_like", "opencyc_like"), shard_counts=(1, 2, 4)) -> list:
    rows = []
    print("profile        shards   AX(np)   REW(np)  REW(jax)  AX/REW(np)  derivs")
    for profile in profiles:
        for n in shard_counts:
            r = run_cell(profile, n)
            rows.append(r)
            print(
                f"{profile:14s} {n:6d} {r['ax_s']:8.3f} {r['rew_np_s']:8.3f}"
                f" {r['rew_jax_s']:9.3f} {r['ax_s']/max(r['rew_np_s'],1e-9):10.2f}"
                f" {r['derivations']:8d}"
            )
    return rows


if __name__ == "__main__":
    main()

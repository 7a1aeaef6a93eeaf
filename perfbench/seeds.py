#!/usr/bin/env python3
"""Many seeds of one cell in one process: the readings behind each limit.

    python3 perfbench/seeds.py --workload <cell> --seeds 1,2,3 --seconds 10 [--trace 1] [--control]

Runs ``run.run_cell`` once per seed, with the system under test or, with
``--control``, the control of ``control.py``, and prints one JSON line per
seed: whether it came out correct and each number the check compared.
The set-up of later seeds reuses the compile cache of the first.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench import run  # noqa: E402
from perfbench.control import Control  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(
            args.workload, seed, args.seconds, bool(args.trace),
            require_tpu=not args.control,
            system=Control if args.control else None,
        )
        if res is None:
            return 2
        print(json.dumps(dict(res, seed=seed, control=args.control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The readings the output limits are set from, many seeds in one process
(not part of a benchmark run).

    python3 perfbench/readings.py --workload <name> --seeds 1,2,3 \
        [--program] [--control] [--faults half_batch,token]

``--program`` runs the cell's set-up (the timed path's first steps) and
compares it with the reference, as a run does; ``--control`` puts the
reference itself in the program's place, computed a precision lower than
the cell states; ``--faults`` puts the reference in the program's place
with a fault planted. One JSON line per seed and reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(_ROOT, ".jax_cache"))
    import importlib

    from perfbench.common import find_cell
    spec = find_cell(args.workload)
    kind = importlib.import_module(f"perfbench.kinds.{spec['mix']['kind']}")
    seeds = [int(s) for s in args.seeds.split(",")]
    who = (["program"] if args.program else []) + \
          (["control"] if args.control else []) + \
          [f for f in args.faults.split(",") if f]
    for seed in seeds:
        for w, numbers in kind.readings(spec, seed, who).items():
            print(json.dumps({"seed": seed, "who": w, **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

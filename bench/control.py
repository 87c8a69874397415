#!/usr/bin/env python3
"""The precision control of ``correct``: the plain reference in the
program's place, computed in float32 where the configurations state
float64, on a cell's own inputs.  It must come out not correct.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 \\
        [--requests <n>]

``--requests`` is the number of window requests a closed-loop live run
prices (its ``attempted``).  Prints the compared numbers of each seed
beside their limits, and exits non-zero if any seed came out correct.
The reference runs on the host; the program is not used.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import compare  # noqa: E402
import run  # noqa: E402
from reference import akpc as reference  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, default=1_000_000)
    args = ap.parse_args(argv)
    spec = run._json(os.path.join(run.ROOT, "BENCHMARK.json"))
    passed = []
    for seed in args.seeds:
        cell = run.Cell(spec, args.workload, seed, args.seconds)
        driver = importlib.import_module(
            "drivers." + cell.traffic["driver"])
        t = time.perf_counter()
        pairs = []
        for log, costs in driver.reference_inputs(cell, args.requests):
            pairs.append((reference.run(log, costs, cell.cfg["policy"]),
                          reference.run(log, costs, cell.cfg["policy"],
                                        np.float32)))
        ok, checks = compare.judge(pairs, cell.cfg["guarantee"])
        passed.append(ok)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": ok, "checks": checks,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 1 if any(passed) else 0


if __name__ == "__main__":
    sys.exit(main())

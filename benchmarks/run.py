"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes JSON payloads to
experiments/results/.  Budget knobs (env):
  REPRO_BENCH_REQUESTS        fig5 trace length   (default 150000)
  REPRO_BENCH_SWEEP_REQUESTS  per-sweep-point     (default 40000)
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from . import (
        fig5_cost_comparison,
        fig6_sensitivity,
        fig7_hyperparams,
        fig8_scalability,
        fig9_cliques_runtime,
        fig10_heterogeneous,
        integration_bench,
        kernel_bench,
        replay_bench,
        sweep_bench,
        table1_cost_model,
    )

    suites = [
        ("replay", replay_bench),
        ("sweep", sweep_bench),
        ("table1", table1_cost_model),
        ("fig5", fig5_cost_comparison),
        ("fig6", fig6_sensitivity),
        ("fig7", fig7_hyperparams),
        ("fig8", fig8_scalability),
        ("fig9", fig9_cliques_runtime),
        ("fig10", fig10_heterogeneous),
        ("kernels", kernel_bench),
        ("integration", integration_bench),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    for name, mod in suites:
        if only and only != name:
            continue
        t0 = time.time()
        mod.main()
        print(f"suite/{name},{int((time.time()-t0)*1e6)},done", flush=True)


if __name__ == "__main__":
    main()

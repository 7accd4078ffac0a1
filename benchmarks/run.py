"""Benchmark driver — one function per paper table/figure.

Prints CSV blocks per benchmark (name,metrics...) and persists each
benchmark's rows to ``BENCH_<name>.json`` at the repo root
(machine-readable perf trajectory across PRs).  BENCH_FAST=1 shrinks
durations for CI.

Usage: ``python benchmarks/run.py [--list] [--seed N] [bench_name ...]``

* positional names run only those benchmarks (e.g. ``fig5_throughput
  table23_recovery`` for the CI smoke subset);
* ``--list`` prints the available benchmark names and exits (the subset CLI
  is discoverable without reading this file);
* ``--seed N`` seeds ``random`` and ``numpy`` and exports
  ``REPRO_BENCH_SEED`` before any benchmark imports, so stochastic
  workload draws are reproducible across runs/machines.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

BENCH_NAMES = [
    "fig5_throughput",
    "fig6_io_bandwidth",
    "fig7_commit_latency",
    "fig8_breakdown",
    "fig9_scalability",
    "fig10_commit_protocol",
    "fig_shard_scalability",
    "fig_replication",
    "fig_truncation",
    "fig_adaptive",
    "fig_serve",
    "fig_kernels",
    "fig_trace",
    "table23_recovery",
    "roofline",
]


def main(only=None, seed=None) -> None:
    if seed is not None:
        import random

        import numpy as np

        os.environ["REPRO_BENCH_SEED"] = str(seed)
        random.seed(seed)
        np.random.seed(seed)

    from _util import bench_runtime_setup

    bench_runtime_setup()

    import importlib

    benches = [(n, importlib.import_module(n).run) for n in BENCH_NAMES]
    if only:
        unknown = set(only) - {n for n, _ in benches}
        if unknown:
            raise SystemExit(f"unknown benchmarks: {sorted(unknown)}; "
                             f"available: {[n for n, _ in benches]}")
        benches = [(n, fn) for n, fn in benches if n in set(only)]
    for name, fn in benches:
        t0 = time.perf_counter()
        print(f"\n### {name}")
        fn()
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("benchmarks", nargs="*",
                    help="benchmark names to run (default: all)")
    ap.add_argument("--list", action="store_true",
                    help="print available benchmark names and exit")
    ap.add_argument("--seed", type=int, default=None,
                    help="seed random+numpy (and REPRO_BENCH_SEED) first")
    args = ap.parse_args()
    if args.list:
        # stable-sorted so CI diffs of the listing are deterministic and
        # independent of the run-order grouping above
        print("\n".join(sorted(BENCH_NAMES)))
        raise SystemExit(0)
    main(args.benchmarks, seed=args.seed)

"""Find an open-loop cell's knee: one load, then a ladder of offered rates.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 2000,4000,8000

Sets the cell up once (load and warm-up as ``bench/run.py`` does), then for
each rate offers Poisson arrivals for ``seconds`` through a fresh scheduler
over the same backend, lets the window settle, and prints one JSON line:
offered and realized rate, goodput, p50 and p99 latency, failures, mean
cut, and the scheduler's queue depth over the first and the last quarter of
the window.  The knee is the highest rate whose goodput keeps up with the
realized arrival rate and whose queue does not grow; the cell's traffic
file then fixes its rate at about four fifths of it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

from run import ROOT, GateError, device_gate  # noqa: E402


def step(r, rate: float, seconds: float) -> dict:
    """One rung: offer ``rate`` for ``seconds``; returns its summary."""
    arrivals = r.open_arrivals(rate, seconds)
    r.gc_pauses.reset()
    w = r.serve(seconds, arrivals=arrivals)
    stats = r.stats
    # queue depth, sampled on every scheduler loop iteration
    q = np.asarray(r.sched.queue_samples[:w.queue_samples_end], dtype=float)
    r.sched.stop(quiesce=True)
    r.sched = r.new_scheduler()
    lat = w.latencies_ms()
    quarter = max(1, len(q) // 4)
    return {
        "offered_per_s": rate,
        "realized_per_s": w.n / seconds,
        "goodput_per_s": w.goodput(),
        "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
        "p99_ms": float(np.percentile(lat, 99)) if len(lat) else None,
        "failed": w.failed(),
        "mean_cut": stats["mean_cut"],
        "queue_first_quarter": float(q[:quarter].mean()) if len(q) else 0.0,
        "queue_last_quarter": float(q[-quarter:].mean()) if len(q) else 0.0,
        "compiles": w.compiles,
        "gc_in_window": r.gc_pauses.summary(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, txn/s")
    args = ap.parse_args(argv)

    from harness.cell import CellRun
    from harness.spec import Bench

    bench = Bench(ROOT)
    try:
        device = device_gate(int(bench.cell(args.workload)["chips"]))
    except GateError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    from repro.kernels import ops

    ops.enable_compile_cache()
    r = CellRun(bench, args.workload, args.seed, args.seconds)
    if r.traffic["arrival"] != "open":
        print("sweep: the cell's traffic is not open-loop", file=sys.stderr)
        return 2
    print(json.dumps({"device": device, "load_s": r.load_s,
                      "warm_s": r.warm_s,
                      "setup_s": time.perf_counter() - T_START}), flush=True)
    try:
        for rate in (float(x) for x in args.rates.split(",")):
            print(json.dumps(step(r, rate, args.seconds)), flush=True)
    finally:
        r.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

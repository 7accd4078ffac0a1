"""Record a small device trace of the three OLTP kernels on the chip.

Runs ``occ_seg_reduce`` (min and max), ``fused_validate_sequence`` and
``fused_replay_scan`` a few times each under the JAX profiler, with a
``TraceAnnotation`` marking the traced window on the host, and writes

* ``<out>/trace.xplane.pb`` — the raw trace (the committed test fixture
  ``bench/tests/data/kernels.xplane.pb`` is one such file);
* ``<out>/summary.json`` — every plane and line with its event names and
  counts, to read by hand how the device names the kernels.

Run from the repository root on a machine with a TPU::

    python bench/record_trace.py --out chiprun_out/trace_probe
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax import profiler

    from repro.kernels import ops

    rng = np.random.default_rng(0)

    def seg(n, slots, op):
        key = rng.integers(0, slots, n).astype(np.int32)
        val = rng.integers(0, 1 << 20, n).astype(np.int32)
        return ops.occ_seg_reduce(key, val, n_slots=slots, op=op)

    def fvs(n_txn, k, cap):
        acc = np.zeros((6, n_txn * k), np.int32)
        acc[0] = rng.integers(0, cap, n_txn * k)
        acc[1] = np.repeat(np.arange(n_txn), k)
        acc[2] = 1
        acc[3] = -1
        return ops.fused_validate_sequence(
            acc, np.ones(n_txn, np.int32), n_txn=n_txn, k=k, cap=cap)

    def scan(n):
        s = np.zeros((3, n), np.int32)
        s[0] = rng.integers(0, 2 * n, n)
        s[1] = rng.integers(0, 1 << 20, n)
        s[2] = np.arange(n)
        return ops.fused_replay_scan(s, n_slots=2 * n)

    calls = [lambda: seg(256, 256, "min"), lambda: seg(256, 256, "max"),
             lambda: fvs(4096, 1, 1 << 24), lambda: scan(2048)]
    for c in calls:                       # compile outside the trace
        jax.block_until_ready(c())
    tmp = tempfile.mkdtemp(prefix="trace_probe_")
    opts = profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    profiler.start_trace(tmp, profiler_options=opts)
    t0 = time.perf_counter()
    with profiler.TraceAnnotation("bench_window"):
        for _ in range(3):
            for c in calls:
                jax.block_until_ready(c())
            time.sleep(0.002)
    t1 = time.perf_counter()
    profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    os.makedirs(args.out, exist_ok=True)
    shutil.copy(path, os.path.join(args.out, "trace.xplane.pb"))
    pd = profiler.ProfileData.from_file(path)
    summary = {"window_host_s": t1 - t0, "planes": []}
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            evs = list(ln.events)
            names = collections.Counter(e.name for e in evs)
            first = [{"name": e.name, "start_ns": e.start_ns,
                      "duration_ns": e.duration_ns,
                      "stats": [[str(k), str(v)] for k, v in e.stats]}
                     for e in evs[:4]]
            lines.append({"name": ln.name, "n": len(evs),
                          "names": names.most_common(40), "first": first})
        summary["planes"].append({
            "name": pl.name, "stats": [[str(k), str(v)] for k, v in pl.stats],
            "lines": lines})
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"ok": True, "kind": jax.devices()[0].device_kind,
                      "bytes": os.path.getsize(path)}))
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

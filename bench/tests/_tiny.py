"""Each cell cut to a size the CPU runs in seconds (Pallas in interpret
mode): the phases and the reference are the full ones."""

import time

import _paths  # noqa: F401
from harness import runner
from harness.spec import Bench

YCSB = {"rows": 3000}
TPCC = {"warehouses": 2, "customers": 30, "items": 200}
SCALE = {
    "ycsb_zipf_lat": {"schema": YCSB, "traffic": {
        "rate_per_s": 200, "warm_max_cut": 16, "max_batch": 16}},
    "ycsb_unif_sat": {"schema": YCSB, "traffic": {
        "clients": 64, "warm_max_cut": 32, "max_batch": 32}},
    "tpcc_lat": {"schema": TPCC, "traffic": {
        "rate_per_s": 50, "warm_max_cut": 3}},
    "tpcc_sat": {"schema": TPCC, "traffic": {
        "clients": 20, "warm_max_cut": 3}},
}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def run(cell, seed=2**31 + 17, seconds=1.5, trace=False, wrap=None,
        root=None, scale=None):
    bench = Bench(root or _paths.ROOT)
    return runner.run(bench, cell, seed, seconds, trace, time.perf_counter(),
                      scale=scale or SCALE[cell], wrap=wrap, device=CPU,
                      say=lambda obj: None)

"""Run one cell once and build its result line.

The result is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``trace`` its per-layer
metrics), ``device``, with ``trace`` a ``breakdown``, and last ``checks``:
each number the plain reference compared, beside its limit.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import kernel_cost
from .cell import CellRun, Window, memory_peak_bytes
from .reference import Verdict
from .spec import Bench


@dataclass
class RunData:
    """Everything a per-layer metric's reader may read (``None`` where the
    run did not record it)."""

    window: Window
    stats: Dict                   # scheduler stats at the end of the window
    spans: object                 # repro.trace TraceDump of the window
    kernel_calls: object          # probes.KernelCalls of the traced window
    device_trace: object          # trace_reduce.DeviceTrace of the window
    peaks: Dict
    verdict: Verdict
    recovery: object              # repro.core.recovery.RecoveryReport

    def roofline_pct(self, kernel: str) -> Optional[float]:
        """Share of its roofline a kernel reached over the traced window:
        the least time its calls need, from their shapes, over the device
        time its programs took.  ``None`` where it never ran."""
        if self.device_trace is None or self.kernel_calls is None:
            return None
        calls = self.kernel_calls.of(kernel)
        runs, dev_ns = self.device_trace.kernel(kernel)
        if not calls or not runs or dev_ns <= 0:
            return None
        least = [kernel_cost.least_seconds(kernel, s, kw, self.peaks)[0]
                 for s, kw in calls]
        # each call is one program run; should the trace have lost some
        # runs, the mean call stands for each run it kept
        need = sum(least) * runs / len(calls)
        return 100.0 * need / (dev_ns * 1e-9)

    def device_idle_pct(self) -> Optional[float]:
        t = self.device_trace
        if t is None or not t.chips or not t.window_ns:
            return None
        return 100.0 * (1.0 - t.busy_ns() / t.window_ns)


def _pct(x: np.ndarray, q: float) -> Optional[float]:
    return float(np.percentile(x, q)) if len(x) else None


def end_to_end(run: CellRun, w: Window, recover_s: float) -> Dict[str, float]:
    return {"setup_s": run.setup_s, "goodput_txn_s": w.goodput(),
            "recover_s": recover_s,
            "commit_p50_ms": _pct(w.latencies_ms(), 50),
            "commit_p99_ms": _pct(w.latencies_ms(), 99)}


def breakdown(data: RunData, k: int = 10) -> Dict[str, List]:
    """Top device programs, and the longest idle gaps labelled with the
    program span the host was in (``no program span`` where it was in none:
    the client, the scheduler's poll, or the logger's sleep)."""
    from repro.trace.span import STAGE_NAMES

    t = data.device_trace
    ops = [[name, ns * 1e-9] for name, ns in t.top_modules(k)]
    gaps = []
    spans = data.spans
    w0 = t.window[0] if t.window else 0.0
    for s, e in t.idle_gaps(k):
        # host perf_counter seconds of the gap, from the window's start
        lo = data.window.t0 + (s - w0) * 1e-9
        hi = data.window.t0 + (e - w0) * 1e-9
        label = "no program span"
        if spans is not None and spans.n:
            ov = np.minimum(spans.t1, hi) - np.maximum(spans.t0, lo)
            if (ov > 0).any():
                label = STAGE_NAMES[int(spans.stage[int(np.argmax(ov))])]
        gaps.append([label, (e - s) * 1e-9])
    return {"device_ops": ops, "idle_gaps": gaps}


def run(bench: Bench, cell: str, seed: int, seconds: float, trace: bool,
        t_start: float, scale: Optional[Dict] = None, wrap=None,
        device: Optional[Dict] = None, say=print, record: bool = True
        ) -> Tuple[Dict, List[str]]:
    """One run of ``cell``; returns the result line and the check lines.
    ``say`` prints the earlier output lines (compile counts, collections).
    With ``record=False`` nothing is kept for the reference, no check runs
    and the result is not correct."""
    r = CellRun(bench, cell, seed, seconds, scale=scale, trace=trace,
                wrap=wrap, record=record)
    try:
        w = r.serve(seconds, t_start)
        gc_window = r.gc_pauses.summary()
        torn = r.crash()
        r.free_program()
        state, recover_s, rec_compiles = r.recover()
        peak = memory_peak_bytes()
        lat = w.latencies_ms()
        say({"load_s": r.load_s, "warm_s": r.warm_s,
             "latency_ms": {"n": len(lat), "p50": _pct(lat, 50),
                            "p99": _pct(lat, 99)},
             "warm_calls": r.warm_calls,
             "compiles": {"setup": r.setup_compiles, "window": w.compiles,
                          "recovery": rec_compiles},
             "gc_in_window": gc_window,
             "gc_in_recovery": r.gc_pauses.summary(),
             "record": record})
        v = r.check(torn, state) if record else Verdict(
            checks={"record_off": 1})
    finally:
        r.close()
    cell_doc = bench.cell(cell)
    dev = dict(device or {})
    dev["memory_peak_bytes"] = peak
    if trace:
        data = RunData(window=w, stats=r.stats, spans=r.spans,
                       kernel_calls=r.kernel_calls,
                       device_trace=r.device_trace,
                       peaks=(bench.peaks(dev["kind"])
                              if r.device_trace and r.device_trace.chips
                              else {}),
                       verdict=v, recovery=state.report)
        metrics = {}
        for m in bench.per_layer(cell_doc["name"]):
            x = bench.reader(m["name"])(data)
            if x is not None:
                metrics[m["name"]] = {"value": float(x), "unit": m["unit"]}
        if r.device_trace is not None and r.device_trace.chips:
            dev["busy_s"] = r.device_trace.busy_ns() * 1e-9
            dev["window_s"] = r.device_trace.window_ns * 1e-9
    else:
        values = end_to_end(r, w, recover_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in bench.end_to_end(cell_doc["name"])
                   if values.get(m["name"]) is not None}
    checks = {k: {"value": n, "limit": 0} for k, n in v.checks.items()}
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": w.n,
        "failed": w.failed(),
        "metrics": metrics,
        "device": dev,
    }
    if trace and r.device_trace is not None and r.device_trace.chips:
        result["breakdown"] = breakdown(data)
    result["checks"] = checks
    lines = [f"check {k}: {c['value']} (limit {c['limit']})"
             for k, c in checks.items()]
    return result, lines

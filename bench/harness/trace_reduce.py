"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

On a TPU the trace has one plane per chip, ``/device:TPU:<n>``, with a line
``XLA Modules`` (one event per program run, named ``jit_<function>(<id>)``)
and a line ``XLA Ops`` (one event per operation inside it).  The host plane
``/host:CPU`` carries the benchmark's ``TraceAnnotation`` that marks the
measured window.  All times are nanoseconds from the start of the profile.

* busy time — the union of the ``XLA Ops`` intervals inside the window,
  averaged over the chips;
* a kernel's device time — the summed durations of its ``XLA Modules``
  events;
* idle gaps — the stretches of the window no operation covers.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench_window"


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


@dataclass
class Chip:
    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    ops: List[Tuple[float, float]] = field(default_factory=list)


@dataclass
class DeviceTrace:
    chips: List[Chip]
    window: Optional[Tuple[float, float]]   # (start, end) ns on the host

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0] if self.window else 0.0

    def _clip(self, s: float, e: float) -> Tuple[float, float]:
        lo, hi = self.window if self.window else (s, e)
        return max(s, lo), min(e, hi)

    def busy_ns(self) -> float:
        """Union of operation intervals inside the window, mean over chips."""
        if not self.chips:
            return 0.0
        total = 0.0
        for c in self.chips:
            clipped = [self._clip(s, e) for s, e in c.ops]
            total += sum(e - s for s, e in _merge(
                [(s, e) for s, e in clipped if e > s]))
        return total / len(self.chips)

    def kernel(self, name: str) -> Tuple[int, float]:
        """``(runs, device ns)`` of ``jit_<name>`` programs in the whole
        trace, summed over chips.  Not clipped to the window: the device's
        clock in the trace is offset from the host's by up to a millisecond,
        and the kernel calls are recorded for the whole trace too."""
        n, t = 0, 0.0
        for c in self.chips:
            for m, s, e in c.modules:
                if m == name:
                    n += 1
                    t += e - s
        return n, t

    def top_modules(self, k: int = 10) -> List[Tuple[str, float]]:
        acc: Dict[str, float] = {}
        for c in self.chips:
            for m, s, e in c.modules:
                cs, ce = self._clip(s, e)
                if ce > cs:
                    acc[m] = acc.get(m, 0.0) + (ce - cs)
        return sorted(acc.items(), key=lambda kv: -kv[1])[:k]

    def idle_gaps(self, k: int = 10) -> List[Tuple[float, float]]:
        """The ``k`` longest stretches of the window with no operation
        running on chip 0, as ``(start, end)`` ns."""
        if not self.chips or not self.window:
            return []
        lo, hi = self.window
        merged = _merge([self._clip(s, e) for s, e in self.chips[0].ops
                         if self._clip(s, e)[1] > self._clip(s, e)[0]])
        gaps, cur = [], lo
        for s, e in merged:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            gaps.append((cur, hi))
        return sorted(gaps, key=lambda g: g[0] - g[1])[:k]


def module_base(name: str) -> str:
    """``jit_occ_seg_reduce(1522063152254680248)`` -> ``occ_seg_reduce``."""
    base = name.split("(", 1)[0]
    return base[4:] if base.startswith("jit_") else base


def load(path: str) -> DeviceTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips: List[Chip] = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = Chip()
            for line in plane.lines:
                if line.name == "XLA Modules":
                    chip.modules = [
                        (module_base(ev.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns) for ev in line.events]
                elif line.name == "XLA Ops":
                    chip.ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                                for ev in line.events]
            chips.append(chip)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    return DeviceTrace(chips=chips, window=window)

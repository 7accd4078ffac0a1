"""Run one benchmark cell once on the chip this machine holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, its traffic
mix and its per-layer metrics are found by name from ``BENCHMARK.json`` and
the files under ``bench/`` (see ``bench/harness/spec.py``).  The run refuses
anything but a TPU on which the Pallas kernels compile, and fewer chips
than the cell asks for: it then exits 2 and prints no result.

Standard output: earlier lines are progress (load and warm-up times, compile
counts in set-up, window and recovery); the last line is the result object.
Standard error ends with each number the reference compared and its limit.
JAX's persistent compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``,
or else in ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # process start, as near as Python gets

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))


class GateError(RuntimeError):
    """No chip this cell can run on."""


def device_gate(chips: int):
    """The devices JAX reports, or :class:`GateError` unless they are at
    least ``chips`` TPUs on which the Pallas kernels run compiled."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise GateError(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if os.environ.get("REPRO_FORCE_INTERPRET", "") not in ("", "0"):
        raise GateError("REPRO_FORCE_INTERPRET is set: the kernels would "
                        "run in interpret mode")
    if len(devs) < chips:
        raise GateError(f"the cell needs {chips} chips, JAX finds "
                        f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import runner
    from harness.spec import Bench, SpecError

    try:
        bench = Bench(ROOT)
        cell = bench.cell(args.workload)
        device = device_gate(int(cell["chips"]))
        bench.peaks(device["kind"])
    except (GateError, SpecError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    from repro.kernels import ops

    ops.enable_compile_cache()      # before the first compile
    result, lines = runner.run(
        bench, args.workload, args.seed, args.seconds, bool(args.trace),
        T_START, device=device,
        say=lambda obj: print(json.dumps(obj), flush=True))
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

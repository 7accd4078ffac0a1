"""Run one cell once with a fault planted under the served path.

    python bench/control.py --workload <cell> --seed <n> --seconds <s> \\
        [--fault early_ack | --record off]

The same run as ``bench/run.py`` (same gate, set-up, window, crash, recovery
and reference) with one fault of ``bench/faults.py`` between the scheduler
and the backend.  The default, ``early_ack``, is the control: transactions
are acknowledged before their records are durable.  A sound comparison
prints ``"correct": false`` for it.  The benchmark's own runs never plant a
fault.  ``--record off`` plants none and keeps nothing for the reference, to
show what the record costs the window (its collections); that run cannot be
checked.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

from run import ROOT, GateError, device_gate  # noqa: E402


def main(argv=None) -> int:
    from faults import FAULTS

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), default="early_ack")
    ap.add_argument("--record", choices=("on", "off"), default="on")
    args = ap.parse_args(argv)
    record = args.record == "on"

    from harness import runner
    from harness.spec import Bench

    bench = Bench(ROOT)
    try:
        device = device_gate(int(bench.cell(args.workload)["chips"]))
    except GateError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    from repro.kernels import ops

    ops.enable_compile_cache()
    result, lines = runner.run(
        bench, args.workload, args.seed, args.seconds, False, T_START,
        wrap=FAULTS[args.fault] if record else None, device=device,
        say=lambda obj: print(json.dumps(obj), flush=True), record=record)
    for line in lines:
        print(line, file=sys.stderr)
    result["fault"] = args.fault if record else None
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

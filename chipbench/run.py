"""Run one cell of the GANDSE chip benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
BENCHMARK.json (see chipbench/harness.py).  The last line of stdout is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or its per-layer metrics with ``--trace 1``),
``device`` and, traced, ``breakdown``; then ``checks``, each compared
number beside its limit.  Without a TPU, with fewer chips than the cell
asks for, or with a device kind missing from peaks.json, it exits 2 and
prints no result.

``--sweep r1,r2,...`` (open-loop mixes only) steps the offered rate, one
window of ``--seconds`` each, and prints per rate the achieved rate, the
backlog left at the window's close and the 95th percentile: the knee sweep
that fixes a mix's rate.  It prints no result line.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    clock = harness.Clock()
    clock.t0 = T0
    try:
        c = harness.cell(args.workload)
        harness.use_program()
        harness.compile_cache()
        from chipbench.compiles import CompileCounter
        counter = CompileCounter()
        drv = harness.driver(c["mix"]["driver"])
        if args.sweep:
            for row in drv.sweep(c, args, [float(r) for r in
                                           args.sweep.split(",")], counter):
                print(json.dumps(row), flush=True)
            return 0
        result, checks = drv.run(c, args, clock, counter)
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())

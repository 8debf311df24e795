"""The readings that set a cell's limits: the compared numbers of the
program over many seeds, and of the control on some of them, all in one
process so that set-up and compilation are paid once.

    python3 chipbench/readings.py --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds 3

Prints one JSON line per seed.  The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    c = harness.cell(args.workload)
    harness.use_program()
    harness.compile_cache()
    from chipbench.compiles import CompileCounter
    counter = CompileCounter()
    drv = harness.driver(c["mix"]["driver"])
    for row in drv.readings(c, ints(args.seeds), set(ints(args.control_seeds)),
                            args.seconds, counter):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""cellbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json once, in this process, on the machine it is
started on. The last line of standard output is the result; the last lines of
standard error are the numbers compared, each beside its limit. Exits with 3
and prints no result where JAX finds no TPU or fewer chips than the cell asks.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="directory to copy the .xplane.pb of a traced run into")
    args = ap.parse_args(argv)

    from cellbench import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                                  t0=T0, keep_trace=args.keep_trace)
    except harness.NoAccelerator as e:
        print(f"cellbench: no accelerator: {e}", file=sys.stderr)
        return 3
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())

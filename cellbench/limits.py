#!/usr/bin/env python3
"""The readings a cell's limits are set from, in one process on the chip.

    python3 cellbench/limits.py --workload W --seeds 1,2,...  [--control-seeds 7,8,9]
        [--set parity_precision=high --set-seeds 4,5,6] [--seconds 2] [--out FILE]

For each seed: one short run of the cell as it is timed (the lower readings),
then for `--control-seeds` the cell's control (the upper readings: the program
with its lower-precision path switched on, or the reference computed in
bfloat16 and put in the program's place, as the configuration's file says),
then for `--reference-control-seeds` the bfloat16 reference in the program's
place whatever the file names, then for `--set-seeds` the program under the
`--set` settings. Prints every
number compared; sets nothing.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--reference-control-seeds", default="")
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--set-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from cellbench import harness

    def seeds(text):
        return [int(s) for s in text.split(",") if s]

    extra = {}
    for item in args.set:
        key, _, value = item.partition("=")
        try:
            extra[key] = json.loads(value)
        except ValueError:
            extra[key] = value  # a bare word, as in parity_precision=high
    plan = ([("program", s, {}) for s in seeds(args.seeds)]
            + [("control", s, {}) for s in seeds(args.control_seeds)]
            + [("reference_bf16", s, {}) for s in seeds(args.reference_control_seeds)]
            + [("set:" + ",".join(args.set), s, extra) for s in seeds(args.set_seeds)])
    for kind, seed, settings in plan:
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               control={"control": True, "reference_bf16": "reference"}.get(kind, False),
                               settings=settings)
        row = {"workload": args.workload, "kind": kind, "seed": seed,
               "correct": res["correct"], "attempted": res["attempted"],
               "failed": res["failed"],
               "checks": {k: c["value"] for k, c in res["checks"].items()}}
        print("LIMITS " + json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Sets of runs of one cell, each run a fresh process, one after another.

    python3 cellbench/sets.py --workload W --seconds S --seeds 11,12,13 --sets 2 \
        [--trace 0|1] [--out chiprun_out/sets.jsonl]

Runs `cellbench/run.py` once per seed per set (the same seeds in every set),
appends each result line (with the run's notes) to `--out`, and prints for
every metric and set the median and the spread: the distance between the
first and third quartile (`statistics.quantiles(values, n=4)`) as a share of
the median. This process never imports JAX, so each child gets the chip.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values):
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else float("nan")


def one_run(workload, seed, seconds, trace, extra):
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)] + extra
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, capture_output=True, text=True)
    notes = {"process_s": time.perf_counter() - t0}
    for line in proc.stderr.splitlines():
        if line.startswith("notes "):
            notes.update(json.loads(line[6:]))
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        return {"rc": proc.returncode, "stderr": proc.stderr[-3000:], "notes": notes}
    return {"rc": 0, "result": json.loads(lines[-1]), "notes": notes}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    extra = ["--keep-trace", args.keep_trace] if args.keep_trace else []
    table = {}
    bad = 0
    for set_no in range(args.sets):
        for seed in seeds:
            run = one_run(args.workload, seed, args.seconds, args.trace, extra)
            run.update(workload=args.workload, seconds=args.seconds, seed=seed,
                       set=set_no, trace=args.trace)
            if args.out:
                os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(run) + "\n")
            if run["rc"] != 0:
                bad += 1
                print(f"RUN FAILED rc={run['rc']} seed={seed}\n{run['stderr']}", flush=True)
                continue
            res = run["result"]
            bad += 0 if res["correct"] else 1
            vals = {k: v["value"] for k, v in res["metrics"].items()}
            vals["h2d_GBps"] = run["notes"].get("h2d_bytes_per_s", 0.0) / 1e9
            vals["process_s"] = run["notes"]["process_s"]
            print(f"{args.workload} {args.seconds:g}s set{set_no} seed={seed} "
                  f"correct={res['correct']} n={res['attempted']} failed={res['failed']} "
                  + " ".join(f"{k}={v:.6g}" for k, v in vals.items())
                  + " checks=" + " ".join(f"{k}={c['value']:.3g}" for k, c in res["checks"].items())
                  + f" peak={res['device'].get('memory_peak_bytes')}"
                  + (f" busy={res['device'].get('busy_s'):.4g}/{res['device'].get('window_s'):.4g}"
                     if "busy_s" in res["device"] else ""), flush=True)
            for k, v in vals.items():
                table.setdefault(k, {}).setdefault(set_no, []).append(v)
    for k, per_set in table.items():
        for set_no, values in per_set.items():
            print(f"SPREAD {args.workload} {args.seconds:g}s {k} set{set_no} n={len(values)} "
                  f"median={statistics.median(values):.6g} spread={spread(values):.4%}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

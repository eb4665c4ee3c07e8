#
# Offline autotune CLI (docs/design.md §6i):
#
#   python -m spark_rapids_ml_tpu.autotune \
#       --knobs selection.tile,selection.strategy --shape 65536,64,16
#
# Searches the requested knobs over the requested shape buckets on the
# CURRENT backend and persists the winners into the per-platform tuning
# table under --dir / SRML_TPU_TUNE_DIR / autotune.dir. `--list` prints the
# knob registry. Runs inside a FitRun so, with SRML_TPU_METRICS_DIR set, the
# sweep exports a full structured run report (trial spans with their
# kernels' analyzed flops/bytes) like every other unit of work in this library.
#

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Tuple


def _parse_shape(raw: str) -> Tuple[int, int, int]:
    parts = [int(p) for p in raw.replace("x", ",").split(",") if p.strip()]
    if len(parts) != 3:
        raise argparse.ArgumentTypeError(
            f"--shape wants N,D,K (got '{raw}')"
        )
    return parts[0], parts[1], parts[2]


def main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m spark_rapids_ml_tpu.autotune",
        description="Search tuning-table entries for the current platform.",
    )
    ap.add_argument(
        "--knobs",
        help="comma-separated knob names (default: every searchable knob)",
    )
    ap.add_argument(
        "--shape", action="append", type=_parse_shape, metavar="N,D,K",
        help="shape bucket(s) to search (repeatable; default 65536,64,16)",
    )
    ap.add_argument("--dir", help="tuning-table directory (over config/env)")
    ap.add_argument("--replicates", type=int, help="timed reps per candidate")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--list", action="store_true",
                    help="print the knob registry and exit")
    ap.add_argument("--json", action="store_true",
                    help="print the full summary as JSON")
    args = ap.parse_args(argv)

    from . import knobs as _knobs

    if args.list:
        for name in sorted(_knobs.KNOBS):
            kb = _knobs.KNOBS[name]
            flags = []
            if kb.searchable:
                flags.append("searchable")
            if kb.exactness != "bit":
                flags.append(f"exactness={kb.exactness}")
            if kb.config_key:
                flags.append(f"pinned-by={kb.config_key}")
            print(f"{name:<24} [{kb.kind}] {' '.join(flags)}")
            print(f"{'':<24} {kb.description}")
        return 0

    from .. import config as _config

    if args.dir:
        _config.set("autotune.dir", args.dir)
    knob_names = (
        [k.strip() for k in args.knobs.split(",") if k.strip()]
        if args.knobs
        else None
    )

    from ..observability import fit_run
    from ..utils import enable_compile_cache

    from .search import run_search

    enable_compile_cache()

    with fit_run(algo="autotune_search", site="autotune"):
        summary = run_search(
            knob_names, shapes=args.shape, dtype=args.dtype,
            replicates=args.replicates,
        )
    if args.json:
        print(json.dumps(summary, indent=1, sort_keys=True))
        return 0
    print(
        f"autotune: platform={summary['platform']} "
        f"device_kind={summary['device_kind']} "
        f"table={summary['table_path'] or '(in-memory only)'} "
        f"entries={summary['table_entries']} "
        f"search_s={summary['search_s']}"
    )
    for e in summary["results"]:
        print(
            f"  {e['knob']:<24} {e['bucket']:<20} -> {e['value']!r:<14} "
            f"speedup={e['speedup']:.3f} "
            f"(median {e['median_s'] * 1e3:.2f} ms vs default "
            f"{e['baseline_s'] * 1e3:.2f} ms, {e['trials']} trials)"
        )
    for s in summary["skipped"]:
        print(f"  {s['knob']:<24} skipped: {s['reason']}")
    if summary["table_path"] is None:
        print(
            "autotune: WARNING no table directory configured "
            "(--dir / SRML_TPU_TUNE_DIR); results were not persisted"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

#!/usr/bin/env bash
#
# Benchmark orchestration — the TPU-VM analog of the reference's
# python/run_benchmark.sh (reference run_benchmark.sh:99-120: mode selection,
# default shapes, per-algorithm scaling rules) without the CSP-specific cluster
# scripts (a TPU VM is one host owning its chips; no Databricks/Dataproc/EMR split).
#
# Usage:
#   benchmark/run_benchmark.sh [tpu|cpu] [all|<bench> ...] [--num_rows N] [--num_cols N]
#
# tpu mode runs on the attached TPU and FAILS when JAX finds none (a speed comes
# from the chip or not at all); cpu mode forces the virtual 8-device CPU mesh (the
# CI smoke configuration: correctness and counts, never a speed). Results append
# to benchmark/results/report.csv and each bench prints its timing + quality
# line. The chip benchmark the driver records is another thing: cellbench/run.py.
#
set -euo pipefail
cd "$(dirname "$0")/.."

MODE="${1:-cpu}"; shift || true
BENCHES="${1:-all}"; shift || true

NUM_ROWS=100000
NUM_COLS=64
EXTRA=()
while [ $# -gt 0 ]; do
  case "$1" in
    --num_rows) NUM_ROWS="$2"; shift 2;;
    --num_cols) NUM_COLS="$2"; shift 2;;
    *) EXTRA+=("$1"); shift;;
  esac
done

if [ "$MODE" = "cpu" ]; then
  export JAX_PLATFORMS=cpu
  export XLA_FLAGS="--xla_force_host_platform_device_count=8"
  # CI-smoke shapes (reference defaults 5000x3000 scaled to the suite budget)
  NUM_ROWS=${NUM_ROWS:-20000}
elif [ "$MODE" = "tpu" ]; then
  python - <<'PY'
import sys
import jax
dev = jax.devices()[0]
if dev.platform != "tpu":
    sys.exit(f"run_benchmark.sh tpu: jax.devices()[0].platform is {dev.platform!r}, not 'tpu'")
print(f"run_benchmark.sh tpu: {dev.device_kind} x {len(jax.devices())}")
PY
else
  echo "usage: benchmark/run_benchmark.sh [tpu|cpu] ..." >&2
  exit 2
fi

REPORT_DIR=benchmark/results
mkdir -p "$REPORT_DIR"

if [ "$BENCHES" = "all" ]; then
  BENCHES="kmeans pca linear_regression logistic_regression random_forest_classifier random_forest_regressor knn approximate_nearest_neighbors umap dbscan"
fi

# per-algorithm scaling rules (the quadratic/neighbor algorithms get smaller rows,
# reference run_benchmark.sh:99-120)
scaled_rows() {
  case "$1" in
    knn|approximate_nearest_neighbors|umap|dbscan) echo $(( NUM_ROWS / 10 > 1000 ? NUM_ROWS / 10 : 1000 ));;
    *) echo "$NUM_ROWS";;
  esac
}

for b in $BENCHES; do
  rows=$(scaled_rows "$b")
  echo "== $b (rows=$rows cols=$NUM_COLS mode=$MODE) =="
  python benchmark/benchmark_runner.py "$b" \
    --num_rows "$rows" --num_cols "$NUM_COLS" --no_cpu \
    --report_path "$REPORT_DIR/report.csv" "${EXTRA[@]}"
done

echo "report: $REPORT_DIR/report.csv"

"""Compile-only probe, outside any cell of BENCHMARK.json: what the chip's
compiler makes of `ops/kmeans.py::lloyd_fit` at a given (rows, cols, k), without
a chip. Compiles the program for one described v5e chip (topology `v5e:2x2`;
libtpu is installed here) with `unit_weight` off and on, the assignment as
`_lloyd` would route it at that shape (ranked at three passes with a six-pass
second look from 128 centres on: `recheck` rows), and once more at six passes
whole, and prints one JSON line a variant: every fusion's `estimated_cycles` as
the compiler's own cost model gives them (a `fusion` whose cycles double runs
twice the MXU passes; the ranking fusion is the one beside the `high,high` dot,
the second look the small `highest,highest` one, and a `copy` of X's size is
the table moved to rows-major for the gather, once a fit), the precision of
each dot, and the program's temporaries (`temp_bytes` holds that copy). Counts
of work, never a speed: at 357,376 x 3000, k=1000 and 1.5 GHz the estimate was
within 1 % of the chip for the distance matmul and the three-pass update and
7 % under it for the six-pass update (PERF.md §5, §6 PR 29), and a time still
comes only from a chip run.

    JAX_PLATFORMS=cpu python -m tools.lloyd_passes [rows cols k [parity]]

Defaults: 357376 3000 1000 highest (the `kmeans_k1000_d3000` cell). About 30 s
a shape; not a tier-1 test (it loads libtpu, which one process at a time may).
"""

from __future__ import annotations

import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.ops.kmeans import _second_look_rows, lloyd_fit

MAX_ITER = 30
_CYCLES = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?\"estimated_cycles\":\"(\d+)\"", re.M
)
_PRECISION = re.compile(r"operand_precision=\{([a-z0-9,]+)\}")
# the ranking reduction's four outputs (least lower end, its centre,
# second-least lower end, least upper end): its running state, all of which
# has to stay float32 (an unread one is stored in bf16: ops/kmeans.py::_rank3)
_RANKING_STATE = re.compile(
    r"= \((\w+)\[\d+\]\{[^}]*\}, (s32)\[\d+\]\{[^}]*\}, (\w+)\[\d+\]\{[^}]*\}, (\w+)\[\d+\]\{[^}]*\}\) reduce\("
)
# below this the instruction is a pass over centres, not over rows
MIN_CYCLES = 100_000


def probe(rows: int, cols: int, k: int, unit_weight: bool, one_chip,
          recheck: int = 0) -> dict:
    """One compile of `lloyd_fit`; the numbers of its optimised HLO."""
    f32 = jnp.float32
    X = jax.ShapeDtypeStruct((rows, cols), f32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((rows,), f32, sharding=one_chip)
    init = jax.ShapeDtypeStruct((k, cols), f32, sharding=one_chip)
    compiled = lloyd_fit.lower(
        X, w, init, 1e-20, MAX_ITER, unit_weight=unit_weight, recheck=recheck
    ).compile()
    text = compiled.as_text()
    fusions = {
        name: int(cycles)
        for name, cycles in _CYCLES.findall(text)
        if int(cycles) >= MIN_CYCLES
    }
    return {
        "rows": rows, "cols": cols, "k": k, "unit_weight": unit_weight,
        "recheck": recheck,
        "parity_precision": str(config.get("parity_precision")),
        "estimated_cycles": dict(sorted(fusions.items(), key=lambda kv: -kv[1])),
        "dot_precisions": sorted(_PRECISION.findall(text)),
        "ranking_state": [list(m) for m in _RANKING_STATE.findall(text)],
        # a described chip runs nothing: there is no call for the device plane to attribute
        "temp_bytes": int(compiled.memory_analysis().temp_size_in_bytes),  # noqa: fence/device-analysis-off-plane
    }


def main(argv) -> int:
    rows, cols, k = (int(a) for a in argv[:3]) if len(argv) >= 3 else (357376, 3000, 1000)
    if len(argv) >= 4:
        config.set("parity_precision", argv[3])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    X = jax.ShapeDtypeStruct((rows, cols), jnp.float32, sharding=one_chip)
    recheck, _ = _second_look_rows(X, k, cosine=False, fast_math=False)
    variants = [(False, recheck), (True, recheck)]
    if recheck:
        variants.append((True, 0))  # six passes whole, to read the routed one against
    for unit_weight, rows_looked_at in variants:
        print(json.dumps(probe(rows, cols, k, unit_weight, one_chip, rows_looked_at)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

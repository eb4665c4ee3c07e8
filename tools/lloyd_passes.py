"""Compile-only probe, outside any cell of BENCHMARK.json: what the chip's
compiler makes of `ops/kmeans.py::lloyd_fit` at a given (rows, cols, k), without
a chip. Compiles the program for one described v5e chip (topology `v5e:2x2`;
libtpu is installed here) with `unit_weight` off and on, and prints one JSON
line a variant: every fusion's `estimated_cycles` as the compiler's own cost
model gives them (a `fusion` whose cycles double runs twice the MXU passes), the
precision of each dot, and the program's temporaries. Counts of work, never a
speed: at 357,376 x 3000, k=1000 and 1.5 GHz the estimate was within 1 % of
the chip for the distance matmul and the three-pass update and 7 % under it
for the six-pass update (PERF.md §5, §6 PR 29), and a time still comes only
from a chip run.

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
from spark_rapids_ml_tpu.ops.kmeans import lloyd_fit

MAX_ITER = 30
_CYCLES = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?\"estimated_cycles\":\"(\d+)\"", re.M
)
_PRECISION = re.compile(r"operand_precision=\{([a-z0-9,]+)\}")
# below this the instruction is a pass over centres, not over rows
MIN_CYCLES = 100_000


def probe(rows: int, cols: int, k: int, unit_weight: bool, one_chip) -> dict:
    """One compile of `lloyd_fit`; the numbers of its optimised HLO."""
    f32 = jnp.float32
    X = jax.ShapeDtypeStruct((rows, cols), f32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((rows,), f32, sharding=one_chip)
    init = jax.ShapeDtypeStruct((k, cols), f32, sharding=one_chip)
    compiled = lloyd_fit.lower(
        X, w, init, 1e-20, MAX_ITER, unit_weight=unit_weight
    ).compile()
    text = compiled.as_text()
    fusions = {
        name: int(cycles)
        for name, cycles in _CYCLES.findall(text)
        if int(cycles) >= MIN_CYCLES
    }
    return {
        "rows": rows, "cols": cols, "k": k, "unit_weight": unit_weight,
        "parity_precision": str(config.get("parity_precision")),
        "estimated_cycles": dict(sorted(fusions.items(), key=lambda kv: -kv[1])),
        "dot_precisions": sorted(_PRECISION.findall(text)),
        # a described chip runs nothing: there is no call for the device plane to attribute
        "temp_bytes": int(compiled.memory_analysis().temp_size_in_bytes),  # noqa: fence/device-analysis-off-plane
    }


def main(argv) -> int:
    rows, cols, k = (int(a) for a in argv[:3]) if len(argv) >= 3 else (357376, 3000, 1000)
    if len(argv) >= 4:
        config.set("parity_precision", argv[3])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for unit_weight in (False, True):
        print(json.dumps(probe(rows, cols, k, unit_weight, one_chip)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

#
# Closed-loop autotuner (docs/design.md §6i): telemetry-driven knob search
# with persisted per-platform tuning tables.
#
# The observability arc (§6f device cost capture, §6g live telemetry, §6h comm
# plane) measured everything a tuner needs; this package spends it. Three
# pieces:
#
#   knobs.py    the knob REGISTRY — every tunable the ops/serving host
#               wrappers consult (selection strategy/tile, pallas geometry
#               and thresholds, Lloyd gate, serving buckets, cache budget),
#               with its candidate grid and exactness class — and lookup(),
#               the single resolution entry point. Resolution order:
#               programmatic config.set() > env > tuning table > default.
#   table.py    persisted per-(platform, device_kind) tables: versioned
#               JSON under `autotune.dir` / SRML_TPU_TUNE_DIR, atomic
#               writes, corrupt/stale fall-through to defaults (counted),
#               loaded once per process.
#   search.py   the measurement loop: candidates timed through the §6f
#               compiled_kernel AOT cache inside `autotune.trial` spans,
#               median + MAD noise floor (`autotune.noise_mads`).
#   defaults.py the knob-registry defaults module — the one home for the
#               numeric tile/threshold defaults ops/ used to hard-code
#               (the analyzer, tools/analysis, enforces the split).
#
# Offline: `python -m spark_rapids_ml_tpu.autotune` searches and persists.
# Online: `autotune.mode` = off | load (default) | search.
#
# This __init__ stays import-light (no jax): ops modules import it at call
# time inside host wrappers.
#

from .defaults import default_select_tile
from .knobs import (
    KNOBS,
    Knob,
    bucket_for,
    lookup,
    report_section,
    reset,
    shape_bucket,
)
from .table import (
    TABLE_VERSION,
    TuningTable,
    entry_key,
    load_table,
    platform_key,
    table_path,
)

__all__ = [
    "KNOBS",
    "Knob",
    "TABLE_VERSION",
    "TuningTable",
    "bucket_for",
    "default_select_tile",
    "entry_key",
    "load_table",
    "lookup",
    "platform_key",
    "report_section",
    "reset",
    "shape_bucket",
    "table_path",
]

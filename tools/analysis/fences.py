#
# The ten plane-fences and the flat hygiene checks, migrated out of
# ci/lint_python.py into the shared rule registry (docs/design.md §6j) so the
# repo has ONE analyzer, one suppression grammar (`# noqa: <rule-id>`), and
# one CI tier. Semantics are the pre-migration ones; what changed is that a
# suppression must now NAME the rule it waives.
#

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set

from .core import AnalysisContext, ModuleInfo, register_pass, register_rule

# --------------------------------------------------------------- rule catalog

register_rule(
    "hygiene/syntax-error",
    "file does not parse",
    "Every target file must compile. Fix the syntax error; nothing else in "
    "this file was analyzed.",
)
register_rule(
    "hygiene/tab-indent",
    "tab character in indentation",
    "The tree indents with spaces; a stray tab breaks diffs and (in mixed "
    "lines) the parser. Replace with spaces.",
)
register_rule(
    "hygiene/bare-except",
    "bare `except:`",
    "A bare except catches SystemExit/KeyboardInterrupt too. Catch "
    "`Exception` (or the narrow type you mean).",
)
register_rule(
    "hygiene/mutable-default",
    "mutable default argument",
    "A list/dict/set default is created once and shared across calls. "
    "Default to None and construct inside the function.",
)
register_rule(
    "hygiene/undefined-all-export",
    "__all__ name that doesn't resolve",
    "A name exported in __all__ is neither defined nor imported in the "
    "module — `from m import *` would raise. Fix the name or the export.",
)
register_rule(
    "hygiene/unused-import",
    "unused import",
    "The imported name is never referenced. Delete it, or — for deliberate "
    "re-exports — suppress with `# noqa: hygiene/unused-import`.",
)
register_rule(
    "fence/silent-except",
    "broad except whose body only passes",
    """
A broad handler (`except:` / `except Exception:` / `except BaseException:`)
whose body is only pass/... hides failures the reliability subsystem exists
to surface — it must at least log. Narrow typed catches stay legal control
flow; the reliability package (which implements handling policy) is exempt.
Suppress a deliberate best-effort site with `# noqa: fence/silent-except`.
""",
)
register_rule(
    "fence/uncached-stream",
    "_batch_stream in a loop without cache=",
    """
A direct `_batch_stream(...)` call inside a for/while loop re-uploads every
batch on every pass, bypassing the HBM batch cache (ops/device_cache.py).
Pass a `cache=` handle (passes 2..N replay from HBM) or hoist the stream out
of the loop.
""",
)
register_rule(
    "fence/profiling-internals",
    "profiling._counters/_spans poked outside observability",
    """
Those dicts no longer exist — profiling.py is a compat shim over the typed
registry (observability/registry.py); historically direct mutation corrupted
scoped FitRun accounting. Go through the public surface (count/span/
counter_totals/...) or the observability API.
""",
)
register_rule(
    "fence/jit-in-models",
    "jax.jit inside spark_rapids_ml_tpu/models/",
    """
Model-layer predict calls must route through
observability.inference.predict_dispatch (uniform metric names,
shape-bucket/recompile-sentinel telemetry); jitted kernels belong in ops/,
where the dispatch helper wraps them.
""",
)
register_rule(
    "fence/topk-off-plane",
    "direct top-k primitive in ops/ outside ops/selection.py",
    """
Every search-plane top-k routes through ops/selection.py (select_topk /
merge_topk / top_k_max) so the strategy knob, the invalid-sentinel
convention, and the selection telemetry can never be bypassed.
""",
)
register_rule(
    "fence/pallas-off-plane",
    "pallas import/pallas_call outside ops/pallas_*.py",
    """
Raw Pallas kernels carry per-toolchain workarounds (Mosaic precision
emulation, ragged-edge masking, VMEM budgets) and parity contracts that live
with the kernel modules — a pallas_call elsewhere bypasses the
interpret-mode gates, the compiled_kernel telemetry routing, and the §5b/§5c
sentinel/tie-order contracts.
""",
)
register_rule(
    "fence/http-off-plane",
    "http.server/ThreadingHTTPServer outside observability/server.py",
    """
The telemetry endpoint is THE driver-resident HTTP plane (refcounted
lifecycle, loopback default, zero threads when disabled, §6g); other planes
mount path-prefix handlers on it via register_mount rather than binding a
second socket.
""",
)
register_rule(
    "fence/device-analysis-off-plane",
    "cost_analysis/memory_analysis/memory_stats outside observability/device.py",
    """
The device-performance plane (docs/design.md §6f) owns XLA cost/memory
capture and HBM sampling — including the graceful degrade when a runtime
lacks them; a direct call elsewhere bypasses the capture contract AND the
no-warning-spam guarantee. Route through compiled_kernel / sample_hbm.
""",
)
register_rule(
    "fence/hlo-parse-off-plane",
    "HLO collective-op text pattern outside observability/comm.py",
    """
The communication plane (docs/design.md §6h) is the ONE HLO-text parser:
ad-hoc regexes drift from the exporter's collective accounting (exactly what
happened to the pre-§6h tests/test_collective_counts.py). Route through
extract_collectives / collectives_of_computation. Prose mentions of the
opcodes don't match.
""",
)
register_rule(
    "fence/host-staging-copy",
    "host staging copy in ops/ outside ops/ingest.py",
    """
`np.ascontiguousarray(...)` or a sliced-block `.astype(...)` in ops/ stages
batch data through a fresh, uncounted host copy, bypassing the zero-copy
ingest plane (ops/ingest.py::stage_block, docs/design.md §6k): contiguous
device-castable slices should upload as views with the dtype conversion
riding the device, and genuine copy fallbacks should go through the counted
staging pool. Suppress a deliberate host copy (e.g. an init slice mutated in
place before upload) with `# noqa: fence/host-staging-copy`.
""",
)
register_rule(
    "fence/hardcoded-tunable",
    "hard-coded tunable tile/block/threshold constant in ops/",
    """
Numeric tile/block/threshold DEFAULTS live in the knob-registry defaults
module (spark_rapids_ml_tpu/autotune/defaults.py, docs/design.md §6i); their
measured per-platform overrides live in tuning tables. A fresh literal in
ops/ is a knob the autotuner can't see and a re-tuning chore on the next
hardware target. Zero-valued sentinels (`BLOCK_ROWS = 0` = adaptive) stay
legal.
""",
)

# ------------------------------------------------------------------ constants

UNUSED_IMPORT_EXEMPT = {"__init__.py"}
SILENT_SWALLOW_EXEMPT_PARTS = ("reliability",)
PROFILING_INTERNALS = {"_counters", "_spans"}
PROFILING_INTERNALS_EXEMPT_PARTS = ("observability", "profiling.py")
_BROAD_EXC_NAMES = {"Exception", "BaseException"}
_TOPK_PRIMS = {"top_k", "approx_max_k"}
_DEVICE_ANALYSIS = {"cost_analysis", "memory_analysis", "memory_stats"}
_HLO_PARSE_RE = re.compile(
    r"(?:all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start|\\?\()"  # the checker's own pattern; tools/analysis is rule-exempt
)
_TUNABLE_NAME_RE = re.compile(r"(TILE|BLOCK|MIN_ITEMS|MIN_K|BUCKET)")


def _const_int(node: ast.AST) -> Optional[int]:
    """Evaluate a literal int expression (`2048`, `1 << 16`, `8 * 1024`);
    None for anything else — only plain numeric literals are banned."""
    if isinstance(node, ast.Constant):
        return node.value if (
            isinstance(node.value, int) and not isinstance(node.value, bool)
        ) else None
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = _const_int(node.operand)
        return -v if v is not None else None
    if isinstance(node, ast.BinOp):
        left, right = _const_int(node.left), _const_int(node.right)
        if left is None or right is None:
            return None
        try:
            if isinstance(node.op, ast.LShift):
                return left << right
            if isinstance(node.op, ast.Mult):
                return left * right
            if isinstance(node.op, ast.Add):
                return left + right
            if isinstance(node.op, ast.Sub):
                return left - right
            if isinstance(node.op, ast.Pow):
                return left ** right
            if isinstance(node.op, ast.FloorDiv):
                return left // right
        except (OverflowError, ZeroDivisionError, ValueError):
            return None
    return None


def _is_broad_catch(type_node: Optional[ast.AST]) -> bool:
    if type_node is None:
        return True
    if isinstance(type_node, ast.Name):
        return type_node.id in _BROAD_EXC_NAMES
    if isinstance(type_node, ast.Tuple):
        return any(_is_broad_catch(elt) for elt in type_node.elts)
    return False


def _is_silent_body(body: List[ast.stmt]) -> bool:
    for stmt in body:
        if isinstance(stmt, ast.Pass):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Constant):
            continue  # docstring or `...`
        return False
    return True


def _in_lib(mod: ModuleInfo) -> bool:
    return mod.rel.startswith("spark_rapids_ml_tpu/")


# ------------------------------------------------------------------- the pass


@register_pass("fences")
def run(ctx: AnalysisContext) -> None:
    for mod in ctx.index.files:
        if mod.parse_error is not None:
            ctx.emit("hygiene/syntax-error", mod, 1,
                     f"syntax error: {mod.parse_error}")
            continue
        assert mod.tree is not None
        _check_hygiene(ctx, mod)
        _check_fences(ctx, mod)


def _check_hygiene(ctx: AnalysisContext, mod: ModuleInfo) -> None:
    tree = mod.tree
    for lineno, line in enumerate(mod.lines, 1):
        if line.lstrip(" ").startswith("\t"):
            ctx.emit("hygiene/tab-indent", mod, lineno, "tab in indentation")

    imports: Dict[str, int] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            if isinstance(node, ast.ImportFrom) and node.module == "__future__":
                continue
            for alias in node.names:
                name = alias.asname or alias.name.split(".")[0]
                if name != "*":
                    imports.setdefault(name, node.lineno)
        elif isinstance(node, ast.ExceptHandler):
            if node.type is None:
                ctx.emit("hygiene/bare-except", mod, node.lineno,
                         "bare `except:` (catch Exception)")
            if (
                node.type is not None
                and _is_broad_catch(node.type)
                and _is_silent_body(node.body)
                and not any(p in SILENT_SWALLOW_EXEMPT_PARTS
                            for p in mod.path.parts)
            ):
                ctx.emit(
                    "fence/silent-except", mod, node.lineno,
                    "silent exception swallowing (broad `except ...: pass` "
                    "with no logging)",
                )
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for default in list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None
            ]:
                if isinstance(default, (ast.List, ast.Dict, ast.Set)):
                    ctx.emit("hygiene/mutable-default", mod, default.lineno,
                             f"mutable default argument in {node.name}()")

    used: Set[str] = set()
    exported: Set[str] = set()
    export_line = 1
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Assign)
            and any(getattr(t, "id", "") == "__all__" for t in node.targets)
            and isinstance(node.value, (ast.List, ast.Tuple))
        ):
            export_line = node.lineno
            for elt in node.value.elts:
                if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                    exported.add(elt.value)

    module_names = {
        n.name for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef))
    }
    top_assigned = {
        getattr(t, "id", None)
        for node in tree.body if isinstance(node, ast.Assign)
        for t in node.targets
    }
    for name in sorted(exported):
        if (name not in module_names and name not in top_assigned
                and name not in imports):
            ctx.emit("hygiene/undefined-all-export", mod, export_line,
                     f"__all__ name '{name}' is not defined")

    if mod.path.name not in UNUSED_IMPORT_EXEMPT:
        for name, lineno in imports.items():
            if name not in used and name not in exported:
                ctx.emit("hygiene/unused-import", mod, lineno,
                         f"unused import '{name}'")


def _check_fences(ctx: AnalysisContext, mod: ModuleInfo) -> None:
    tree = mod.tree
    parts = mod.path.parts
    in_lib = _in_lib(mod)

    # uncached multi-pass re-ingest
    class _Stream(ast.NodeVisitor):
        def __init__(self) -> None:
            self.loop_depth = 0

        def _loop(self, node: ast.AST) -> None:
            self.loop_depth += 1
            self.generic_visit(node)
            self.loop_depth -= 1

        visit_For = visit_AsyncFor = visit_While = _loop

        def visit_Call(self, node: ast.Call) -> None:
            func = node.func
            name = (
                func.id if isinstance(func, ast.Name)
                else func.attr if isinstance(func, ast.Attribute) else ""
            )
            if (
                name == "_batch_stream"
                and self.loop_depth > 0
                and not any(kw.arg == "cache" for kw in node.keywords)
            ):
                ctx.emit(
                    "fence/uncached-stream", mod, node.lineno,
                    "_batch_stream call inside a loop without a cache= "
                    "handle (multi-pass re-ingest bypassing ops/device_cache)",
                )
            self.generic_visit(node)

    _Stream().visit(tree)

    # jax.jit in models/
    if "models" in parts and in_lib:
        for node in ast.walk(tree):
            hit = None
            if (
                isinstance(node, ast.Attribute) and node.attr == "jit"
                and isinstance(node.value, ast.Name)
                and node.value.id == "jax"
            ):
                hit = "jax.jit"
            elif (
                isinstance(node, ast.ImportFrom) and node.module
                and node.module.split(".")[0] == "jax"
                and any(a.name == "jit" for a in node.names)
            ):
                hit = "from jax import jit"
            if hit:
                ctx.emit(
                    "fence/jit-in-models", mod, node.lineno,
                    f"{hit} in models/ — route predict calls through "
                    "observability.inference.predict_dispatch (jitted "
                    "kernels belong in ops/)",
                )

    # top-k primitives outside ops/selection.py
    if "ops" in parts and in_lib and mod.path.name != "selection.py":
        for node in ast.walk(tree):
            hit = None
            if (
                isinstance(node, ast.Attribute) and node.attr in _TOPK_PRIMS
                and (
                    (isinstance(node.value, ast.Attribute)
                     and node.value.attr == "lax")
                    or (isinstance(node.value, ast.Name)
                        and node.value.id == "lax")
                )
            ):
                hit = f"direct {node.attr}"
            elif (
                isinstance(node, ast.ImportFrom) and node.module == "jax.lax"
                and any(a.name in _TOPK_PRIMS for a in node.names)
            ):
                hit = "from jax.lax import top_k/approx_max_k"
            if hit:
                ctx.emit(
                    "fence/topk-off-plane", mod, node.lineno,
                    f"{hit} in ops/ — route top-k through ops/selection.py "
                    "(select_topk/merge_topk/top_k_max)",
                )

    # hard-coded tunables in ops/
    if "ops" in parts and in_lib:
        for node in tree.body:
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            else:
                continue
            names = [
                t.id for t in targets
                if isinstance(t, ast.Name) and _TUNABLE_NAME_RE.search(t.id)
            ]
            if not names:
                continue
            v = _const_int(value)
            if not v:  # zero = adaptive sentinel, None = not a literal
                continue
            ctx.emit(
                "fence/hardcoded-tunable", mod, node.lineno,
                f"hard-coded tunable '{names[0]} = {v}' in ops/ — numeric "
                "tile/threshold defaults live in spark_rapids_ml_tpu/"
                "autotune/defaults.py (knob registry, docs/design.md §6i); "
                "import it or declare a knob",
            )

    # host staging copies in ops/ outside the ingest plane
    if "ops" in parts and in_lib and mod.path.name != "ingest.py":
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            hit = None
            if (
                isinstance(func, ast.Attribute)
                and func.attr == "ascontiguousarray"
            ) or (isinstance(func, ast.Name) and func.id == "ascontiguousarray"):
                hit = "ascontiguousarray(...)"
            elif (
                isinstance(func, ast.Attribute)
                and func.attr == "astype"
                and isinstance(func.value, ast.Subscript)
            ):
                hit = "sliced-block .astype(...)"
            if hit:
                ctx.emit(
                    "fence/host-staging-copy", mod, node.lineno,
                    f"{hit} in ops/ — block staging goes through the counted "
                    "zero-copy ingest plane (ops/ingest.py::stage_block / "
                    "StagingPool, docs/design.md §6k)",
                )

    # pallas outside ops/pallas_*.py
    if not ("ops" in parts and in_lib and mod.path.name.startswith("pallas_")):
        for node in ast.walk(tree):
            hit = None
            if isinstance(node, ast.Import) and any(
                a.name.startswith("jax.experimental.pallas")
                for a in node.names
            ):
                hit = "import jax.experimental.pallas"
            elif isinstance(node, ast.ImportFrom) and (
                (node.module or "").startswith("jax.experimental.pallas")
                or (node.module == "jax.experimental"
                    and any(a.name == "pallas" for a in node.names))
            ):
                hit = "from jax.experimental import pallas"
            elif isinstance(node, ast.Attribute) and node.attr == "pallas_call":
                hit = "direct pallas_call"
            if hit:
                ctx.emit(
                    "fence/pallas-off-plane", mod, node.lineno,
                    f"{hit} outside ops/pallas_*.py — Pallas kernels live in "
                    "the pallas kernel modules (interpret gates, Mosaic "
                    "workarounds, §5c parity contracts); route through their "
                    "host wrappers",
                )

    # http.server outside observability/server.py
    if not (mod.path.name == "server.py" and "observability" in parts):
        for node in ast.walk(tree):
            hit = None
            if isinstance(node, ast.Import) and any(
                a.name == "http.server" or a.name.startswith("http.server.")
                for a in node.names
            ):
                hit = "import http.server"
            elif isinstance(node, ast.ImportFrom) and (
                (node.module or "") == "http.server"
                or (node.module or "").startswith("http.server.")
                or (node.module == "http"
                    and any(a.name == "server" for a in node.names))
            ):
                hit = "from http.server import ..."
            elif (
                isinstance(node, (ast.Name, ast.Attribute))
                and (getattr(node, "id", None) == "ThreadingHTTPServer"
                     or getattr(node, "attr", None) == "ThreadingHTTPServer")
            ):
                hit = "ThreadingHTTPServer reference"
            if hit:
                ctx.emit(
                    "fence/http-off-plane", mod, node.lineno,
                    f"{hit} outside observability/server.py — one HTTP plane "
                    "only; mount handlers on it via observability.server."
                    "register_mount (docs/design.md §6g/§7)",
                )

    # device analysis outside observability/device.py
    if not (mod.path.name == "device.py" and "observability" in parts):
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) and node.attr in _DEVICE_ANALYSIS:
                ctx.emit(
                    "fence/device-analysis-off-plane", mod, node.lineno,
                    f"direct .{node.attr}() outside observability/device.py "
                    "— route through the device-performance plane "
                    "(compiled_kernel / sample_hbm, docs/design.md §6f)",
                )

    # HLO collective text outside observability/comm.py (and the analyzer,
    # which implements this very check)
    if not (
        (mod.path.name == "comm.py" and "observability" in parts)
        or mod.rel.startswith("tools/analysis/")
    ):
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)):
                continue
            if not _HLO_PARSE_RE.search(node.value):
                continue
            ctx.emit(
                "fence/hlo-parse-off-plane", mod, node.lineno,
                "HLO collective-op text pattern in a string literal — "
                "collective parsing lives in observability/comm.py only "
                "(extract_collectives / collectives_of_computation, "
                "docs/design.md §6h)",
                noqa_lines=[getattr(node, "end_lineno", node.lineno)],
            )

    # profiling internals outside observability/profiling
    if not any(p in PROFILING_INTERNALS_EXEMPT_PARTS for p in parts):
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Attribute)
                and node.attr in PROFILING_INTERNALS
                and isinstance(node.value, ast.Name)
                and node.value.id == "profiling"
            ):
                ctx.emit(
                    "fence/profiling-internals", mod, node.lineno,
                    f"direct use of profiling.{node.attr} (the dict no "
                    "longer exists — go through the profiling/observability "
                    "public surface)",
                )

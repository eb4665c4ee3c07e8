#
# Trace-purity pass (docs/design.md §6j): the PR-5/PR-13 host-wrapper
# discipline, machine-checked. Config/knob/env/clock/randomness reads execute
# at TRACE time, not run time — inside a `compiled_kernel` impl, a Pallas
# kernel body, or a function handed to lax.map/scan/while_loop/fori_loop/cond
# or shard_map, the value read is BAKED into the cached executable and every
# later call replays the stale choice (the stale-bake hazard; resolution
# belongs in the host wrapper). This pass:
#
#   1. seeds the intra-package call graph with every traced entry point,
#   2. walks reachability through resolved call edges (lambdas handed to the
#      trace constructs are scanned in their enclosing scope),
#   3. flags impure reads and module-global mutation anywhere reachable.
#
# There is no legitimate grandfathering for these findings: the baseline for
# purity/* must stay EMPTY (a stale-baked knob is a silent wrong answer at
# multi-host scale, not a style issue). Fix the wrapper, or scope a noqa with
# a justification on the single line that is provably trace-safe.
#

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .callgraph import CallGraph, FunctionInfo, get_callgraph, _body_nodes
from .core import AnalysisContext, register_pass, register_rule

register_rule(
    "purity/config-read",
    "config read reachable from traced code",
    """
`config.get()` / `config.source()` executes at trace time inside a
compiled_kernel impl / Pallas body / lax-control-flow function, baking the
current value into the cached executable — later `config.set()` calls are
silently ignored by every cache hit (the PR-13 stale-bake hazard). Resolve
the knob in the HOST wrapper and pass the value in as a (static) argument.
Suppress only a provably trace-safe line with `# noqa: purity/config-read`.
""",
)
register_rule(
    "purity/env-read",
    "os.environ read reachable from traced code",
    """
Environment reads inside traced code bake the process environment at first
trace into the executable cache. Read the env in the host wrapper (or through
config.py, which owns env resolution) and pass the value in.
""",
)
register_rule(
    "purity/time-read",
    "wall-clock read reachable from traced code",
    """
`time.time()`/`perf_counter()` inside traced code measures TRACE time once,
then replays that constant forever — timings computed from it are fiction
after the first call. Time in the host wrapper, around the compiled call.
""",
)
register_rule(
    "purity/random-read",
    "host randomness reachable from traced code",
    """
`random.*` / `np.random.*` inside traced code draws ONE sample at trace time
and bakes it — every cached call replays the same "random" value, and the
draw is invisible to jax's key discipline. Use `jax.random` with an explicit
key argument, or draw in the host wrapper and pass the value in.
""",
)
register_rule(
    "purity/global-write",
    "module-global mutation reachable from traced code",
    """
A `global` write inside traced code fires once at trace time and never again
on cache hits — state updates silently stop happening, exactly the class of
bug that is a test flake single-host and a pod-wide wrong answer multi-host.
Return the value instead, or move the mutation to the host wrapper.
""",
)

# traced-seed packages: the library itself (tests deliberately poke impure
# paths in host harness code; benchmark drives hosts)
_SEED_PKG = "spark_rapids_ml_tpu"

# host-plane boundary: reachability does NOT descend INTO these modules.
# A traced function calling config.get is flagged AT THE
# CALL SITE (that's the finding); walking into the host plane's own
# implementation would re-report the same root cause against config.py's
# internals (os.environ inside config.get) and drown the signal.
_BOUNDARY_PREFIXES = (
    "spark_rapids_ml_tpu.config",
    "spark_rapids_ml_tpu.observability",
    "spark_rapids_ml_tpu.reliability",
    "spark_rapids_ml_tpu.profiling",
    "spark_rapids_ml_tpu.utils",
)


def _crosses_boundary(cg: CallGraph, caller: str, callee: str) -> bool:
    caller_mod = cg.functions[caller].module.name or ""
    callee_fi = cg.functions.get(callee)
    callee_mod = (callee_fi.module.name or "") if callee_fi else callee
    if caller_mod == callee_mod:
        return False  # a boundary module's own seeds still walk themselves
    return callee_mod.startswith(_BOUNDARY_PREFIXES)

_TIME_FNS = {
    "time", "perf_counter", "monotonic", "time_ns", "perf_counter_ns",
    "monotonic_ns", "process_time", "process_time_ns",
}

# jax.lax control-flow constructs and which positional args are traced bodies
_LAX_BODY_ARGS = {
    "map": (0,),
    "scan": (0,),
    "while_loop": (0, 1),
    "fori_loop": (2,),
    "cond": (1, 2),
    "switch": None,  # every arg from 1 on is a branch
}


def _attr_base_name(node: ast.Attribute) -> Optional[str]:
    return node.value.id if isinstance(node.value, ast.Name) else None


def _import_target(cg: CallGraph, fi: FunctionInfo, name: str) -> Optional[str]:
    return cg.imports.get(fi.module.name or "", {}).get(name)


class _Hazard:
    __slots__ = ("rule", "line", "what")

    def __init__(self, rule: str, line: int, what: str):
        self.rule, self.line, self.what = rule, line, what


def _function_hazards(cg: CallGraph, fi: FunctionInfo,
                      nodes: Optional[List[ast.AST]] = None) -> List[_Hazard]:
    """Direct impure reads / global writes lexically inside fi (nested defs
    excluded — they are their own graph nodes)."""
    out: List[_Hazard] = []
    body = nodes if nodes is not None else cg.body_nodes(fi)
    assigned: Set[str] = set()
    globals_decl: List[Tuple[ast.Global, Tuple[str, ...]]] = []
    for node in body:
        if isinstance(node, ast.Global):
            globals_decl.append((node, tuple(node.names)))
        elif isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (
                node.targets if isinstance(node, ast.Assign) else [node.target]
            )
            for t in targets:
                if isinstance(t, ast.Name):
                    assigned.add(t.id)
        if isinstance(node, ast.Attribute):
            base = _attr_base_name(node)
            if base == "os" and node.attr in ("environ", "getenv"):
                out.append(_Hazard("purity/env-read", node.lineno,
                                   f"os.{node.attr}"))
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Attribute):
            base = _attr_base_name(func)
            target = _import_target(cg, fi, base) if base else None
            if func.attr in ("get", "source") and (
                target == "spark_rapids_ml_tpu.config"
                or base in ("_config",)
            ):
                out.append(_Hazard("purity/config-read", node.lineno,
                                   f"{base}.{func.attr}(...)"))
            elif base is not None and target == "time" and func.attr in _TIME_FNS:
                out.append(_Hazard("purity/time-read", node.lineno,
                                   f"{base}.{func.attr}()"))
            elif base is not None and target == "random":
                out.append(_Hazard("purity/random-read", node.lineno,
                                   f"{base}.{func.attr}()"))
            elif (
                isinstance(func.value, ast.Attribute)
                and func.value.attr == "random"
                and isinstance(func.value.value, ast.Name)
                and _import_target(cg, fi, func.value.value.id) == "numpy"
            ):
                out.append(_Hazard("purity/random-read", node.lineno,
                                   f"{func.value.value.id}.random."
                                   f"{func.attr}()"))
        elif isinstance(func, ast.Name):
            target = _import_target(cg, fi, func.id)
            if target and target.startswith("time.") and (
                target.split(".", 1)[1] in _TIME_FNS
            ):
                out.append(_Hazard("purity/time-read", node.lineno,
                                   f"{func.id}()"))
            elif target and target.startswith("random."):
                out.append(_Hazard("purity/random-read", node.lineno,
                                   f"{func.id}()"))
    for gnode, names in globals_decl:
        written = [n for n in names if n in assigned]
        if written:
            out.append(_Hazard("purity/global-write", gnode.lineno,
                               f"global {', '.join(written)}"))
    return out


def _is_compiled_kernel_deco(deco: ast.AST) -> bool:
    node = deco.func if isinstance(deco, ast.Call) else deco
    name = (
        node.id if isinstance(node, ast.Name)
        else node.attr if isinstance(node, ast.Attribute) else ""
    )
    return name == "compiled_kernel"


def _is_shard_map_partial_deco(deco: ast.AST) -> bool:
    """`@functools.partial(shard_map, mesh=...)` — the tree's idiom for
    shard-mapped local functions."""
    if not (isinstance(deco, ast.Call) and deco.args):
        return False
    fname = (
        deco.func.attr if isinstance(deco.func, ast.Attribute)
        else deco.func.id if isinstance(deco.func, ast.Name) else ""
    )
    if fname != "partial":
        return False
    first = deco.args[0]
    name = (
        first.id if isinstance(first, ast.Name)
        else first.attr if isinstance(first, ast.Attribute) else ""
    )
    return name in ("shard_map", "pallas_call")


def _callee_name(call: ast.Call) -> str:
    f = call.func
    return (
        f.id if isinstance(f, ast.Name)
        else f.attr if isinstance(f, ast.Attribute) else ""
    )


def _traced_fn_args(call: ast.Call) -> List[ast.AST]:
    """Positional args of `call` that are traced function bodies."""
    name = _callee_name(call)
    out: List[ast.AST] = []
    if name == "pallas_call" and call.args:
        out.append(call.args[0])
    elif name == "shard_map" and call.args:
        out.append(call.args[0])
    elif name in _LAX_BODY_ARGS:
        f = call.func
        base = (
            f.value.id if isinstance(f, ast.Attribute)
            and isinstance(f.value, ast.Name) else
            f.value.attr if isinstance(f, ast.Attribute)
            and isinstance(f.value, ast.Attribute) else ""
        )
        if base != "lax":
            return out
        idxs = _LAX_BODY_ARGS[name]
        if idxs is None:  # switch: branches from arg 1 on
            out.extend(call.args[1:])
        else:
            out.extend(call.args[i] for i in idxs if i < len(call.args))
    elif name == "partial" and call.args:
        inner = call.args[0]
        iname = (
            inner.id if isinstance(inner, ast.Name)
            else inner.attr if isinstance(inner, ast.Attribute) else ""
        )
        if iname in ("shard_map", "pallas_call") and len(call.args) > 1:
            out.append(call.args[1])
    return out


def _seed_functions(cg: CallGraph) -> Dict[str, str]:
    """qualname -> seed kind, for every traced entry point in the package."""
    seeds: Dict[str, str] = {}
    for q, fi in cg.functions.items():
        if not (fi.module.name or "").startswith(_SEED_PKG):
            continue
        node = fi.node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for deco in node.decorator_list:
                if _is_compiled_kernel_deco(deco):
                    seeds[q] = "compiled_kernel impl"
                elif _is_shard_map_partial_deco(deco):
                    seeds[q] = "shard_map body"
    # functions PASSED to trace constructs (pallas_call/lax.*/shard_map)
    for q, fi in cg.functions.items():
        if not (fi.module.name or "").startswith(_SEED_PKG):
            continue
        for call, _resolved in fi.calls:
            for arg in _traced_fn_args(call):
                if isinstance(arg, ast.Name):
                    tq = cg.resolve_name(fi, arg.id)
                    if tq and tq not in seeds:
                        kind = _callee_name(call)
                        seeds[tq] = f"fn passed to {kind}"
    return seeds


@register_pass("purity")
def run(ctx: AnalysisContext) -> None:
    cg = get_callgraph(ctx)
    seeds = _seed_functions(cg)

    hazard_cache: Dict[str, List[_Hazard]] = {}

    def hazards_of(q: str) -> List[_Hazard]:
        if q not in hazard_cache:
            hazard_cache[q] = _function_hazards(cg, cg.functions[q])
        return hazard_cache[q]

    # BFS from each traced seed through resolved call edges; remember the
    # shortest chain for the message. A function reachable from several seeds
    # reports once per distinct hazard site.
    reported: Set[Tuple[str, str, int]] = set()
    for seed_q in sorted(seeds):
        kind = seeds[seed_q]
        chain: Dict[str, Tuple[str, ...]] = {seed_q: (seed_q,)}
        frontier = [seed_q]
        while frontier:
            nxt: List[str] = []
            for q in frontier:
                fi = cg.functions.get(q)
                if fi is None:
                    continue
                # lambdas handed to trace constructs inside this function
                lam_nodes: List[ast.AST] = []
                for call, _r in fi.calls:
                    for arg in _traced_fn_args(call):
                        if isinstance(arg, ast.Lambda):
                            lam_nodes.extend(_body_nodes(arg))
                hs = list(hazards_of(q))
                if lam_nodes:
                    hs.extend(_function_hazards(cg, fi, nodes=lam_nodes))
                for h in hs:
                    key = (h.rule, fi.module.rel, h.line)
                    if key in reported:
                        continue
                    reported.add(key)
                    via = chain[q]
                    path = " -> ".join(p.split(".")[-1] for p in via[-3:])
                    ctx.emit(
                        h.rule, fi.module, h.line,
                        f"{h.what} is reachable from traced seed "
                        f"`{seed_q.split('.', 1)[-1]}` ({kind}"
                        + (f"; via {path}" if len(via) > 1 else "")
                        + ") — resolve in the host wrapper and pass the "
                        "value in",
                    )
                for callee, _line in cg.edges.get(q, ()):
                    if (
                        callee not in chain
                        and callee.startswith(_SEED_PKG)
                        and not _crosses_boundary(cg, q, callee)
                    ):
                        chain[callee] = chain[q] + (callee,)
                        nxt.append(callee)
            frontier = nxt

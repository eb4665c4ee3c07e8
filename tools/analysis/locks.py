#
# Lock-graph pass (docs/design.md §6j): the ~20 locks across the serving
# registry / device cache / observability runs / autotune table planes are
# correct today by convention; this pass makes the two conventions checkable:
#
#   * locks/order-cycle — build a lock-ORDER graph (edge A->B when B is
#     acquired, directly or through a resolved call chain, while A is held)
#     and report every cycle. A cycle is a deadlock waiting for the right
#     thread interleaving — a hung barrier at pod scale. Self-edges on
#     RLocks are legal re-entry and skipped; a self-edge on a plain Lock is a
#     guaranteed self-deadlock and reported.
#
#   * locks/blocking-under-lock — device execution (calls into
#     compiled_kernel-decorated impls or .block_until_ready()), file I/O,
#     HTTP, sleeps, subprocesses, and queue.get() without a timeout performed
#     while a REGISTRY or CACHE lock is held. These locks sit on the serving
#     hot path and the metric write fan-out; blocking under one turns every
#     concurrent request/emitter into a convoy.
#
# Lock identity is static: module-level `_lock = threading.Lock()` becomes
# `<module>._lock`, `self._lock` in class C becomes `<module>.C._lock`.
# Acquisitions through unresolvable objects (`obj._lock` on a parameter) are
# recorded for blocking checks but excluded from order edges — a guessed
# identity would fabricate cycles.
#

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from .callgraph import FunctionInfo, get_callgraph
from .core import AnalysisContext, register_pass, register_rule

register_rule(
    "locks/order-cycle",
    "lock-order cycle (deadlock) across the threaded planes",
    """
Two (or more) locks are acquired in opposite orders on different code paths —
with the right thread interleaving each thread holds one and waits forever on
the other. Fix by imposing one global order (acquire the cycle's locks in a
single canonical sequence everywhere) or by narrowing one critical section so
the nested acquisition happens after release. A self-cycle on a non-reentrant
Lock means the function (or a callee) re-acquires a lock the caller already
holds: make it an RLock only if re-entry is genuinely intended; usually the
inner acquisition should move to a _locked() variant called under the lock.
""",
)
register_rule(
    "locks/blocking-under-lock",
    "blocking operation while holding a registry/cache lock",
    """
Device execution, file I/O, HTTP, sleeps, or an untimed queue.get() runs
while a registry or cache lock is held. Every other thread that touches that
plane (serving requests, metric emitters, eviction) convoys behind the slow
operation — the §7 serving path budget assumes lock hold times are
microseconds. Move the slow work outside the critical section (snapshot under
the lock, operate after release), or pass a timeout. Suppress a deliberate
case with `# noqa: locks/blocking-under-lock` and a justification.
""",
)

_LOCK_CTORS = {"Lock", "RLock", "Condition", "Semaphore", "BoundedSemaphore"}

# lock identities (substring match) that guard the serving/metric hot paths
_HOT_LOCK_PATTERNS = (
    "serving.registry.",
    "serving.http.",
    "ops.device_cache",
    "observability.registry.",
    "observability.runs.",
    "observability.device",
    "autotune.table",
)

_BLOCKING_TIME = {"sleep"}


def _short_mod(name: str) -> str:
    return name[len("spark_rapids_ml_tpu."):] if name.startswith(
        "spark_rapids_ml_tpu."
    ) else name


@dataclass
class _LockMeta:
    rlock: bool = False


@dataclass
class _FnLocks:
    # (lock_id, held_before tuple, line)
    acquires: List[Tuple[str, Tuple[str, ...], int]] = field(default_factory=list)
    # (callee qualname, held tuple, line)
    calls: List[Tuple[str, Tuple[str, ...], int]] = field(default_factory=list)
    # (kind, held tuple, line)
    blocking: List[Tuple[str, Tuple[str, ...], int]] = field(default_factory=list)


class _LockPass:
    def __init__(self, ctx: AnalysisContext):
        self.ctx = ctx
        self.cg = get_callgraph(ctx)
        self.locks: Dict[str, _LockMeta] = {}
        self.kernel_fns: Set[str] = set()
        self.fn_locks: Dict[str, _FnLocks] = {}

    # ------------------------------------------------------- lock discovery

    def _discover_locks(self) -> None:
        for mod in self.ctx.index.files:
            if mod.tree is None or not mod.name:
                continue
            short = _short_mod(mod.name)
            cls_stack: List[str] = []

            def visit(node: ast.AST, cls: Optional[str]) -> None:
                for child in ast.iter_child_nodes(node):
                    nxt_cls = cls
                    if isinstance(child, ast.ClassDef):
                        nxt_cls = child.name
                    if isinstance(child, ast.Assign) and isinstance(
                        child.value, ast.Call
                    ):
                        ctor = child.value.func
                        cname = (
                            ctor.attr if isinstance(ctor, ast.Attribute)
                            else ctor.id if isinstance(ctor, ast.Name) else ""
                        )
                        if cname in _LOCK_CTORS:
                            rlock = cname == "RLock"
                            for t in child.targets:
                                if isinstance(t, ast.Name):
                                    owner = f"{short}.{cls}" if cls else short
                                    self.locks[f"{owner}.{t.id}"] = _LockMeta(rlock)
                                elif (
                                    isinstance(t, ast.Attribute)
                                    and isinstance(t.value, ast.Name)
                                    and t.value.id == "self"
                                    and cls
                                ):
                                    self.locks[f"{short}.{cls}.{t.attr}"] = (
                                        _LockMeta(rlock)
                                    )
                    visit(child, nxt_cls)

            visit(mod.tree, None)

    def _discover_kernels(self) -> None:
        from .purity import _is_compiled_kernel_deco

        for q, fi in self.cg.functions.items():
            node = fi.node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(_is_compiled_kernel_deco(d) for d in node.decorator_list):
                    self.kernel_fns.add(q)

    # --------------------------------------------------- per-function facts

    def _lock_id(self, fi: FunctionInfo, expr: ast.AST) -> Optional[str]:
        """Identity of a lock-looking with/acquire expression; None when the
        expression isn't lock-shaped; '?<attr>' for lock-shaped but
        unresolvable (counted for blocking, excluded from ordering)."""
        short = _short_mod(fi.module.name or "")
        if isinstance(expr, ast.Name):
            if "lock" not in expr.id.lower():
                return None
            mid = f"{short}.{expr.id}"
            if mid in self.locks:
                return mid
            # not a discovered module lock (a parameter, a local): lock-shaped
            # but unresolvable — counted for blocking, excluded from ordering
            # (a guessed identity with unknown RLock-ness would fabricate
            # self-deadlock findings on legal re-entrant code)
            return f"?{expr.id}"
        if isinstance(expr, ast.Attribute):
            if "lock" not in expr.attr.lower():
                return None
            if isinstance(expr.value, ast.Name) and expr.value.id == "self" and (
                fi.class_name
            ):
                cid = f"{short}.{fi.class_name}.{expr.attr}"
                return cid
            if isinstance(expr.value, ast.Name):
                # Module attr: `_table._lock` style
                target = self.cg.imports.get(fi.module.name or "", {}).get(
                    expr.value.id
                )
                if target:
                    tid = f"{_short_mod(target)}.{expr.attr}"
                    if tid in self.locks:
                        return tid
            return f"?{expr.attr}"
        return None

    def _blocking_kind(self, fi: FunctionInfo, call: ast.Call,
                       resolved: Optional[str]) -> Optional[str]:
        func = call.func
        kwnames = {kw.arg for kw in call.keywords}
        if resolved is not None and resolved in self.kernel_fns:
            return f"device execution ({resolved.split('.')[-1]})"
        if isinstance(func, ast.Name):
            if func.id == "open":
                return "file I/O (open)"
            target = self.cg.imports.get(fi.module.name or "", {}).get(func.id)
            if target in ("urllib.request.urlopen",):
                return "HTTP (urlopen)"
        if isinstance(func, ast.Attribute):
            base = (
                func.value.id if isinstance(func.value, ast.Name) else None
            )
            target = (
                self.cg.imports.get(fi.module.name or "", {}).get(base)
                if base else None
            )
            if func.attr == "sleep" and (target == "time" or base == "time"):
                return "time.sleep"
            if func.attr == "urlopen":
                return "HTTP (urlopen)"
            if func.attr in ("run", "check_output", "check_call", "Popen") and (
                target == "subprocess" or base == "subprocess"
            ):
                return "subprocess"
            if func.attr == "block_until_ready":
                return "device sync (block_until_ready)"
            if (
                func.attr == "get"
                and base is not None
                and ("queue" in base.lower() or base.lower().endswith("_q"))
                and "timeout" not in kwnames
                and not call.args  # q.get(0.5) positional timeout
            ):
                return f"untimed {base}.get()"
        return None

    def _analyze_function(self, q: str, fi: FunctionInfo) -> _FnLocks:
        facts = _FnLocks()

        def walk(stmts: List[ast.stmt], held: Tuple[str, ...]) -> None:
            for node in stmts:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue  # nested defs are their own graph nodes
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    new_held = held
                    for item in node.items:
                        lid = self._lock_id(fi, item.context_expr)
                        if lid is not None:
                            facts.acquires.append((lid, new_held, node.lineno))
                            new_held = new_held + (lid,)
                        else:
                            # `with open(...)` under a lock is still file I/O
                            self._scan_tree(item.context_expr, fi, facts, held)
                    walk(node.body, new_held)
                    continue
                # other compound statements: recurse into bodies with the
                # same held set; scan this statement's own expressions
                self._scan_exprs(node, fi, facts, held)
                for attr in ("body", "orelse", "finalbody"):
                    sub = getattr(node, attr, None)
                    if sub:
                        walk(sub, held)
                for h in getattr(node, "handlers", []):
                    walk(h.body, held)

        if isinstance(fi.node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            walk(fi.node.body, ())
        return facts

    def _scan_exprs(self, stmt: ast.stmt, fi: FunctionInfo, facts: _FnLocks,
                    held: Tuple[str, ...]) -> None:
        """Calls/acquires in the EXPRESSION part of one statement (compound
        statements' bodies are walked separately so held-sets stay right)."""
        blocks = {"body", "orelse", "finalbody", "handlers"}
        stack: List[ast.AST] = []
        for name, value in ast.iter_fields(stmt):
            if name in blocks:
                continue
            if isinstance(value, ast.AST):
                stack.append(value)
            elif isinstance(value, list):
                stack.extend(v for v in value if isinstance(v, ast.AST))
        self._scan_stack(stack, fi, facts, held)

    def _scan_tree(self, root: ast.AST, fi: FunctionInfo, facts: _FnLocks,
                   held: Tuple[str, ...]) -> None:
        self._scan_stack([root], fi, facts, held)

    def _scan_stack(self, stack: List[ast.AST], fi: FunctionInfo,
                    facts: _FnLocks, held: Tuple[str, ...]) -> None:
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            if isinstance(node, ast.Call):
                func = node.func
                if isinstance(func, ast.Attribute) and func.attr == "acquire":
                    lid = self._lock_id(fi, func.value)
                    if lid is not None:
                        facts.acquires.append((lid, held, node.lineno))
                kind = None
                resolved = self.cg.resolve_call(fi, node)
                kind = self._blocking_kind(fi, node, resolved)
                if kind is not None:
                    facts.blocking.append((kind, held, node.lineno))
                elif resolved is not None:
                    facts.calls.append((resolved, held, node.lineno))
            stack.extend(ast.iter_child_nodes(node))

    # ------------------------------------------------------------ summaries

    def _transitive(self) -> Tuple[
        Dict[str, Dict[str, Tuple[str, ...]]],
        Dict[str, List[Tuple[str, Tuple[str, ...]]]],
    ]:
        """Per function: transitively acquired locks (lock -> witness chain of
        qualnames) and transitive blocking ops (kind, chain). Depth-limited
        fixpoint over the call graph."""
        acq: Dict[str, Dict[str, Tuple[str, ...]]] = {}
        blk: Dict[str, List[Tuple[str, Tuple[str, ...]]]] = {}
        for q, facts in self.fn_locks.items():
            acq[q] = {lid: (q,) for lid, _h, _l in facts.acquires
                      if not lid.startswith("?")}
            blk[q] = [(kind, (q,)) for kind, held, _l in facts.blocking]
        for _round in range(8):  # call chains deeper than 8 don't exist here
            changed = False
            for q, facts in self.fn_locks.items():
                for callee, _held, _line in facts.calls:
                    for lid, chain in acq.get(callee, {}).items():
                        if lid not in acq[q]:
                            acq[q][lid] = (q,) + chain
                            changed = True
                    for kind, chain in blk.get(callee, []):
                        if all(k != kind for k, _c in blk[q]):
                            blk[q].append((kind, (q,) + chain))
                            changed = True
            if not changed:
                break
        return acq, blk

    # ---------------------------------------------------------------- main

    def run(self) -> None:
        self._discover_locks()
        self._discover_kernels()
        for q, fi in self.cg.functions.items():
            if isinstance(fi.node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.fn_locks[q] = self._analyze_function(q, fi)
        acq, blk = self._transitive()

        # ---- order edges: (a, b) -> witness (qualname, line, via)
        edges: Dict[Tuple[str, str], Tuple[str, int, Tuple[str, ...]]] = {}
        for q, facts in self.fn_locks.items():
            for lid, held, line in facts.acquires:
                if lid.startswith("?"):
                    continue
                for h in held:
                    if h.startswith("?"):
                        continue
                    if h == lid:
                        if not self.locks.get(lid, _LockMeta()).rlock:
                            self._emit_self_deadlock(q, lid, line)
                        continue
                    edges.setdefault((h, lid), (q, line, (q,)))
            for callee, held, line in facts.calls:
                for lid, chain in acq.get(callee, {}).items():
                    for h in held:
                        if h.startswith("?"):
                            continue
                        if h == lid:
                            if not self.locks.get(lid, _LockMeta()).rlock:
                                self._emit_self_deadlock(q, lid, line,
                                                         via=chain)
                            continue
                        edges.setdefault((h, lid), (q, line, chain))

        self._report_cycles(edges)

        # ---- blocking under hot locks
        reported: Set[Tuple[str, int]] = set()
        for q, facts in self.fn_locks.items():
            fi = self.cg.functions[q]
            for kind, held, line in facts.blocking:
                hot = [h for h in held if _is_hot(h)]
                if hot and (fi.module.rel, line) not in reported:
                    reported.add((fi.module.rel, line))
                    self.ctx.emit(
                        "locks/blocking-under-lock", fi.module, line,
                        f"{kind} while holding {hot[0]} — move the slow "
                        "work outside the critical section",
                    )
            for callee, held, line in facts.calls:
                hot = [h for h in held if _is_hot(h)]
                if not hot:
                    continue
                for kind, chain in blk.get(callee, []):
                    if (fi.module.rel, line) in reported:
                        continue
                    reported.add((fi.module.rel, line))
                    via = " -> ".join(c.split(".")[-1] for c in chain[:4])
                    self.ctx.emit(
                        "locks/blocking-under-lock", fi.module, line,
                        f"call chain performs {kind} while holding "
                        f"{hot[0]} (via {via}) — move the slow work outside "
                        "the critical section",
                    )

    def _emit_self_deadlock(self, q: str, lid: str, line: int,
                            via: Tuple[str, ...] = ()) -> None:
        fi = self.cg.functions[q]
        extra = (
            " (via " + " -> ".join(c.split(".")[-1] for c in via[:4]) + ")"
            if via else ""
        )
        self.ctx.emit(
            "locks/order-cycle", fi.module, line,
            f"non-reentrant lock {lid} re-acquired while already held"
            f"{extra} — self-deadlock; use the _locked() pattern or an RLock",
        )

    def _report_cycles(
        self,
        edges: Dict[Tuple[str, str], Tuple[str, int, Tuple[str, ...]]],
    ) -> None:
        graph: Dict[str, Set[str]] = {}
        for (a, b) in edges:
            graph.setdefault(a, set()).add(b)
            graph.setdefault(b, set())
        # iterative Tarjan SCC
        index: Dict[str, int] = {}
        low: Dict[str, int] = {}
        on_stack: Set[str] = set()
        stack: List[str] = []
        counter = [0]
        sccs: List[List[str]] = []

        def strongconnect(v: str) -> None:
            work = [(v, iter(sorted(graph[v])))]
            index[v] = low[v] = counter[0]
            counter[0] += 1
            stack.append(v)
            on_stack.add(v)
            while work:
                node, it = work[-1]
                advanced = False
                for w in it:
                    if w not in index:
                        index[w] = low[w] = counter[0]
                        counter[0] += 1
                        stack.append(w)
                        on_stack.add(w)
                        work.append((w, iter(sorted(graph[w]))))
                        advanced = True
                        break
                    elif w in on_stack:
                        low[node] = min(low[node], index[w])
                if advanced:
                    continue
                work.pop()
                if work:
                    low[work[-1][0]] = min(low[work[-1][0]], low[node])
                if low[node] == index[node]:
                    comp = []
                    while True:
                        w = stack.pop()
                        on_stack.discard(w)
                        comp.append(w)
                        if w == node:
                            break
                    if len(comp) > 1:
                        sccs.append(sorted(comp))

        for v in sorted(graph):
            if v not in index:
                strongconnect(v)

        for comp in sccs:
            # pick a representative edge inside the SCC for the location
            witness = None
            for (a, b), w in sorted(edges.items()):
                if a in comp and b in comp:
                    witness = (a, b, w)
                    break
            if witness is None:
                continue
            a, b, (q, line, chain) = witness
            fi = self.cg.functions[q]
            ctx_chain = " -> ".join(c.split(".")[-1] for c in chain[:4])
            self.ctx.emit(
                "locks/order-cycle", fi.module, line,
                f"lock-order cycle among {{{', '.join(comp)}}}: here "
                f"{a} is held while acquiring {b} (via {ctx_chain}); "
                "another path acquires them in the reverse order — impose "
                "one canonical order",
            )


def _is_hot(lock_id: str) -> bool:
    return any(p in lock_id for p in _HOT_LOCK_PATTERNS)


@register_pass("locks")
def run(ctx: AnalysisContext) -> None:
    _LockPass(ctx).run()

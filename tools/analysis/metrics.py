#
# Metric-contract pass (docs/design.md §6j): the PR 3–13 telemetry arc made
# `name{label=}` metric keys the join surface between the library, CI smokes,
# bench gates, dashboards, and docs — and nothing checked that both sides of
# the join still exist. This pass harvests:
#
#   EMISSIONS — every Counter/Gauge/Histogram/span write with a literal name:
#     the fan-out helpers (counter_inc/gauge_set/gauge_inc/gauge_dec/observe/
#     add_span_total), the legacy shims (count/legacy_count), the
#     registry getters (.counter("x")/.gauge("x")/.histogram("x")), and
#     span("x"). Label KEYS come from the call's keyword arguments. A dynamic
#     site (non-literal name) can declare itself with a pragma comment:
#     `# srml-metric: name{key1,key2}` on or above the emitting line.
#
#   CONSUMPTIONS — metric-shaped string literals (`ns.name` dotted grammar,
#     first segment restricted to an emitted namespace) in the consumer
#     corpora: tests/, ci/ (the test.sh heredoc smokes), benchmark/, and the
#     docs (docs/*.md, README.md); and what the benchmark's metric files
#     (cellbench/metrics/*.json) read of the program: `counter` with the keys
#     of `labels`, and the span a `span` label or field names.
#
# and reports three contract breaks:
#   metrics/consumed-unemitted — a consumer references a name no library code
#     emits (the pre-§6h test_collective_counts.py failure mode).
#   metrics/label-mismatch — one name emitted with conflicting label-key sets
#     (neither a subset of the other): the exported series would split.
#   metrics/undocumented — an emitted name appearing in no doc file; the
#     catalog lives in docs/metrics.md.
#

from __future__ import annotations

import ast
import json
import re
from typing import Dict, List, Optional, Set, Tuple

from .core import (
    AnalysisContext,
    Finding,
    ModuleInfo,
    register_pass,
    register_rule,
)

register_rule(
    "metrics/consumed-unemitted",
    "metric name consumed but never emitted",
    """
A test assertion, CI smoke, bench gate, or doc references a metric name that
no code emits — the consumer is asserting on a key that can never appear
(green-by-vacuity for `sum(v for k if k.startswith(...))` shapes, red forever
for exact-key asserts). Either the metric was renamed (update the consumer)
or the emission was deleted (delete the consumer). Dynamic emission sites can
declare their names with a `# srml-metric: name{label1,label2}` pragma.
""",
)
register_rule(
    "metrics/label-mismatch",
    "one metric name emitted with conflicting label-key sets",
    """
Two emission sites write the same metric name with label-key sets where
neither is a subset of the other. The exported series splits into disjoint
key spaces: `name{a=}` and `name{b=}` never aggregate, and a dashboard's
greps silently see half the data. Pick one label schema per name
(a site may ADD labels to a common core, but not swap them). Also reported: a
benchmark metric file (cellbench/metrics/*.json) that reads a counter by a
label key no emission site of that counter writes — its reader would sum
nothing.
""",
)
register_rule(
    "metrics/undocumented",
    "emitted metric name documented nowhere",
    """
A metric is emitted but appears in no doc file (docs/*.md, README.md) — the
telemetry surface grew without the catalog. Add the name (with its labels and
one-line meaning) to docs/metrics.md. The catalog is what makes a dashboard
buildable without reading the emitters.
""",
)

# emit helpers: callable terminal name -> kwargs that are NOT labels
_EMIT_FUNCS: Dict[str, Set[str]] = {
    "counter_inc": {"n"},
    "gauge_set": {"value"},
    "gauge_inc": {"n"},
    "gauge_dec": {"n"},
    "observe": {"buckets", "value", "exemplar"},
    "add_span_total": set(),
    "legacy_count": set(),
    "count": set(),
    "span": set(),
}

# phase-name surfaces: progress() publishes fit.progress{phase=<arg0>} and
# note_rank_phase() feeds the comm plane's per-phase keys — arg0 is the token
# smokes/tests reference. They join the consumed-satisfier vocabulary, NOT
# the metric-name universe (no label schema, no doc-catalog obligation).
_PHASE_FUNCS = ("progress", "note_rank_phase")

# local import aliases of the emit helpers seen in-tree
_EMIT_ALIASES = {
    "obs_span": "span",
    "_obs_span": "span",
    "_span": "span",
    "obs_counter_inc": "counter_inc",
    "obs_gauge_set": "gauge_set",
    "obs_observe": "observe",
    "obs_progress": "progress",
}


def _canon_fname(fname: str) -> str:
    return _EMIT_ALIASES.get(fname, fname)
_REGISTRY_GETTERS = {"counter", "gauge", "histogram"}

_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
_PRAGMA_RE = re.compile(
    r"#\s*srml-metric:\s*([a-z][a-z0-9_.]*)(?:\{([a-z0-9_,\s]*)\})?"
)
# a dotted token inside quotes/backticks in non-python corpora
_CORPUS_TOKEN_RE = re.compile(
    r"[\"'`]([a-z][a-z0-9_]*(?:\.[a-z0-9_]+)+)(?:\{[^\"'`]*)?[\"'`]"
)

_DOC_FILES = ("docs/metrics.md", "docs/design.md", "docs/configuration.md",
              "README.md")
_SHELL_CONSUMERS = ("ci/test.sh",)

# consumer python files: anything under these roots reads metrics back
_CONSUMER_PREFIXES = ("tests/", "ci/", "benchmark/")
# the benchmark's per-layer metric files: data, read by cellbench/readers
_BENCH_METRIC_DIR = "cellbench/metrics"


class _Emission:
    __slots__ = ("name", "labels", "rel", "line", "dynamic_labels")

    def __init__(self, name: str, labels: Optional[Tuple[str, ...]],
                 rel: str, line: int):
        self.name = name
        self.labels = labels  # None == **dynamic, excluded from mismatch
        self.rel = rel
        self.line = line


def _literal_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _literal_strs(node: ast.AST) -> List[str]:
    """Literal string value(s) of an emission-name argument. A conditional
    name (`"a.x" if cond else "a.y"`, ops/knn.py::_count_x2) emits both."""
    s = _literal_str(node)
    if s is not None:
        return [s]
    if isinstance(node, ast.IfExp):
        return [s for sub in (node.body, node.orelse)
                for s in _literal_strs(sub)]
    return []


def _harvest_emissions(mod: ModuleInfo) -> List[_Emission]:
    out: List[_Emission] = []
    if mod.tree is None:
        return out
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        fname = _canon_fname(
            func.id if isinstance(func, ast.Name)
            else func.attr if isinstance(func, ast.Attribute) else ""
        )
        names: List[str] = []
        labels: Optional[Tuple[str, ...]] = ()
        if fname in _EMIT_FUNCS and node.args:
            names = _literal_strs(node.args[0])
            skip = _EMIT_FUNCS[fname]
            keys: List[str] = []
            dynamic = False
            for kw in node.keywords:
                if kw.arg is None:
                    dynamic = True  # **labels
                elif kw.arg not in skip:
                    keys.append(kw.arg)
            labels = None if dynamic else tuple(sorted(keys))
        elif (
            fname in ("inc", "dec", "set")
            and isinstance(func, ast.Attribute)
            and isinstance(func.value, ast.Call)
            and isinstance(func.value.func, ast.Attribute)
            and func.value.func.attr in _REGISTRY_GETTERS
            and func.value.args
        ):
            # reg.counter("x").inc(n, **labels) chained form
            names = _literal_strs(func.value.args[0])
            keys = []
            dynamic = False
            for kw in node.keywords:
                if kw.arg is None:
                    dynamic = True
                elif kw.arg not in ("n", "value"):
                    keys.append(kw.arg)
            labels = None if dynamic else tuple(sorted(keys))
        elif (
            fname in _REGISTRY_GETTERS
            and isinstance(func, ast.Attribute)
            and node.args
        ):
            # bare reg.histogram("x") — name only, labels unknowable
            names = _literal_strs(node.args[0])
            labels = None
        for name in names:
            if _NAME_RE.match(name):
                out.append(_Emission(name, labels, mod.rel, node.lineno))
    # pragma-declared dynamic emissions
    for i, line in enumerate(mod.lines, 1):
        m = _PRAGMA_RE.search(line)
        if m:
            keys = tuple(sorted(
                k.strip() for k in (m.group(2) or "").split(",") if k.strip()
            ))
            out.append(_Emission(m.group(1), keys or (), mod.rel, i))
    return out


def _is_consumer(mod: ModuleInfo) -> bool:
    return mod.rel.startswith(_CONSUMER_PREFIXES)


# dotted vocabularies that share the metric grammar but are NOT metrics:
# config keys (config.py _DEFAULTS/_ENV_KEYS) and compiled-kernel names (they
# surface as `device.compile{kernel=}` label VALUES and `device.kernels[].kernel`
# records, both legitimately consumed by tests/smokes/docs)
_FILEISH_SUFFIXES = (".py", ".sh", ".md", ".json", ".jsonl", ".txt", ".yaml")


def _harvest_vocab(ctx: AnalysisContext) -> Set[str]:
    vocab: Set[str] = set()
    cfg = ctx.index.by_rel.get("spark_rapids_ml_tpu/config.py")
    if cfg is not None and cfg.tree is not None:
        for node in ast.walk(cfg.tree):
            if isinstance(node, ast.Dict):
                for kn in node.keys:
                    s = _literal_str(kn) if kn is not None else None
                    if s:
                        vocab.add(s)
    for mod in ctx.index.files:
        if mod.tree is None or not mod.rel.startswith("spark_rapids_ml_tpu/"):
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fname = _canon_fname(
                node.func.id if isinstance(node.func, ast.Name)
                else node.func.attr if isinstance(node.func, ast.Attribute)
                else ""
            )
            if fname in ("compiled_kernel",) + _PHASE_FUNCS and node.args:
                for s in _literal_strs(node.args[0]):
                    vocab.add(s)
            # phase names threaded as keywords (streamed-fit loops pass
            # progress_phase="kmeans.batches" down to the ingest tier)
            for kw in node.keywords:
                if kw.arg in ("phase", "progress_phase"):
                    for s in _literal_strs(kw.value):
                        vocab.add(s)
    return vocab


def _harvest_py_consumptions(mod: ModuleInfo,
                             namespaces: Set[str]) -> List[Tuple[str, int]]:
    """Metric-shaped string literals in a consumer module. The literal may
    carry a `{label=` suffix (prefix-grep form); only the dotted base is
    checked."""
    out: List[Tuple[str, int]] = []
    if mod.tree is None:
        return out
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Constant) and isinstance(node.value, str)):
            continue
        base = node.value.split("{")[0]
        if not _NAME_RE.match(base):
            continue
        if base.split(".")[0] not in namespaces:
            continue
        out.append((base, node.lineno))
    return out


def _bench_metric_reads(ctx: AnalysisContext
                        ) -> List[Tuple[str, int, str, str, Optional[Set[str]]]]:
    """(file, line, line text, name, label keys or None) for everything the
    benchmark's metric files read of the program: the `counter` with the keys
    of `labels`, and the span named by a `span` label or a `span` field (a
    name with a `{placeholder}` is filled in at run time and is not checked
    here: tests/test_benchmark_contract.py runs it)."""
    out: List[Tuple[str, int, str, str, Optional[Set[str]]]] = []
    root = ctx.index.root / _BENCH_METRIC_DIR
    for path in sorted(root.glob("*.json")) if root.is_dir() else ():
        rel = path.relative_to(ctx.index.root).as_posix()
        text = ctx.index.read_text(rel) or ""
        try:
            spec = json.loads(text)
        except ValueError:
            continue
        if not isinstance(spec, dict):
            continue
        lines = text.splitlines()

        def read(name: str, keys: Optional[Set[str]]) -> None:
            at = next((i for i, ln in enumerate(lines) if f'"{name}"' in ln), 0)
            out.append((rel, at + 1, lines[at] if lines else "", name, keys))

        labels = spec.get("labels") or {}
        if isinstance(spec.get("counter"), str):
            read(spec["counter"], set(labels))
        for name in (spec.get("span"), labels.get("span")):
            if isinstance(name, str) and "{" not in name:
                read(name, None)
    return out


@register_pass("metrics")
def run(ctx: AnalysisContext) -> None:
    emissions: List[_Emission] = []
    lib_mods: List[ModuleInfo] = []
    for mod in ctx.index.files:
        if mod.rel.startswith("spark_rapids_ml_tpu/"):
            lib_mods.append(mod)
            emissions.extend(_harvest_emissions(mod))

    emitted: Dict[str, List[_Emission]] = {}
    for e in emissions:
        emitted.setdefault(e.name, []).append(e)
    namespaces = {n.split(".")[0] for n in emitted}
    vocab = _harvest_vocab(ctx)

    # ---- consumed-but-never-emitted
    def satisfied(base: str) -> bool:
        if base in emitted or base in vocab:
            return True
        if base.endswith(_FILEISH_SUFFIXES):
            return True  # file path, not a metric
        return any(
            name == base or name.startswith(base)
            or base.startswith(name + ".")  # dynamic-suffix families
            for name in emitted
        )

    for mod in ctx.index.files:
        if not _is_consumer(mod):
            continue
        # a test that emits its own fixture metric (span("t.x") then asserts
        # on "t.x") satisfies itself — only names NOBODY emits are drift
        own = {e.name for e in _harvest_emissions(mod)}
        for base, line in _harvest_py_consumptions(mod, namespaces):
            if satisfied(base) or base in own or any(
                n.startswith(base) for n in own
            ):
                continue
            ctx.emit(
                "metrics/consumed-unemitted", mod, line,
                f"`{base}` is consumed here but no library code emits "
                "it (rename drift? add a `# srml-metric:` pragma at a "
                "dynamic emission site if one exists)",
            )
    for rel in _SHELL_CONSUMERS:
        text = ctx.index.read_text(rel)
        mod = ctx.index.by_rel.get(rel)
        if text is None:
            continue
        for i, line in enumerate(text.splitlines(), 1):
            for m in _CORPUS_TOKEN_RE.finditer(line):
                base = m.group(1)
                if base.split(".")[0] in namespaces and not satisfied(base):
                    # shell corpus has no ModuleInfo; report against test.sh
                    # through a synthetic one-off emit
                    ctx.findings.append(Finding(
                        "metrics/consumed-unemitted", rel, i,
                        f"`{base}` is consumed here but no library code "
                        "emits it",
                        line_text=line,
                    ))

    # ---- the benchmark's metric files: exact names, and label keys some
    # emission site of the counter writes (a dynamic site may write any)
    for rel, line, line_text, name, keys in _bench_metric_reads(ctx):
        sites = emitted.get(name)
        if sites is None:
            ctx.findings.append(Finding(
                "metrics/consumed-unemitted", rel, line,
                f"`{name}` is read by this benchmark metric file but no "
                "library code emits it: the per-layer metric reads nothing "
                "on the chip",
                line_text=line_text,
            ))
        elif keys and not any(
            e.labels is None or keys <= set(e.labels) for e in sites
        ):
            ctx.findings.append(Finding(
                "metrics/label-mismatch", rel, line,
                f"`{name}` is read here by labels "
                f"{{{', '.join(sorted(keys))}}} but no emission site writes "
                "them all: "
                + "; ".join(sorted(
                    f"{{{', '.join(e.labels or ())}}} at {e.rel}:{e.line}"
                    for e in sites)),
                line_text=line_text,
            ))

    # ---- label-set conflicts (static sites only; None == dynamic, skipped)
    for name in sorted(emitted):
        sets: Dict[Tuple[str, ...], _Emission] = {}
        for e in emitted[name]:
            if e.labels is not None:
                sets.setdefault(e.labels, e)
        keysets = sorted(sets)
        conflict = None
        for i in range(len(keysets)):
            for j in range(i + 1, len(keysets)):
                a, b = set(keysets[i]), set(keysets[j])
                if not (a <= b or b <= a):
                    conflict = (sets[keysets[i]], sets[keysets[j]])
                    break
            if conflict:
                break
        if conflict:
            e1, e2 = conflict
            mod = ctx.index.by_rel[e2.rel]
            ctx.emit(
                "metrics/label-mismatch", mod, e2.line,
                f"`{name}` emitted here with labels "
                f"{{{', '.join(e2.labels or ())}}} but with "
                f"{{{', '.join(e1.labels or ())}}} at {e1.rel}:{e1.line} — "
                "neither is a subset of the other; pick one label schema",
            )

    # ---- undocumented emissions
    doc_tokens: Set[str] = set()
    for rel in _DOC_FILES:
        text = ctx.index.read_text(rel)
        if text is None:
            continue
        for m in _CORPUS_TOKEN_RE.finditer(text):
            doc_tokens.add(m.group(1))
        # docs also reference names in prose/backticks without quotes
        for m in re.finditer(r"`([a-z][a-z0-9_]*(?:\.[a-z0-9_]+)+)[`{]", text):
            doc_tokens.add(m.group(1))
    for name in sorted(emitted):
        if name in doc_tokens or any(
            t != name and name.startswith(t + ".") for t in doc_tokens
        ):
            continue
        e = min(emitted[name], key=lambda e: (e.rel, e.line))
        mod = ctx.index.by_rel[e.rel]
        ctx.emit(
            "metrics/undocumented", mod, e.line,
            f"emitted metric `{name}` appears in no doc file — add it to "
            "the docs/metrics.md catalog",
        )

#
# Framework half of the whole-program analyzer (docs/design.md §6j): ONE
# shared AST parse + module index per run, a rule registry with stable IDs,
# findings that carry file:line + rule + a one-line why, a scoped-suppression
# grammar (`# noqa: <rule-id>`), and a checked-in baseline for grandfathered
# findings. The passes (fences/purity/locks/metrics) are pure consumers of
# this module: they read the index, emit findings, and never re-read a file.
#
# Suppression grammar — exactly one form is legal:
#
#     <code>  # noqa: rule-id[, rule-id...] [— free-text justification]
#
# A bare `# noqa` (no rule id) is itself a finding (noqa/blanket): blanket
# waivers are how dead suppressions rot. A rule id the registry doesn't know
# is a finding (noqa/unknown-rule); a known id that suppresses nothing on its
# line is a finding (noqa/unused). The baseline file plays the same game at
# the repository level: entries are fingerprinted on (rule, file, source-line
# text) — stable across line renumbering — and an entry that no longer
# matches any live finding is a finding (baseline/stale).
#

from __future__ import annotations

import ast
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Set, Tuple

# default analysis targets, relative to the repo root: every python surface CI
# runs plus the analyzer itself (it eats its own dogfood)
DEFAULT_TARGETS = (
    "spark_rapids_ml_tpu",
    "benchmark",
    "tests",
    "ci",
    "tools",
    "__graft_entry__.py",
)

DEFAULT_BASELINE = "tools/analysis/baseline.json"

_NOQA_RE = re.compile(
    r"#\s*noqa(?P<scoped>:\s*(?P<ids>[A-Za-z0-9_./-]+(?:\s*,\s*[A-Za-z0-9_./-]+)*))?"
)


# ----------------------------------------------------------------- rule model


@dataclass(frozen=True)
class Rule:
    """One named invariant. `explain` is what `--explain <id>` prints: enough
    for a failing CI line to be actionable without opening the analyzer."""

    id: str
    summary: str  # one line, shown in --list-rules and findings
    explain: str  # paragraph(s): rationale + how to fix + how to suppress


_RULES: Dict[str, Rule] = {}


def register_rule(id: str, summary: str, explain: str) -> Rule:
    if id in _RULES:
        raise ValueError(f"duplicate rule id {id!r}")
    r = Rule(id=id, summary=summary, explain=explain.strip())
    _RULES[id] = r
    return r


def all_rules() -> Dict[str, Rule]:
    return dict(_RULES)


def rule_exists(rule_id: str) -> bool:
    return rule_id in _RULES


# the meta rules live here because core owns the suppression/baseline grammar
register_rule(
    "noqa/blanket",
    "bare `# noqa` without a rule id",
    """
A suppression that names no rule waives every current AND future check on its
line — nobody can tell which finding it was written for, so it can never be
safely removed. Scope it: `# noqa: <rule-id>` (comma-separate several ids).
Run `--list-rules` for the catalog.
""",
)
register_rule(
    "noqa/unknown-rule",
    "`# noqa: <id>` names a rule the registry doesn't know",
    """
The rule id in this suppression doesn't exist (typo, or a rule that was
renamed/retired). An unknown id suppresses nothing, so the comment is dead
weight that READS like a waiver. Fix the id (`--list-rules`) or delete the
comment.
""",
)
register_rule(
    "noqa/unused",
    "scoped `# noqa: <id>` suppresses nothing on its line",
    """
No finding of the named rule fires on this line, so the suppression is dead.
Dead suppressions rot: they survive refactors, migrate onto unrelated code,
and silently waive the rule if the hazard ever comes back somewhere else on
the line. Delete the comment (keep any prose as a plain comment).
""",
)
register_rule(
    "baseline/stale",
    "baseline entry matches no live finding",
    """
A grandfathered finding recorded in the baseline file no longer occurs — the
code was fixed or deleted. Remove the entry (re-run with --write-baseline, or
edit tools/analysis/baseline.json) so the baseline only ever shrinks and a
REINTRODUCED finding can't hide behind a stale entry.
""",
)


# ---------------------------------------------------------------- module index


@dataclass
class Noqa:
    line: int
    rule_ids: Tuple[str, ...]  # empty tuple == a bare (blanket) directive
    used: Set[str] = field(default_factory=set)


@dataclass
class ModuleInfo:
    path: Path  # absolute
    rel: str  # repo-root-relative, '/'-separated
    name: Optional[str]  # dotted module name ('' parts stripped), None for scripts
    src: str
    lines: List[str]
    tree: Optional[ast.AST]  # None when the file doesn't parse
    parse_error: Optional[str]
    noqa: Dict[int, Noqa]

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""


def _scan_noqa(src: str, lines: Sequence[str]) -> Dict[int, Noqa]:
    """noqa directives from REAL comment tokens only — a `# noqa` mentioned
    inside a docstring or string literal (rule explanations, documentation of
    the grammar itself) neither suppresses nor counts as a directive. Falls
    back to a raw line scan when the file doesn't tokenize."""
    out: Dict[int, Noqa] = {}
    if "noqa" not in src:
        return out

    def _add(lineno: int, comment: str) -> None:
        m = _NOQA_RE.search(comment)
        if not m:
            return
        ids: Tuple[str, ...] = ()
        if m.group("scoped"):
            ids = tuple(s.strip() for s in m.group("ids").split(",") if s.strip())
        out[lineno] = Noqa(line=lineno, rule_ids=ids)

    import io
    import tokenize

    try:
        for tok in tokenize.generate_tokens(io.StringIO(src).readline):
            if tok.type != tokenize.COMMENT or "noqa" not in tok.string:
                continue
            # a directive is a TRAILING comment on a code line; `# noqa`
            # prose on a comment-only line (module headers documenting the
            # grammar) is neither a suppression nor a finding
            lineno, col = tok.start
            before = lines[lineno - 1][:col] if lineno <= len(lines) else ""
            if before.strip():
                _add(lineno, tok.string)
    except (tokenize.TokenError, IndentationError, SyntaxError):
        for i, line in enumerate(lines, 1):
            if "noqa" in line and line.split("#", 1)[0].strip():
                _add(i, line)
    return out


def _module_name(rel: str) -> Optional[str]:
    if not rel.endswith(".py"):
        return None
    parts = rel[: -len(".py")].split("/")
    if parts[-1] == "__init__":
        parts = parts[:-1]
    if not parts:
        return None
    return ".".join(parts)


class ProjectIndex:
    """The single shared parse: every target file read and ast-parsed exactly
    once, keyed by repo-relative path and by dotted module name."""

    def __init__(self, root: Path, targets: Sequence[str] = DEFAULT_TARGETS):
        self.root = Path(root).resolve()
        self.targets = tuple(targets)
        self.files: List[ModuleInfo] = []
        self.by_rel: Dict[str, ModuleInfo] = {}
        self.by_module: Dict[str, ModuleInfo] = {}
        self._parse_all()

    def _iter_paths(self) -> Iterable[Path]:
        for t in self.targets:
            p = self.root / t
            if p.is_file():
                yield p
            elif p.is_dir():
                for f in sorted(p.rglob("*.py")):
                    if "__pycache__" in f.parts:
                        continue
                    yield f

    def _parse_all(self) -> None:
        for path in self._iter_paths():
            rel = path.relative_to(self.root).as_posix()
            src = path.read_text()
            lines = src.splitlines()
            tree: Optional[ast.AST] = None
            err: Optional[str] = None
            try:
                tree = ast.parse(src)
            except SyntaxError as e:
                err = f"line {e.lineno}: {e.msg}"
            info = ModuleInfo(
                path=path,
                rel=rel,
                name=_module_name(rel),
                src=src,
                lines=lines,
                tree=tree,
                parse_error=err,
                noqa=_scan_noqa(src, lines),
            )
            self.files.append(info)
            self.by_rel[rel] = info
            if info.name:
                self.by_module[info.name] = info

    def read_text(self, rel: str) -> Optional[str]:
        """Non-python corpus files (docs, shell) for the metric-contract pass;
        cached so repeated rule access stays one read."""
        cache = getattr(self, "_text_cache", None)
        if cache is None:
            cache = self._text_cache = {}
        if rel not in cache:
            p = self.root / rel
            cache[rel] = p.read_text() if p.is_file() else None
        return cache[rel]


# ------------------------------------------------------------------- findings


@dataclass
class Finding:
    rule: str
    rel: str
    line: int
    message: str
    line_text: str = ""
    baselined: bool = False

    @property
    def fingerprint(self) -> str:
        """Stable identity across line renumbering: rule + file + the exact
        (whitespace-stripped) source line the finding points at."""
        return f"{self.rule}::{self.rel}::{self.line_text.strip()}"

    def render(self) -> str:
        return f"{self.rel}:{self.line}: [{self.rule}] {self.message}"

    def as_json(self) -> Dict[str, Any]:
        return {
            "rule": self.rule,
            "file": self.rel,
            "line": self.line,
            "message": self.message,
            "fingerprint": self.fingerprint,
        }


class AnalysisContext:
    """What a pass sees: the index plus an emit() that applies the scoped
    suppression grammar centrally (passes never parse noqa themselves)."""

    def __init__(self, index: ProjectIndex):
        self.index = index
        self.findings: List[Finding] = []
        # populated lazily by passes that share the call graph
        self.shared: Dict[str, Any] = {}

    def emit(
        self,
        rule: str,
        module: ModuleInfo,
        lineno: int,
        message: str,
        noqa_lines: Optional[Sequence[int]] = None,
    ) -> None:
        """Record a finding unless a scoped noqa with this rule id sits on the
        finding line (or one of `noqa_lines`, for multi-line constructs)."""
        if rule not in _RULES:
            raise ValueError(f"pass emitted unregistered rule {rule!r}")
        for ln in list(noqa_lines or ()) + [lineno]:
            nq = module.noqa.get(ln)
            if nq is not None and rule in nq.rule_ids:
                nq.used.add(rule)
                return
        self.findings.append(
            Finding(
                rule=rule,
                rel=module.rel,
                line=lineno,
                message=message,
                line_text=module.line_text(lineno),
            )
        )


# ------------------------------------------------------------------- baseline


def load_baseline(path: Path) -> Dict[str, str]:
    """fingerprint -> one-line justification. Missing file == empty baseline."""
    if not path.is_file():
        return {}
    doc = json.loads(path.read_text())
    entries = doc.get("entries", {})
    return {str(k): str(v) for k, v in entries.items()}

def write_baseline(path: Path, findings: Sequence[Finding],
                   justifications: Optional[Dict[str, str]] = None) -> None:
    entries = {}
    for f in sorted(findings, key=lambda f: f.fingerprint):
        just = (justifications or {}).get(
            f.fingerprint, "grandfathered by --write-baseline; justify or fix"
        )
        entries[f.fingerprint] = just
    path.write_text(
        json.dumps(
            {
                "comment": (
                    "Grandfathered analyzer findings (tools/analysis). Keyed by "
                    "rule::file::stripped-source-line; values are one-line "
                    "justifications. Entries may only be removed (by fixing the "
                    "finding) — a stale entry is itself a finding "
                    "(baseline/stale). The purity/* section of this file must "
                    "stay EMPTY: trace-purity findings are fixed, never waived."
                ),
                "entries": entries,
            },
            indent=2,
            sort_keys=False,
        )
        + "\n"
    )


# ------------------------------------------------------------------ the driver

PassFn = Callable[[AnalysisContext], None]
_PASSES: List[Tuple[str, PassFn]] = []


def register_pass(name: str) -> Callable[[PassFn], PassFn]:
    def deco(fn: PassFn) -> PassFn:
        _PASSES.append((name, fn))
        return fn

    return deco


def _meta_noqa_pass(ctx: AnalysisContext) -> None:
    """Runs AFTER every rule pass: judge the suppressions themselves."""
    for mod in ctx.index.files:
        for nq in mod.noqa.values():
            if not nq.rule_ids:
                ctx.emit(
                    "noqa/blanket",
                    mod,
                    nq.line,
                    "bare `# noqa` — scope it to a rule id "
                    "(`# noqa: <rule-id>`; see --list-rules)",
                )
                continue
            for rid in nq.rule_ids:
                if not rule_exists(rid):
                    ctx.emit(
                        "noqa/unknown-rule",
                        mod,
                        nq.line,
                        f"`# noqa: {rid}` names an unknown rule id "
                        "(see --list-rules)",
                    )
                elif rid not in nq.used:
                    ctx.emit(
                        "noqa/unused",
                        mod,
                        nq.line,
                        f"`# noqa: {rid}` suppresses nothing on this line — "
                        "delete the dead suppression",
                    )


def run_analysis(
    root: Path,
    targets: Sequence[str] = DEFAULT_TARGETS,
    baseline_path: Optional[Path] = None,
    only_passes: Optional[Set[str]] = None,
) -> Dict[str, Any]:
    """Run every registered pass over one shared index; returns the report
    dict (also the --json payload). Import of the pass modules is the caller's
    job (tools.analysis.__init__ pulls them all in)."""
    import time as _time

    t0 = _time.perf_counter()
    index = ProjectIndex(Path(root), targets)
    ctx = AnalysisContext(index)
    for name, fn in _PASSES:
        if only_passes is not None and name not in only_passes:
            continue
        fn(ctx)
    if only_passes is None or "noqa" in (only_passes or {"noqa"}):
        _meta_noqa_pass(ctx)

    baseline = load_baseline(baseline_path) if baseline_path else {}
    live: List[Finding] = []
    matched: Set[str] = set()
    for f in ctx.findings:
        fp = f.fingerprint
        if fp in baseline:
            f.baselined = True
            matched.add(fp)
        else:
            live.append(f)
    for fp in sorted(set(baseline) - matched):
        rule, rel, _ = fp.split("::", 2)
        mod = index.by_rel.get(rel)
        if mod is None:
            # the whole file is gone; report against the baseline itself
            try:
                rel_b = Path(baseline_path).resolve().relative_to(
                    index.root
                ).as_posix()
            except (ValueError, TypeError):
                rel_b = str(baseline_path)
            live.append(Finding("baseline/stale", rel_b, 1,
                                f"entry {fp!r} matches no live finding"))
        else:
            live.append(
                Finding("baseline/stale", rel, 1,
                        f"entry {fp!r} matches no live finding — remove it")
            )

    live.sort(key=lambda f: (f.rel, f.line, f.rule))
    elapsed = _time.perf_counter() - t0
    return {
        "root": str(index.root),
        "files_analyzed": len(index.files),
        "elapsed_s": round(elapsed, 3),
        "findings": [f.as_json() for f in live],
        "baselined": sorted(matched),
        "ok": not live,
        "_finding_objs": live,  # stripped before JSON serialization
        "_index": index,
    }

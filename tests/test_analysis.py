#
# Tests for the whole-program static-analysis plane (tools/analysis,
# docs/design.md §6j) — the first tests the lint tier has ever had. Coverage
# per the acceptance contract:
#
#   * each of the three cross-file passes (purity/locks/metrics) has at least
#     one TRUE-POSITIVE fixture and one deliberate NEAR-MISS false-positive
#     fixture (the hazard shape without the hazard);
#   * two migrated fences (fence/silent-except, fence/hardcoded-tunable) have
#     the same TP/near-miss pair;
#   * the suppression grammar round-trips: a scoped `# noqa: <rule-id>`
#     silences exactly its rule, DELETING it re-surfaces the finding (exit 1),
#     unknown/blanket/dead suppressions are findings themselves;
#   * the baseline grandfathers by fingerprint and rots loudly
#     (baseline/stale);
#   * re-introducing a fixed finding — a `_config.get` inside a
#     compiled_kernel impl, a reversed lock pair, a consumed metric key
#     nothing emits — fails the run with that rule id;
#   * the REAL tree is clean, within the wall-clock budget, with an EMPTY
#     trace-purity baseline.
#
# Fixtures are tiny synthetic repo trees written to tmp_path; the analyzer
# runs in-process via run_analysis(root, targets).
#

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.analysis import all_rules, run_analysis  # sys.path set above
from tools.analysis.core import DEFAULT_BASELINE


def _write(root: Path, rel: str, body: str) -> Path:
    p = root / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(body))
    return p


def _run(root: Path, targets=("spark_rapids_ml_tpu", "tests", "ci"),
         baseline: Path = None):
    report = run_analysis(root, targets=targets, baseline_path=baseline)
    findings = report["_finding_objs"]
    return report, findings, {f.rule for f in findings}


# --------------------------------------------------------------- purity pass


PURITY_TP = """
    from ..observability.device import compiled_kernel
    from .. import config as _config

    @compiled_kernel("foo.kernel")
    def _impl(x):
        if _config.get("fast_math"):
            return x * 2
        return x
"""

PURITY_NEAR_MISS = """
    from ..observability.device import compiled_kernel
    from .. import config as _config

    @compiled_kernel("foo.kernel", static_argnames=("fast",))
    def _impl(x, fast):
        return x * 2 if fast else x

    def host_wrapper(x):
        # the SAME read, in the host wrapper: the sanctioned PR-13 shape
        return _impl(x, bool(_config.get("fast_math")))
"""


def test_purity_true_positive_config_read(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/ops/foo.py", PURITY_TP)
    _, findings, rules = _run(tmp_path)
    assert "purity/config-read" in rules
    f = next(f for f in findings if f.rule == "purity/config-read")
    assert f.rel == "spark_rapids_ml_tpu/ops/foo.py"
    assert "_config.get" in f.message or "_config.get" in f.line_text


def test_purity_near_miss_host_wrapper_read(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/ops/foo.py", PURITY_NEAR_MISS)
    _, _, rules = _run(tmp_path)
    assert not any(r.startswith("purity/") for r in rules)


def test_purity_reaches_through_call_chain_and_lax_map(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/ops/foo.py", """
        import os
        import jax
        from jax import lax

        def _helper(row):
            limit = int(os.environ.get("SRML_LIMIT", "8"))
            return row[:limit]

        def host(X):
            def body(row):
                return _helper(row)
            return jax.lax.map(body, X)
    """)
    _, findings, rules = _run(tmp_path)
    assert "purity/env-read" in rules


def test_purity_scoped_noqa_suppresses_and_its_deletion_resurfaces(tmp_path):
    noqa_line = (
        "        v = _config.get('fast_math')"
        "  # noqa: purity/config-read — trace-epoch keyed\n"
    )
    src = (
        "from ..observability.device import compiled_kernel\n"
        "from .. import config as _config\n\n\n"
        "@compiled_kernel('foo.kernel')\n"
        "def _impl(x):\n"
        "    if True:\n" + noqa_line +
        "    return x\n"
    )
    p = tmp_path / "spark_rapids_ml_tpu/ops/foo.py"
    p.parent.mkdir(parents=True)
    p.write_text(src)
    _, _, rules = _run(tmp_path)
    assert "purity/config-read" not in rules, "scoped noqa must suppress"
    assert "noqa/unused" not in rules, "the suppression is live, not dead"
    # the acceptance clause: DELETE the scoped noqa -> the finding returns
    p.write_text(src.replace(
        "  # noqa: purity/config-read — trace-epoch keyed", ""
    ))
    report, _, rules = _run(tmp_path)
    assert "purity/config-read" in rules
    assert report["ok"] is False


# ---------------------------------------------------------------- locks pass


LOCKS_CYCLE = """
    import threading

    _registry_lock = threading.Lock()
    _cache_lock = threading.Lock()

    def register():
        with _registry_lock:
            with _cache_lock:
                pass

    def evict():
        with _cache_lock:
            with _registry_lock:
                pass
"""

LOCKS_ORDERED = """
    import threading

    _registry_lock = threading.Lock()
    _cache_lock = threading.Lock()

    def register():
        with _registry_lock:
            with _cache_lock:
                pass

    def evict():
        # same canonical order on every path: no cycle
        with _registry_lock:
            with _cache_lock:
                pass
"""


def test_locks_true_positive_reversed_pair(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/serving/registry.py", LOCKS_CYCLE)
    report, findings, rules = _run(tmp_path)
    assert "locks/order-cycle" in rules
    assert report["ok"] is False
    f = next(f for f in findings if f.rule == "locks/order-cycle")
    assert "_registry_lock" in f.message and "_cache_lock" in f.message


def test_locks_near_miss_consistent_order(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/serving/registry.py", LOCKS_ORDERED)
    _, _, rules = _run(tmp_path)
    assert "locks/order-cycle" not in rules


def test_locks_cycle_through_call_chain(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/serving/registry.py", """
        import threading
        from ..ops import device_cache

        _lock = threading.Lock()

        def register():
            with _lock:
                device_cache.reserve()
    """)
    _write(tmp_path, "spark_rapids_ml_tpu/ops/device_cache.py", """
        import threading
        from ..serving import registry

        _lock = threading.Lock()

        def reserve():
            with _lock:
                pass

        def evict():
            with _lock:
                registry.register()
    """)
    _, _, rules = _run(tmp_path)
    assert "locks/order-cycle" in rules


def test_locks_self_deadlock_on_plain_lock_but_not_rlock(tmp_path):
    tp = """
        import threading

        class Registry:
            def __init__(self):
                self._lock = threading.{kind}()

            def get(self):
                with self._lock:
                    return self._locked_get()

            def _locked_get(self):
                with self._lock:
                    return 1
    """
    _write(tmp_path, "spark_rapids_ml_tpu/serving/registry.py",
           tp.format(kind="Lock"))
    _, _, rules = _run(tmp_path)
    assert "locks/order-cycle" in rules  # plain Lock re-entry: self-deadlock
    _write(tmp_path, "spark_rapids_ml_tpu/serving/registry.py",
           tp.format(kind="RLock"))
    _, _, rules = _run(tmp_path)
    assert "locks/order-cycle" not in rules  # RLock re-entry is legal


def test_locks_blocking_under_hot_lock_and_near_miss(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/serving/registry.py", """
        import threading

        class Registry:
            def __init__(self):
                self._lock = threading.Lock()

            def snapshot_bad(self, path):
                with self._lock:
                    with open(path) as f:  # file I/O inside the section
                        return f.read()

            def snapshot_good(self, path):
                with self._lock:
                    p = str(path)
                # near miss: the slow work happens AFTER release
                with open(p) as f:
                    return f.read()
    """)
    _, findings, rules = _run(tmp_path)
    assert "locks/blocking-under-lock" in rules
    hits = [f for f in findings if f.rule == "locks/blocking-under-lock"]
    assert len(hits) == 1 and "snapshot_bad" not in hits[0].message
    # the one finding points inside snapshot_bad, not snapshot_good
    src = (tmp_path / "spark_rapids_ml_tpu/serving/registry.py").read_text()
    bad_span = range(src.index("snapshot_bad"), src.index("snapshot_good"))
    assert src.index("open(path)") in bad_span


def test_locks_device_execution_under_registry_lock(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/serving/registry.py", """
        import threading
        from ..observability.device import compiled_kernel

        @compiled_kernel("serve.predict")
        def _predict(x):
            return x

        class Registry:
            def __init__(self):
                self._lock = threading.Lock()

            def prewarm(self, x):
                with self._lock:
                    return _predict(x)  # device execution under the lock
    """)
    _, findings, rules = _run(tmp_path)
    assert "locks/blocking-under-lock" in rules
    f = next(f for f in findings if f.rule == "locks/blocking-under-lock")
    assert "device execution" in f.message


# -------------------------------------------------------------- metrics pass


def test_metrics_consumed_unemitted_and_near_miss(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/ops/cacheish.py", """
        from ..observability.runs import counter_inc

        def hit():
            counter_inc("cache.hits", 1)
    """)
    _write(tmp_path, "tests/test_cacheish.py", """
        def test_reads_counters(totals):
            assert totals["cache.hits"] >= 0          # near miss: emitted
            assert totals["cache.hitz_total"] == 0    # drift: nothing emits
    """)
    _, findings, rules = _run(tmp_path)
    assert "metrics/consumed-unemitted" in rules
    hits = [f for f in findings if f.rule == "metrics/consumed-unemitted"]
    assert len(hits) == 1 and "cache.hitz_total" in hits[0].message  # noqa: metrics/consumed-unemitted — fixture token, not a real consumer


def test_metrics_benchmark_metric_file_reads_are_consumptions(tmp_path):
    """cellbench/metrics/*.json is the consumer the driver runs: a metric file
    that names a counter, a label key or a span the library does not emit is
    reported; the ones that match are not."""
    _write(tmp_path, "spark_rapids_ml_tpu/ops/lloydish.py", """
        from ..observability.runs import counter_inc, span

        def lloyd():
            with span("kmeans.lloyd"):
                counter_inc("kmeans.lloyd_path", 1, path="xla")
    """)
    _write(tmp_path, "spark_rapids_ml_tpu/observability/registry.py", """
        # srml-metric: span.seconds{span}
    """)
    def metric(name, body):
        _write(tmp_path, f"cellbench/metrics/{name}.json", json.dumps(body))

    metric("ok_counter", {"kind": "report_counter_per_op",
                          "counter": "kmeans.lloyd_path", "labels": {"path": "xla"}})
    metric("ok_span", {"kind": "report_counter_per_op", "counter": "span.seconds",
                       "labels": {"span": "kmeans.lloyd"}})
    metric("ok_filled_in", {"kind": "span_seconds_per_op", "span": "{estimator}.prepare"})
    renamed = "kmeans.lloyd_route"  # noqa: metrics/consumed-unemitted — fixture token, not a real consumer
    metric("renamed_counter", {"kind": "report_counter_per_op",
                               "counter": renamed, "labels": {"path": "xla"}})
    renamed = "kmeans.loop"  # noqa: metrics/consumed-unemitted — fixture token, not a real consumer
    metric("renamed_span", {"kind": "report_counter_per_op", "counter": "span.seconds",
                            "labels": {"span": renamed}})
    metric("wrong_label", {"kind": "report_counter_per_op",
                           "counter": "kmeans.lloyd_path", "labels": {"route": "xla"}})
    _, findings, _ = _run(tmp_path)
    got = {(f.rule, f.rel.rsplit("/", 1)[-1]) for f in findings
           if f.rel.startswith("cellbench/")}
    assert got == {
        ("metrics/consumed-unemitted", "renamed_counter.json"),
        ("metrics/consumed-unemitted", "renamed_span.json"),
        ("metrics/label-mismatch", "wrong_label.json"),
    }


def test_metrics_label_mismatch_and_subset_near_miss(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/ops/a.py", """
        from ..observability.runs import counter_inc

        def f():
            counter_inc("serve.requests", 1, model="m")
            counter_inc("serve.rows", 1, model="m")
    """)
    _write(tmp_path, "spark_rapids_ml_tpu/ops/b.py", """
        from ..observability.runs import counter_inc

        def g():
            counter_inc("serve.requests", 1, bucket="b")      # disjoint: split
            counter_inc("serve.rows", 1, model="m", site="s")  # superset: fine
    """)
    _, findings, rules = _run(tmp_path)
    hits = [f for f in findings if f.rule == "metrics/label-mismatch"]
    assert len(hits) == 1 and "serve.requests" in hits[0].message


def test_metrics_undocumented_and_pragma(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/ops/a.py", """
        from ..observability.runs import counter_inc

        def f(site):
            counter_inc("ingest.batches", 1)
            # srml-metric: ingest.bytes_s — dynamic per-site family
            counter_inc(f"ingest.bytes_s.{site}", 1)
    """)
    _write(tmp_path, "docs/metrics.md", "catalog: `ingest.batches` only\n")
    _, findings, rules = _run(tmp_path)
    hits = {f.message.split("`")[1] for f in findings
            if f.rule == "metrics/undocumented"}
    assert hits == {"ingest.bytes_s"}  # pragma-declared but not in the doc


# ------------------------------------------------------------ migrated fences


def test_fence_silent_except_tp_and_near_miss(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/x.py", """
        def f():
            try:
                risky()
            except Exception:
                pass  # TP: broad and silent

        def g():
            try:
                risky()
            except StopIteration:
                pass  # near miss: narrow typed catch is legal control flow

        def h(logger):
            try:
                risky()
            except Exception:
                logger.warning("boom")  # near miss: it logs
    """)
    _, findings, rules = _run(tmp_path)
    hits = [f for f in findings if f.rule == "fence/silent-except"]
    assert len(hits) == 1
    assert "except Exception" in hits[0].line_text


def test_fence_hardcoded_tunable_tp_and_zero_sentinel_near_miss(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/ops/k.py", """
        SCAN_TILE = 1 << 11        # TP: a literal tunable in ops/
        BLOCK_ROWS = 0             # near miss: zero = adaptive sentinel
        SOMETHING_ELSE = 4096      # near miss: not a tunable-looking name
    """)
    _, findings, rules = _run(tmp_path)
    hits = [f for f in findings if f.rule == "fence/hardcoded-tunable"]
    assert len(hits) == 1 and "SCAN_TILE = 2048" in hits[0].message


def test_fence_topk_fires_outside_selection_only(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/ops/knnish.py", """
        import jax

        def f(d2, k):
            return jax.lax.top_k(-d2, k)
    """)
    _write(tmp_path, "spark_rapids_ml_tpu/ops/selection.py", """
        import jax

        def select(d2, k):
            return jax.lax.top_k(-d2, k)  # the primitive's one legal home
    """)
    _, findings, rules = _run(tmp_path)
    hits = [f for f in findings if f.rule == "fence/topk-off-plane"]
    assert len(hits) == 1
    assert hits[0].rel == "spark_rapids_ml_tpu/ops/knnish.py"


# ------------------------------------------------- suppression grammar + meta


def test_noqa_blanket_unknown_and_unused_are_findings(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/x.py", """
        import os  # noqa
        import sys  # noqa: not/a-rule
        import json  # noqa: fence/silent-except
        print(os.name, sys.argv, json.dumps({}))
    """)
    _, findings, rules = _run(tmp_path)
    assert {"noqa/blanket", "noqa/unknown-rule", "noqa/unused"} <= rules


def test_noqa_prose_in_comments_and_docstrings_is_inert(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/x.py", '''
        # module header documenting the grammar: `# noqa: rule-id` — inert
        def f():
            """Suppress with `# noqa: fence/silent-except` — also inert."""
            return 1
    ''')
    _, _, rules = _run(tmp_path)
    assert not any(r.startswith("noqa/") for r in rules)


# ------------------------------------------------------------------- baseline


def test_baseline_grandfathers_by_fingerprint_and_rots_loudly(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/x.py", """
        def f():
            try:
                g()
            except Exception:
                pass
    """)
    # no baseline: the finding fails the run
    report, findings, rules = _run(tmp_path)
    assert "fence/silent-except" in rules
    fp = next(f for f in findings if f.rule == "fence/silent-except").fingerprint
    # baselined: same tree passes, finding reported as grandfathered
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"entries": {fp: "pre-analyzer site"}}))
    report, findings, rules = _run(tmp_path, baseline=bl)
    assert "fence/silent-except" not in rules
    assert report["ok"] is True and fp in report["baselined"]
    # the finding moves lines but keeps its source text: STILL grandfathered
    src = (tmp_path / "spark_rapids_ml_tpu/x.py").read_text()
    (tmp_path / "spark_rapids_ml_tpu/x.py").write_text("\n\n" + src)
    report, _, rules = _run(tmp_path, baseline=bl)
    assert report["ok"] is True
    # fix the finding: the stale entry itself fails the run
    (tmp_path / "spark_rapids_ml_tpu/x.py").write_text("def f():\n    return 1\n")
    report, findings, rules = _run(tmp_path, baseline=bl)
    assert "baseline/stale" in rules and report["ok"] is False


# ------------------------------------------ acceptance: the real tree + CLI


def test_real_tree_is_clean_within_budget_and_purity_baseline_empty():
    baseline = REPO / DEFAULT_BASELINE
    doc = json.loads(baseline.read_text())
    assert not any(k.startswith("purity/") for k in doc["entries"]), (
        "trace-purity findings must be fixed, never baselined"
    )
    report = run_analysis(REPO, baseline_path=baseline)
    findings = report["_finding_objs"]
    assert not findings, "\n".join(f.render() for f in findings)
    # per-file, not absolute: the tree grows every PR and this guard is about
    # the shared-parse design staying LINEAR (one parse, all rules), not about
    # tree size — 100ms/file is ~2x the loaded-machine per-file cost
    budget_s = max(10.0, 0.1 * report["files_analyzed"])
    assert report["elapsed_s"] < budget_s, (
        f"shared-parse budget blown: {report['elapsed_s']}s for "
        f"{report['files_analyzed']} files (budget {budget_s:.1f}s)"
    )


def test_reintroduced_config_read_in_kernel_fails_run(tmp_path):
    # the exact regression the acceptance clause names: put a _config.get
    # back inside a real compiled_kernel impl and the analyzer must exit 1
    real = (REPO / "spark_rapids_ml_tpu/ops/_precision.py").read_text()
    assert "# noqa: purity/config-read" in real
    stripped = real.replace(
        "  # noqa: purity/config-read — trace-epoch keyed", ""
    )
    _write(tmp_path, "spark_rapids_ml_tpu/ops/_precision.py", "")
    (tmp_path / "spark_rapids_ml_tpu/ops/_precision.py").write_text(stripped)
    _write(tmp_path, "spark_rapids_ml_tpu/ops/kern.py", """
        from ..observability.device import compiled_kernel
        from ._precision import pdot

        @compiled_kernel("kern.gram")
        def _gram(x):
            return pdot(x, x)
    """)
    report, _, rules = _run(tmp_path)
    assert "purity/config-read" in rules and report["ok"] is False


def test_cli_list_rules_explain_and_json(tmp_path):
    env_cwd = str(REPO)
    out = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--list-rules"],
        cwd=env_cwd, capture_output=True, text=True,
    )
    assert out.returncode == 0
    listed = {ln.split()[0] for ln in out.stdout.splitlines() if ln.strip()}
    assert set(all_rules()) == listed
    out = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--explain",
         "locks/order-cycle"],
        cwd=env_cwd, capture_output=True, text=True,
    )
    assert out.returncode == 0 and "canonical" in out.stdout
    out = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--explain", "nope/nope"],
        cwd=env_cwd, capture_output=True, text=True,
    )
    assert out.returncode == 2
    # --json on the real tree: exits 0, parses, carries the contract fields
    report_path = tmp_path / "report.json"
    out = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--json", "--out",
         str(report_path), "--max-seconds", "10"],
        cwd=env_cwd, capture_output=True, text=True,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    doc = json.loads(report_path.read_text())
    assert doc["ok"] is True and doc["findings"] == []
    assert doc["files_analyzed"] > 150


def test_write_baseline_refuses_purity_findings(tmp_path):
    _write(tmp_path, "spark_rapids_ml_tpu/ops/foo.py", PURITY_TP)
    out = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--root", str(tmp_path),
         "--write-baseline", "--baseline", str(tmp_path / "b.json"),
         "spark_rapids_ml_tpu"],
        cwd=str(REPO), capture_output=True, text=True,
    )
    assert out.returncode == 1
    assert "never" in out.stdout and "purity/config-read" in out.stdout
    assert not (tmp_path / "b.json").exists()

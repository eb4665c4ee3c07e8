"""chip_smoke.py and the compile-cache helper, as far as a CPU can check them.

The smoke itself only runs on the chip (`python chip_smoke.py` through the chip
tool). Here: it REFUSES a CPU backend with a non-zero exit and no result line;
the cache helper leaves a set JAX_COMPILATION_CACHE_DIR alone and otherwise
resolves to the fixed path in the checkout; and the smoke's kernel checks — the
same functions, at tiny sizes, Pallas in interpret mode — still agree with the
XLA paths and the numpy references, so an API drift in ops/ shows up in tier-1
and not first on the chip."""

import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, timeout=300, capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""  # no result line, nothing at all
    assert "refusing to run" in proc.stderr and "'cpu'" in proc.stderr


def test_chip_smoke_fails_without_the_package(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo the
    script must fail too (here it fails on the CPU refusal first; on the chip
    the package import does it) — never a result line."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], env=env, timeout=300,
        capture_output=True, text=True, cwd=tmp_path,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


# ------------------------------------------------------------ compile cache


def test_cache_helper_leaves_a_set_env_dir_alone(monkeypatch, tmp_path):
    import jax

    from spark_rapids_ml_tpu.utils import enable_compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "elsewhere"))
    assert enable_compile_cache() == str(tmp_path / "elsewhere")
    assert calls == []  # jax reads the variable itself; nothing is set in code
    assert not os.path.exists(tmp_path / "elsewhere")


def test_cache_helper_resolves_to_the_fixed_checkout_path(monkeypatch):
    import jax

    from spark_rapids_ml_tpu.utils import enable_compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert enable_compile_cache() == want
    assert enable_compile_cache() == want  # no pid, no time, no temp name
    assert calls == [("jax_compilation_cache_dir", want)] * 2
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_entry_points_call_the_cache_helper():
    """chip_smoke.py and the autotune CLI each enable the cache; no other file
    in the tree names a cache directory."""
    for rel in ("chip_smoke.py",
                os.path.join("spark_rapids_ml_tpu", "autotune", "__main__.py")):
        with open(os.path.join(REPO, rel)) as f:
            assert "enable_compile_cache()" in f.read(), rel
    offenders = []
    for root, _, files in os.walk(os.path.join(REPO, "spark_rapids_ml_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path) as f:
                if "jax_compilation_cache_dir" in f.read() and not path.endswith(
                        os.path.join("utils", "__init__.py")):
                    offenders.append(path)
    assert offenders == []


# ------------------------------------------- the smoke's checks, tiny, on CPU


def test_smoke_references_agree_with_a_tiny_fit(smoke):
    """The host-side reference helpers the legs lean on (numpy Lloyd fixed
    point, covariance, center matching) against a real tiny fit."""
    import numpy as np

    from spark_rapids_ml_tpu.clustering import KMeans

    X, true_centers = smoke.make_blobs(6000, 16, 5, seed=3)
    km = KMeans(k=5, maxIter=10, seed=7).fit(X)
    C = np.asarray(km.cluster_centers_)
    labels, inertia = smoke.np_assign(X, C)
    means, counts = smoke.np_cluster_means(X, labels, 5)
    assert np.abs(C - means).max() <= 1e-4
    assert abs(km.inertia_ - inertia) / inertia <= 1e-5
    assert counts.tolist() == list(km.summary.clusterSizes)
    perm = smoke.match_rows(C, true_centers)
    assert sorted(perm.tolist()) == list(range(5))
    mean, cov = smoke.np_covariance(X)
    np.testing.assert_allclose(mean, X.mean(axis=0, dtype=np.float64), atol=1e-6)
    np.testing.assert_allclose(
        cov, np.cov(X.astype(np.float64), rowvar=False), rtol=1e-5, atol=1e-5)
    rep = km.fit_report_
    assert smoke._counter(rep, "kmeans.lloyd_path", path="xla") == 1
    assert smoke._counter(rep, "device.kernel_calls", kernel="kmeans.lloyd_fit") == 1
    assert smoke._counter(rep, "device.kernel_calls", kernel="nope") == 0


@pytest.mark.parametrize("name,call", [
    ("gram d=128", lambda s: s.check_gram(128, n=1500, gate=False)),
    # d=512 picks a 512-row block: the label-row layout Mosaic refused before
    ("normal-eq d=512", lambda s: s.check_normal_eq(512, n=1500, gate=False)),
    ("normal-eq d=128", lambda s: s.check_normal_eq(128, n=3000, gate=False)),
    ("lloyd masked", lambda s: s.check_lloyd(True, n=2000, d=32, k=16, gate=False)),
    ("lloyd weighted", lambda s: s.check_lloyd(False, n=2000, d=32, k=16, gate=False)),
    ("assign", lambda s: s.check_assign(n=2000, d=32, k=16, gate=False)),
    ("top-k", lambda s: s.check_topk(10, n=1500, d=16, nq=40, gate=False)),
    ("count", lambda s: s.check_count(n=2000, d=16, gate=False)),
    ("histograms", lambda s: s.check_histograms(n=2000, d=8, gate=False)),
    # 1500 = 1024 + 476 samples past the last whole block; a width off the tiling
    ("logistic eval", lambda s: s.check_logistic_eval(300, n=1500, gate=False)),
])
def test_smoke_kernel_check_passes_tiny_in_interpret_mode(smoke, name, call):
    assert isinstance(call(smoke), str)


def test_smoke_kernel_gates_are_closed_off_tpu(smoke):
    """`gate=True` is the chip's assertion that the default route reaches the
    kernel: off-TPU every auto gate is closed, so the same call must fail."""
    with pytest.raises(AssertionError, match="gate is closed|is closed"):
        smoke.check_assign(n=500, d=32, k=128)
    with pytest.raises(AssertionError, match="is closed"):
        smoke.check_gram(128, n=500)
    with pytest.raises(AssertionError, match="the gate says .False, 'platform'."):
        smoke.check_logistic_eval(300, n=500)

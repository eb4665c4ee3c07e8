"""Fault-tolerant serving fleet (serving/fleet.py + serving/router.py +
reliability/chaos.py; docs/design.md §7c).

The load-bearing contracts (ISSUE acceptance):
  * FAILOVER: a chaos-killed replica's queued and in-flight requests replay
    onto survivors — ZERO failed client requests across a mid-run kill — and
    the dead replica restarts from the registry's pinned weights and rejoins
    rotation LIVE;
  * ZERO-COMPILE RECOVERY: a replica restart re-warms through the
    process-wide compiled-kernel cache, so the kill -> recover -> serve cycle
    adds ZERO new `device.compile` entries (the PR-15 counter-assert pattern);
  * HEALTH: consecutive batch failures walk LIVE -> DEGRADED -> DEAD; the
    monitor restarts DEAD replicas; success flips DEGRADED back to LIVE;
  * ROUTING/ADMISSION: health-weighted least-outstanding pick, per-tenant
    fair-share shedding, and every rejection bounded (QueueFull/NoLiveReplicas
    carrying a Retry-After hint, never a bare error);
  * SINGLE-DISPATCHER ROBUSTNESS: a `serving_execute` fault fails exactly
    that batch's requests with a retryable error and the queue keeps serving;
  * DEADLINES: an expired client deadline fails fast at submit and expires
    queued requests at batch close (DeadlineExpired, never executed);
  * HTTP: structured `error_kind` on every failure (incl. the catch-all 500,
    counted `serving.errors{model=,kind=}`) and Retry-After headers on
    429/503.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import config, profiling, serving
from spark_rapids_ml_tpu.reliability import (
    ReplicaKilled,
    chaos_point,
    parse_chaos_spec,
    reset_chaos,
    reset_faults,
)
from spark_rapids_ml_tpu.serving import (
    DeadlineExpired,
    MicroBatcher,
    ModelRegistry,
    NoLiveReplicas,
    QueueFull,
    Router,
    resolve_replicas,
)
from spark_rapids_ml_tpu.serving.fleet import (
    DEAD,
    DEGRADED,
    LIVE,
    ReplicaFleet,
    ReplicaHandle,
)

FLEET_KEYS = (
    "serving.replicas",
    "serving.heartbeat_timeout_s",
    "serving.hedge_after_p99_frac",
    "serving.max_batch_rows",
    "serving.max_wait_ms",
    "serving.queue_depth",
    "serving.bucket_min_rows",
    "serving.request_timeout_s",
    "reliability.chaos_spec",
    "reliability.fault_spec",
    "observability.http_port",
)


@pytest.fixture(autouse=True)
def fleet_env():
    yield
    serving.stop_serving()
    for key in FLEET_KEYS:
        config.unset(key)
    reset_faults()
    reset_chaos()


rng = np.random.default_rng(11)
X_BLOBS = np.concatenate(
    [rng.normal(-3, 1, (96, 6)), rng.normal(3, 1, (96, 6))]
).astype(np.float32)


@pytest.fixture(scope="module")
def km():
    from spark_rapids_ml_tpu.clustering import KMeans

    pdf = pd.DataFrame({"features": list(X_BLOBS)})
    return KMeans(k=3, maxIter=4, seed=5).fit(pdf)


def _ctr(prefix: str, also: str = "") -> int:
    """Sum counters by name prefix (label-order agnostic), optionally
    filtered to keys containing `also`."""
    return sum(
        v for k, v in profiling.counter_totals().items()
        if k.startswith(prefix) and also in k
    )


def _compile_counters():
    return {
        k: v for k, v in profiling.counter_totals().items()
        if k.startswith("device.compile{")
    }


def _wait_until(cond, timeout=10.0, tick=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(tick)
    return cond()


# ------------------------------------------------------------- chaos grammar


def test_parse_chaos_spec_grammar():
    specs = parse_chaos_spec(
        "serving_execute:replica=1:after=3:action=kill;"
        "serving_heartbeat:replica=0:action=hang:sleep=0.5;"
        "serving_dispatch:action=slow:times=8"
    )
    assert [s.site for s in specs] == [
        "serving_execute", "serving_heartbeat", "serving_dispatch",
    ]
    assert specs[0].replica == 1 and specs[0].after == 3
    assert specs[0].action == "kill" and specs[0].times == 1
    assert specs[1].action == "hang" and specs[1].sleep == 0.5
    assert specs[2].action == "slow" and specs[2].times == 8
    assert parse_chaos_spec("") == []


@pytest.mark.parametrize("bad", [
    "serving_execute:batch=2:after=3",  # contradictory ordinal filters
    "serving_execute:action=explode",  # unknown verb
    "serving_execute:replica",  # field without '='
    "serving_execute:wat=1",  # unknown field
    "serving_execute:sleep=-1",  # negative duration
    ":action=kill",  # empty site
])
def test_parse_chaos_spec_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_chaos_spec(bad)


def test_chaos_point_deterministic_filters_and_budget():
    config.set(
        "reliability.chaos_spec", "serving_execute:replica=1:batch=2"
    )
    reset_chaos()
    # wrong replica / wrong ordinal: no-ops
    chaos_point("serving_execute", replica=0, batch=2)
    chaos_point("serving_execute", replica=1, batch=1)
    chaos_point("serving_heartbeat", replica=1, batch=2)
    with pytest.raises(ReplicaKilled) as ei:
        chaos_point("serving_execute", replica=1, batch=2)
    assert ei.value.replica == 1 and ei.value.batch == 2
    # times=1 (default): the clause is spent — same call is now a no-op
    chaos_point("serving_execute", replica=1, batch=2)
    reset_chaos()  # re-armed: fires again
    with pytest.raises(ReplicaKilled):
        chaos_point("serving_execute", replica=1, batch=2)


def test_resolve_replicas_config_pin_and_default():
    config.set("serving.replicas", 3)
    assert resolve_replicas() == 3
    config.unset("serving.replicas")
    assert resolve_replicas() >= 1  # 0 = auto -> at least one replica


# ------------------------------------------------------------------- routing


class _FakeBatcher:
    def __init__(self, pending=0, rate=None):
        self._pending, self._rate = pending, rate

    def pending(self):
        return self._pending

    def drain_rate(self):
        return self._rate


class _FakeReplica:
    def __init__(self, index, state=LIVE, outstanding=0, pending=0, rate=None):
        self.index = index
        self.state = state
        self.outstanding = outstanding
        self.batcher = _FakeBatcher(pending, rate)

    def routable(self):
        return self.state in (LIVE, DEGRADED)

    def health_weight(self):
        return 1.0 if self.state == LIVE else 3.0


def test_router_pick_least_outstanding_health_weighted():
    reps = [
        _FakeReplica(0, outstanding=3),
        _FakeReplica(1, outstanding=1),
        _FakeReplica(2, state=DEAD),
    ]
    router = Router("m", reps)
    assert router.pick().index == 1  # least loaded routable
    assert router.pick(exclude=(1,)).index == 0  # dead replica never picked
    assert router.pick(exclude=(0, 1)) is None
    # queued depth counts as load too
    reps[1].batcher = _FakeBatcher(pending=5)
    assert router.pick().index == 0
    # DEGRADED costs 3x: a busier LIVE replica still wins
    reps2 = [
        _FakeReplica(0, outstanding=2),
        _FakeReplica(1, state=DEGRADED, outstanding=1),
    ]
    assert Router("m", reps2).pick().index == 0
    # index-ordered tie-break keeps routing deterministic
    reps3 = [_FakeReplica(0), _FakeReplica(1)]
    assert Router("m", reps3).pick().index == 0


def test_router_admission_fleet_cap_and_tenant_fair_share():
    config.set("serving.queue_depth", 4)
    router = Router("m", [_FakeReplica(0)])
    before = _ctr("serving.shed_total{", "model=m")
    for _ in range(2):
        router.admit("a")
    router.admit("b")  # b activates: 2 active tenants, share = 4 // 2 = 2
    with pytest.raises(QueueFull) as ei:
        router.admit("a")  # a is AT its fair share — sheds against itself
    assert ei.value.retry_after_s is not None
    assert ei.value.retry_after_s >= 0.05
    assert _ctr("serving.tenant_shed{", "tenant=a") >= 1
    router.admit("b")  # b is under its share: still admitted
    with pytest.raises(QueueFull):  # fleet-wide cap: 4 outstanding >= depth
        router.admit("c")
    assert _ctr("serving.shed_total{", "model=m") >= before + 2
    router.release("a")
    router.admit("a")  # refund reopened the slot
    assert router.tenants() == {"a": 2, "b": 2}


def test_router_no_live_replicas_carries_retry_after():
    config.set("serving.heartbeat_timeout_s", 0.7)
    router = Router("m", [_FakeReplica(0, state=DEAD)])
    assert not router.has_routable()
    err = router.no_live()
    assert isinstance(err, NoLiveReplicas)
    assert err.retry_after_s == pytest.approx(0.7)
    assert _ctr("serving.no_live_replicas{", "model=m") >= 1


# ------------------------------------------------- fleet health state machine


def _stub_fleet(n=2, execute=None, spawn_gate=None):
    """A ReplicaFleet over stub replicas: `execute(stage, n_valid, idx)`
    returns the output dict; `spawn_gate()` False makes respawn fail."""

    def default_exec(stage, n_valid, idx):
        return {"y": stage[:, 0].copy() + idx}

    run = execute or default_exec

    def spawn(i):
        if spawn_gate is not None and not spawn_gate():
            raise RuntimeError("spawn refused by test gate")
        return ReplicaHandle(
            execute=lambda stage, n_valid, _i=i: run(stage, n_valid, _i),
            warm=set(),
        )

    return ReplicaFleet("stub", 3, n, spawn=spawn, retire=lambda i: None)


def _fleet_config(hb=0.2):
    config.set("serving.heartbeat_timeout_s", hb)
    config.set("serving.max_wait_ms", 1.0)
    config.set("serving.max_batch_rows", 64)
    config.set("serving.bucket_min_rows", 4)
    config.set("serving.queue_depth", 16)


def test_fleet_degrade_dead_restart_lifecycle():
    """Consecutive batch failures walk a replica LIVE -> DEGRADED -> DEAD
    (clients see the triggering retryable error once the RetryPolicy budget
    is spent — never a hang); the monitor restarts DEAD replicas and they
    rejoin LIVE with the failure count cleared."""
    _fleet_config()
    failing = {"on": True}

    def flaky(stage, n_valid, idx):
        if failing["on"]:
            raise OSError(f"injected replica {idx} failure")
        return {"y": stage[:, 0].copy()}

    fleet = _stub_fleet(2, execute=flaky)
    try:
        assert [r.state for r in fleet._replicas] == [LIVE, LIVE]
        for _ in range(3):
            fut = fleet.submit(np.ones((2, 3), np.float32))
            with pytest.raises(OSError):  # replay budget exhausted
                fut.result(timeout=20)
        assert _ctr("serving.replayed{", "model=stub") >= 2
        assert _ctr("serving.replica_deaths{", "model=stub") >= 1
        assert _ctr("serving.failovers{", "model=stub") >= 1
        failing["on"] = False
        assert _wait_until(
            lambda: all(r.state == LIVE for r in fleet._replicas)
        ), [r.state for r in fleet._replicas]
        assert sum(r.restarts for r in fleet._replicas) >= 1
        assert _ctr("serving.replica_restarts{", "model=stub") >= 1
        out = fleet.submit(np.ones((2, 3), np.float32)).result(timeout=20)
        assert out["y"].shape == (2,)
        assert all(r.consec_failures == 0 for r in fleet._replicas)
    finally:
        fleet.close()


def test_fleet_degraded_flips_back_live_on_success():
    _fleet_config()
    fleet = _stub_fleet(2)
    try:
        rep = fleet._replicas[1]
        fleet._note_failure(rep, OSError("x"))
        assert rep.state == LIVE  # one failure is noise
        fleet._note_failure(rep, OSError("x"))
        assert rep.state == DEGRADED
        fleet._note_success(rep)
        assert rep.state == LIVE and rep.consec_failures == 0
    finally:
        fleet.close()


def test_fleet_no_live_replicas_until_restart_lands():
    _fleet_config()
    gate = {"open": True}
    fleet = _stub_fleet(1, spawn_gate=lambda: gate["open"])
    try:
        gate["open"] = False  # restarts fail: the fleet stays dark
        fleet._declare_dead(fleet._replicas[0], "test")
        assert _wait_until(
            lambda: fleet._replicas[0].state in (DEAD, "RECOVERING"), 2.0
        )
        with pytest.raises(NoLiveReplicas) as ei:
            fleet.submit(np.ones((1, 3), np.float32))
        assert ei.value.retry_after_s is not None
        assert fleet.live_count() == 0
        gate["open"] = True  # restart can land now
        assert _wait_until(lambda: fleet._replicas[0].state == LIVE)
        out = fleet.submit(np.ones((1, 3), np.float32)).result(timeout=20)
        assert out["y"].shape == (1,)
        assert fleet._replicas[0].restarts >= 1
    finally:
        fleet.close()


def test_fleet_hedges_past_p99_cutoff_and_fast_replica_wins():
    _fleet_config(hb=2.0)  # long heartbeat: the stall must NOT look dead
    config.set("serving.hedge_after_p99_frac", 0.5)
    release = threading.Event()

    def ex(stage, n_valid, idx):
        if idx == 0 and not release.is_set():
            release.wait(10)
        return {"y": stage[:, 0].copy() + idx}

    fleet = _stub_fleet(2, execute=ex)
    try:
        # prime the p99 estimate so the hedge cutoff is tiny and known
        fleet._latencies.extend([0.01] * 30)
        fut = fleet.submit(np.ones((2, 3), np.float32))
        out = fut.result(timeout=10)  # resolves while replica 0 is stalled
        assert np.array_equal(out["y"], np.full(2, 2.0, np.float32))  # r1 won
        assert _ctr("serving.hedges{", "model=stub") >= 1
        assert _ctr("serving.hedge_wins{", "model=stub") >= 1
    finally:
        release.set()
        fleet.close()


# --------------------------------------- registry-backed fleet: E2E failover


def test_fleet_chaos_kill_failover_zero_failed_requests_zero_compiles(km):
    """The tentpole acceptance path: a 2-replica registry fleet takes a
    deterministic chaos kill mid-stream — zero failed client requests, the
    dead replica restarts from the registry's pinned weights, rejoins LIVE,
    and the whole kill -> recover -> serve cycle adds zero new compiles."""
    config.set("serving.replicas", 2)
    config.set("serving.heartbeat_timeout_s", 0.3)
    registry = ModelRegistry()
    try:
        registry.register("km", km, prewarm=True)
        entry = registry._models["km"]
        assert entry.fleet is not None and entry.fleet.live_count() == 2
        ref = km._serving_predict(X_BLOBS)["prediction"]
        before = _compile_counters()
        deaths0 = _ctr("serving.replica_deaths{", "model=km")

        # replica 0's third dispatched batch dies; queued + in-flight work
        # replays onto replica 1 (times=1: one incident)
        config.set(
            "reliability.chaos_spec",
            "serving_execute:replica=0:after=2:action=kill",
        )
        reset_chaos()
        for i in range(12):
            n = 3 + (i % 5)
            out = registry.predict("km", X_BLOBS[:n], timeout=20.0)
            assert np.array_equal(out["prediction"], ref[:n]), i
        assert _ctr("serving.replica_deaths{", "model=km") == deaths0 + 1
        assert _ctr("serving.replayed{", "model=km") >= 1

        # the dead replica restarts from pinned weights and rejoins LIVE
        assert _wait_until(
            lambda: entry.fleet.live_count() == 2
            and all(r.state == LIVE for r in entry.fleet._replicas), 15.0
        ), registry.stats("km")["replicas"]
        assert sum(r.restarts for r in entry.fleet._replicas) >= 1

        # post-recovery traffic lands on both replicas' warm executables
        for i in range(6):
            out = registry.predict("km", X_BLOBS[: 4 + i], timeout=20.0)
            assert np.array_equal(out["prediction"], ref[: 4 + i])
        after = _compile_counters()
        new = {
            k: after.get(k, 0) - before.get(k, 0)
            for k in set(after) | set(before)
            if after.get(k, 0) != before.get(k, 0)
        }
        assert not new, f"failover/recovery compiled: {new}"

        stats = registry.stats("km")
        assert stats["live_replicas"] == 2
        assert {r["replica"] for r in stats["replicas"]} == {0, 1}
    finally:
        registry.close()


def test_single_dispatcher_execute_fault_fails_batch_without_wedging(km):
    """serving_execute fault in single-dispatcher mode: exactly that batch's
    requests fail with a retryable error; the dispatcher loop and queue keep
    serving afterwards."""
    from spark_rapids_ml_tpu.reliability import is_transient

    config.set("reliability.fault_spec", "serving_execute:batch=2:raise=OSError")
    reset_faults()
    registry = ModelRegistry()
    try:
        registry.register("km", km, prewarm=False)
        assert registry._models["km"].fleet is None  # single-dispatcher mode
        ref = km._serving_predict(X_BLOBS)["prediction"]
        for _ in range(2):  # batches 0 and 1 serve normally
            out = registry.predict("km", X_BLOBS[:4], timeout=20.0)
            assert np.array_equal(out["prediction"], ref[:4])
        with pytest.raises(OSError) as ei:  # batch 2 takes the injected fault
            registry.predict("km", X_BLOBS[:4], timeout=20.0)
        assert is_transient(ei.value)  # a client/fleet MAY replay it
        for _ in range(3):  # the queue did not stall
            out = registry.predict("km", X_BLOBS[:5], timeout=20.0)
            assert np.array_equal(out["prediction"], ref[:5])
    finally:
        registry.close()


# ------------------------------------------------------------------ deadlines


def test_deadline_fail_fast_at_submit_and_expiry_at_batch_close():
    config.set("serving.max_wait_ms", 1.0)
    config.set("serving.max_batch_rows", 8)
    release = threading.Event()
    started = threading.Event()

    def slow(stage, n_valid):
        started.set()
        assert release.wait(timeout=30)
        return {"y": stage[:, 0].copy()}

    b = MicroBatcher("dl", 3, execute=slow)
    try:
        expired0 = _ctr("serving.expired{", "model=dl")
        with pytest.raises(DeadlineExpired):  # already dead at submit
            b.submit(
                np.zeros((2, 3), np.float32),
                deadline_ts=time.perf_counter() - 0.1,
            )
        f1 = b.submit(np.zeros((2, 3), np.float32))
        assert started.wait(timeout=10)  # f1's batch now stalls the queue
        f2 = b.submit(
            np.zeros((2, 3), np.float32),
            deadline_ts=time.perf_counter() + 0.05,
        )
        time.sleep(0.2)  # f2's deadline passes while it sits in the queue
        release.set()
        assert f1.result(timeout=30)["y"].shape == (2,)
        with pytest.raises(DeadlineExpired):  # expired at batch close
            f2.result(timeout=30)
        assert _ctr("serving.expired{", "model=dl") >= expired0 + 2
    finally:
        release.set()
        b.stop()


def test_queue_full_retry_after_derived_from_drain_rate():
    config.set("serving.queue_depth", 2)
    config.set("serving.max_batch_rows", 4)
    config.set("serving.max_wait_ms", 1.0)
    release = threading.Event()
    started = threading.Event()

    def slow(stage, n_valid):
        started.set()
        assert release.wait(timeout=30)
        return {"y": stage[:, 0].copy()}

    b = MicroBatcher("rafull", 3, execute=slow)
    try:
        shed0 = _ctr("serving.shed_total{", "model=rafull")
        futs = [b.submit(np.zeros((4, 3), np.float32))]
        assert started.wait(timeout=10)
        futs += [b.submit(np.zeros((4, 3), np.float32)) for _ in range(2)]
        with pytest.raises(QueueFull) as ei:
            b.submit(np.zeros((4, 3), np.float32))
        assert ei.value.retry_after_s is not None
        assert 0.05 <= ei.value.retry_after_s <= 30.0
        assert _ctr("serving.shed_total{", "model=rafull") >= shed0 + 1
        release.set()
        for f in futs:
            f.result(timeout=30)
    finally:
        release.set()
        b.stop()


# ----------------------------------------------------------------------- HTTP


def test_http_structured_error_kinds_and_retry_after_headers(km):
    addr = serving.start_serving(port=0)
    assert addr is not None
    port = addr[1]
    serving.register_model("km", km, prewarm=False)
    reg = serving.get_registry()
    orig_predict = reg.predict

    def post(path, doc):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(doc).encode(),
            method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=15) as resp:
                return resp.status, json.loads(resp.read()), dict(resp.headers)
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read()), dict(e.headers)

    body = {"instances": X_BLOBS[:2].tolist()}
    try:
        code, doc, _ = post("/v1/models/km:predict", body)
        assert code == 200 and doc["rows"] == 2

        code, doc, _ = post("/v1/models/nope:predict", body)
        assert code == 404 and doc["error_kind"] == "KeyError"

        def raiser(exc):
            def _r(*a, **k):
                raise exc
            return _r

        reg.predict = raiser(QueueFull("saturated", retry_after_s=2.2))
        code, doc, headers = post("/v1/models/km:predict", body)
        assert code == 429 and doc["error_kind"] == "QueueFull"
        assert doc["retry_after_s"] == pytest.approx(2.2)
        assert headers["Retry-After"] == "3"  # ceil, whole seconds

        reg.predict = raiser(NoLiveReplicas("dark", retry_after_s=0.4))
        code, doc, headers = post("/v1/models/km:predict", body)
        assert code == 503 and doc["error_kind"] == "NoLiveReplicas"
        assert headers["Retry-After"] == "1"

        reg.predict = raiser(DeadlineExpired("client gave up"))
        code, doc, _ = post("/v1/models/km:predict", body)
        assert code == 504 and doc["error_kind"] == "DeadlineExpired"

        errors0 = _ctr("serving.errors{", "kind=RuntimeError")
        reg.predict = raiser(RuntimeError("boom"))
        code, doc, _ = post("/v1/models/km:predict", body)
        assert code == 500 and doc["error_kind"] == "RuntimeError"
        assert _ctr("serving.errors{", "kind=RuntimeError") == errors0 + 1

        reg.predict = orig_predict
        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5).read())
        assert health["serving"]["models"]["km"]["pending"] == 0
    finally:
        reg.predict = orig_predict
        serving.stop_serving()


def test_healthz_reports_fleet_replica_states(km):
    config.set("serving.replicas", 2)
    addr = serving.start_serving(port=0)
    port = addr[1]
    serving.register_model("km", km, prewarm=False)
    try:
        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5).read())
        model = health["serving"]["models"]["km"]
        assert model["live_replicas"] == 2
        assert [r["state"] for r in model["replicas"]] == [LIVE, LIVE]
    finally:
        serving.stop_serving()

#
# Fault-tolerant serving fleet — replicated dispatchers with health-driven
# failover (docs/design.md §7c).
#
# The single-dispatcher serving plane (batcher.py + registry.py) leaves one
# failure domain per model: a hung or killed dispatcher strands every
# queued and in-flight request. This module replicates that domain N ways
# (`serving.replicas`), Podracer-style (arXiv:2104.06272 — decoupled feed
# threads fanning into replicated batched accelerator steps), and makes the
# MLlib failure-transparency contract (arXiv:1505.06807) hold for serving:
#
#   * N replicas per model, each its OWN MicroBatcher + model clone + HBM
#     weight stream ("serving_model", "<name>#r<i>" cache keys) over disjoint
#     local device groups (degenerating to the one local device on CPU);
#   * a router (router.py) in front: health-weighted least-outstanding
#     routing, per-tenant fair admission, bounded shedding with Retry-After;
#   * a per-replica health state machine LIVE -> DEGRADED -> DEAD ->
#     RECOVERING -> LIVE, fed by dispatcher heartbeats (batcher.last_beat),
#     consecutive-failure counts, and the chaos/fault sites
#     (`serving_execute`/`serving_heartbeat`); transitions are flight-recorded
#     and exported as the `serving.replica_state{model=,replica=}` gauge;
#   * FAILOVER: on replica death, still-queued requests are stolen from its
#     queue and in-flight requests are duplicated onto survivors — predict is
#     pure, so replay is idempotent; replays run under the
#     `reliability.RetryPolicy` attempt/deadline budget (counted
#     `serving.replayed{model=}`); with no survivor, requests PARK until the
#     monitor restarts a replica (bounded by the client deadline);
#   * HEDGING (optional): when a request has waited longer than
#     `serving.hedge_after_p99_frac` x the observed p99, a duplicate issues
#     to a second replica and the first resolution wins — the loser is
#     cancelled (counters `serving.hedges`/`serving.hedge_wins{model=}`);
#   * RECOVERY: dead replicas restart from the registry's pinned host
#     weights with the full bucketed AOT pre-warm BEFORE rejoining rotation,
#     so recovery never causes a warm-path compile (the pre-warm replays
#     through the process-wide compiled-kernel cache — CI-asserted).
#

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

from .. import config as _config
from ..observability import flight as _flight
from ..observability.runs import counter_inc, event as _obs_event, gauge_set
from ..reliability.chaos import ReplicaKilled, chaos_point
from ..reliability.faults import fault_point, is_transient
from ..reliability.policy import RetryPolicy
from ..utils import get_logger
from .batcher import DeadlineExpired, MicroBatcher, QueueFull, ServingError
from .router import NoLiveReplicas, Router

_logger = get_logger("serving.fleet")

# ------------------------------------------------------ health state machine

LIVE = "LIVE"  # in rotation, weight 1
DEGRADED = "DEGRADED"  # in rotation, weighted away from; failures mounting
DEAD = "DEAD"  # out of rotation; queue stolen, in-flight replayed
RECOVERING = "RECOVERING"  # restarting from pinned weights + pre-warm

_STATE_CODE = {LIVE: 0, DEGRADED: 1, DEAD: 2, RECOVERING: 3}

# consecutive batch failures that demote LIVE -> DEGRADED, and DEGRADED ->
# DEAD: a replica that keeps failing batches is indistinguishable from a sick
# device even when its thread still answers heartbeats
_DEGRADE_AFTER_FAILURES = 2
_DEAD_AFTER_FAILURES = 4

_LATENCY_WINDOW = 512  # client latencies kept for the hedge p99 estimate
_HEDGE_MIN_SAMPLES = 20


def resolve_replicas() -> int:
    """Replica count for a new fleet: the `serving.replicas` config pin;
    `0` (the default) means auto -> 1."""
    cfg = int(_config.get("serving.replicas") or 0)
    return cfg if cfg >= 1 else 1


def _hedge_frac() -> float:
    return float(_config.get("serving.hedge_after_p99_frac") or 0.0)


class ReplicaHandle(NamedTuple):
    """What the registry's spawn callback returns: the bound padded-predict
    closure for one fresh replica entry, and its pre-warmed bucket set."""

    execute: Callable[[Any, int], Dict[str, Any]]
    warm: set


class _Replica:
    """One replica's rotation state. Mutated only under the fleet lock
    (except `batches`, which only the replica's own dispatcher advances)."""

    __slots__ = ("index", "state", "batcher", "outstanding", "consec_failures",
                 "batches", "restarts", "inflight_reqs")

    def __init__(self, index: int):
        self.index = index
        self.state = RECOVERING
        self.batcher: Optional[MicroBatcher] = None
        self.outstanding = 0  # dispatched, not yet resolved
        self.consec_failures = 0
        self.batches = 0  # execute ordinal (persists across restarts)
        self.restarts = 0
        self.inflight_reqs: Dict[int, "_FleetRequest"] = {}

    # duck-typed surface the router reads (router.py stays fleet-free)
    def routable(self) -> bool:
        return self.state in (LIVE, DEGRADED)

    def health_weight(self) -> float:
        return 1.0 if self.state == LIVE else 3.0


class _FleetRequest:
    """One client request's fleet-side bookkeeping: the client Future, which
    replicas currently hold a copy, and the replay/hedge state."""

    __slots__ = ("X", "tenant", "deadline_ts", "enqueue_ts", "client", "lock",
                 "attempts", "hedged", "primary", "inflight", "released",
                 "trace")

    def __init__(self, X: Any, tenant: str, deadline_ts: Optional[float],
                 trace: Any = None):
        self.X = X
        self.tenant = tenant
        self.deadline_ts = deadline_ts
        self.trace = trace  # RequestTrace or None (§6l)
        self.enqueue_ts = time.perf_counter()
        self.client: "Future[Dict[str, Any]]" = Future()
        self.lock = threading.Lock()
        self.attempts = 0  # failed dispatches so far (RetryPolicy budget)
        self.hedged = False
        self.primary: Optional[int] = None
        self.inflight: Dict[int, Future] = {}  # replica index -> inner Future
        self.released = False


class ReplicaFleet:
    """N dispatcher replicas for one served model, fronted by a Router, kept
    honest by a health-monitor thread. The registry supplies `spawn(i)` (build
    a fresh replica entry from the pinned weights: clone, upload, pre-warm;
    returns a ReplicaHandle) and `retire(i)` (drop that replica's HBM
    stream) — the fleet never touches model internals itself."""

    def __init__(self, name: str, n_cols: int, n_replicas: int,
                 spawn: Callable[..., ReplicaHandle],
                 retire: Callable[[int], None]):
        self.name = name
        self.n_cols = int(n_cols)
        self._spawn = spawn
        self._retire = retire
        # disjoint device groups drawn from the active Partitioner's mesh —
        # NOT the raw local-device list — so a pod-sliced mesh hands each
        # replica its slice of this host (parallel/partitioner.py)
        from ..parallel.partitioner import active_partitioner

        self.device_groups = active_partitioner().replica_device_groups(
            max(1, int(n_replicas))
        )
        # spawn callbacks predating device groups take only the index
        import inspect

        try:
            self._spawn_takes_devices = (
                len(inspect.signature(spawn).parameters) >= 2
            )
        except (TypeError, ValueError):  # pragma: no cover — builtins
            self._spawn_takes_devices = False
        self._lock = threading.RLock()
        self._stop = False
        self._seq = 0
        self._outstanding: "set[_FleetRequest]" = set()
        self._parked: List[_FleetRequest] = []
        self._latencies: "deque[float]" = deque(maxlen=_LATENCY_WINDOW)
        self._replicas: List[_Replica] = []
        for i in range(max(1, int(n_replicas))):
            rep = _Replica(i)
            self._boot(rep)
            self._replicas.append(rep)
        self.router = Router(name, self._replicas)
        self._monitor = threading.Thread(
            target=self._monitor_loop,
            name=f"srml-serving-fleet-{name}", daemon=True,
        )
        self._monitor.start()

    # ------------------------------------------------------------- replica mgmt

    def _boot(self, rep: _Replica) -> None:
        """Build (or rebuild) one replica from the registry's pinned weights:
        spawn the entry (upload + AOT pre-warm), wrap its execute with the
        chaos/liveness guard, start a fresh dispatcher."""
        if self._spawn_takes_devices:
            handle = self._spawn(rep.index, self.device_groups[rep.index])
        else:
            handle = self._spawn(rep.index)
        rep.batcher = MicroBatcher(
            self.name, self.n_cols,
            execute=self._wrap_execute(rep, handle.execute),
            warm_buckets=handle.warm,
            labels={"model": self.name, "replica": str(rep.index)},
            thread_suffix=f"#r{rep.index}",
        )
        self._set_state(rep, LIVE)

    def _wrap_execute(self, rep: _Replica, execute: Callable) -> Callable:
        def _run(stage: Any, n_valid: int) -> Dict[str, Any]:
            b = rep.batches
            rep.batches += 1
            if rep.state == DEAD:
                # declared dead while this batch waited: fail it replayably
                # instead of executing on a replica out of rotation
                raise ReplicaKilled("serving_execute", rep.index, b)
            chaos_point("serving_execute", replica=rep.index, batch=b)
            return execute(stage, n_valid)

        return _run

    def _set_state(self, rep: _Replica, state: str) -> None:
        with self._lock:
            prev, rep.state = rep.state, state
        gauge_set(
            "serving.replica_state", _STATE_CODE[state],
            model=self.name, replica=str(rep.index),
        )
        if prev != state:
            _flight.note(
                "serving.replica_state", model=self.name, replica=rep.index,
                state=state, prev=prev,
            )

    def _declare_dead(self, rep: _Replica, cause: str) -> None:
        """Take a replica out of rotation and make its requests whole: steal
        its still-queued requests (their futures fail replayably) and
        duplicate its in-flight ones onto survivors. Idempotent."""
        with self._lock:
            if rep.state in (DEAD, RECOVERING):
                return
            rep.state = DEAD
            inflight = list(rep.inflight_reqs.values())
        gauge_set(
            "serving.replica_state", _STATE_CODE[DEAD],
            model=self.name, replica=str(rep.index),
        )
        counter_inc(
            "serving.replica_deaths", 1,
            model=self.name, replica=str(rep.index),
        )
        counter_inc("serving.failovers", 1, model=self.name)
        _flight.note(
            "serving.replica_dead", model=self.name, replica=rep.index,
            cause=cause,
        )
        _obs_event(
            "replica_dead", model=self.name, replica=rep.index, cause=cause,
        )
        _logger.warning(
            "serving replica %s#r%d declared DEAD (%s); failing over",
            self.name, rep.index, cause,
        )
        assert rep.batcher is not None
        steal_now = time.perf_counter()
        for r in rep.batcher.steal_pending():
            # the inner futures carry fleet callbacks: failing them with
            # ReplicaKilled routes each stolen request into the replay path
            if r.trace is not None:
                # the dead dispatcher will never close this queue span itself
                r.trace.add_span("serving.queue", r.enqueue_ts, steal_now,
                             parent_id=r.trace.root_span_id,
                             attrs={"model": self.name,
                                    "replica": str(rep.index)},
                             status="stolen")
                r.trace.add_event("queue_steal", model=self.name,
                                  replica=rep.index, cause=cause)
            if r.future.set_running_or_notify_cancel():
                r.future.set_exception(
                    ReplicaKilled("serving_dispatch", rep.index)
                )
        for freq in inflight:
            # the batch may be hung inside the dead replica; predict is pure,
            # so duplicate it now — first resolution wins, the loser is dropped
            self._try_replay(
                freq, rep.index, ReplicaKilled("serving_execute", rep.index),
            )

    def _restart(self, rep: _Replica) -> None:
        """DEAD -> RECOVERING -> LIVE: abandon the old dispatcher, drop the
        dead clone's weight stream, respawn from the registry's pinned
        weights with the full AOT pre-warm, rejoin rotation. A failed restart
        returns the replica to DEAD for the next monitor tick."""
        with self._lock:
            if rep.state != DEAD:
                return
            rep.state = RECOVERING
        gauge_set(
            "serving.replica_state", _STATE_CODE[RECOVERING],
            model=self.name, replica=str(rep.index),
        )
        _flight.note(
            "serving.replica_recovering", model=self.name, replica=rep.index,
        )
        if rep.batcher is not None:
            try:
                # short join: a hung dispatcher is a daemon thread we abandon
                rep.batcher.stop(timeout=0.2)
            except Exception:  # noqa: fence/silent-except — already dead
                pass
        try:
            self._retire(rep.index)
            self._boot(rep)
        except Exception as e:
            _logger.warning(
                "serving replica %s#r%d restart failed (%s: %s); will retry",
                self.name, rep.index, type(e).__name__, e,
            )
            self._set_state(rep, DEAD)
            return
        with self._lock:
            rep.consec_failures = 0
            rep.restarts += 1
        counter_inc(
            "serving.replica_restarts", 1,
            model=self.name, replica=str(rep.index),
        )
        _obs_event("replica_restarted", model=self.name, replica=rep.index)
        _logger.info(
            "serving replica %s#r%d recovered and rejoined rotation",
            self.name, rep.index,
        )

    def _note_failure(self, rep: _Replica, exc: BaseException) -> None:
        demote = False
        with self._lock:
            rep.consec_failures += 1
            if rep.state == LIVE and \
                    rep.consec_failures >= _DEGRADE_AFTER_FAILURES:
                rep.state = DEGRADED
                gauge_set(
                    "serving.replica_state", _STATE_CODE[DEGRADED],
                    model=self.name, replica=str(rep.index),
                )
                _flight.note(
                    "serving.replica_degraded", model=self.name,
                    replica=rep.index, error=type(exc).__name__,
                )
            elif rep.state == DEGRADED and \
                    rep.consec_failures >= _DEAD_AFTER_FAILURES:
                demote = True
        if demote:
            self._declare_dead(rep, f"failures:{type(exc).__name__}")

    def _note_success(self, rep: _Replica) -> None:
        with self._lock:
            rep.consec_failures = 0
            if rep.state == DEGRADED:
                rep.state = LIVE
            else:
                return
        gauge_set(
            "serving.replica_state", _STATE_CODE[LIVE],
            model=self.name, replica=str(rep.index),
        )

    # ------------------------------------------------------------- client side

    def submit(self, X: Any, deadline_ts: Optional[float] = None,
               tenant: Optional[str] = None,
               trace: Any = None) -> "Future[Dict[str, Any]]":
        """Admit + route one request; the returned Future survives replica
        death (replayed), hedging (first resolution wins), and restarts
        (parked until a replica recovers) — it fails only on non-retryable
        errors, an exhausted RetryPolicy, or the client's own deadline."""
        tenant = tenant or "-"
        try:
            self.router.admit(tenant)  # raises QueueFull (429 + Retry-After)
        except QueueFull:
            if trace is not None:
                trace.add_event("tenant_shed", model=self.name, tenant=tenant)
            raise
        freq = _FleetRequest(X, tenant, deadline_ts, trace=trace)
        with self._lock:
            self._outstanding.add(freq)
        try:
            self._dispatch(freq, first=True)
        except BaseException:
            self._finalize(freq)
            raise
        return freq.client

    def _next_seq(self) -> int:
        with self._lock:
            self._seq += 1
            return self._seq - 1

    def _dispatch(self, freq: _FleetRequest, exclude: Tuple[int, ...] = (),
                  first: bool = False) -> None:
        """Route + enqueue on the cheapest routable replica, skipping full
        queues. On the submit path (`first`) total failure raises to the
        caller; on replay/hedge paths it settles the client future or parks
        the request for the monitor."""
        seq = self._next_seq()
        try:
            fault_point("serving_dispatch", batch=seq)
            chaos_point("serving_dispatch", batch=seq)
        except Exception as e:
            if first:
                raise
            self._settle_err(freq, e)
            return
        tried = set(exclude)
        while True:
            rep = self.router.pick(tuple(tried))
            if rep is None:
                break
            try:
                if self._enqueue_on(rep, freq):
                    return
            except Exception as e:
                if first:
                    raise
                self._settle_err(freq, e)
                return
            tried.add(rep.index)  # that queue is full — try the next one
        if self.router.has_routable():
            counter_inc("serving.shed_total", 1, model=self.name)
            err = QueueFull(
                f"every replica queue of '{self.name}' is full",
                retry_after_s=self.router._fleet_retry_after_s(),
            )
            if first:
                raise err
            self._settle_err(freq, err)
            return
        if first:
            raise self.router.no_live()
        self._park(freq)

    def _enqueue_on(self, rep: _Replica, freq: _FleetRequest) -> bool:
        """One replica attempt; False on that replica's backpressure."""
        assert rep.batcher is not None
        try:
            inner = rep.batcher.submit(freq.X, deadline_ts=freq.deadline_ts,
                                       trace=freq.trace)
        except QueueFull:
            return False
        with self._lock:
            rep.outstanding += 1
            rep.inflight_reqs[id(freq)] = freq
        with freq.lock:
            freq.inflight[rep.index] = inner
            if freq.primary is None:
                freq.primary = rep.index
        inner.add_done_callback(
            lambda f, _r=rep: self._on_inner_done(freq, _r, f)
        )
        return True

    def _on_inner_done(self, freq: _FleetRequest, rep: _Replica,
                       fut: Future) -> None:
        with self._lock:
            rep.outstanding = max(0, rep.outstanding - 1)
            rep.inflight_reqs.pop(id(freq), None)
        with freq.lock:
            freq.inflight.pop(rep.index, None)
        if fut.cancelled():
            return  # hedge loser — already settled by the winner
        exc = fut.exception()
        if exc is None:
            with freq.lock:
                hedge_win = (
                    freq.hedged and freq.primary is not None
                    and rep.index != freq.primary and not freq.client.done()
                )
            if self._settle_ok(freq, fut.result(), rep.index):
                self._note_success(rep)
                self._latencies.append(time.perf_counter() - freq.enqueue_ts)
                if hedge_win:
                    counter_inc("serving.hedge_wins", 1, model=self.name)
                    if freq.trace is not None:
                        freq.trace.add_event("hedge_won", model=self.name,
                                             replica=rep.index)
            return
        if isinstance(exc, ReplicaKilled):
            self._declare_dead(rep, "killed")
        elif isinstance(exc, DeadlineExpired):
            self._settle_err(freq, exc)
            return
        else:
            self._note_failure(rep, exc)
        if isinstance(exc, ReplicaKilled) or is_transient(exc):
            self._try_replay(freq, rep.index, exc)
        else:
            self._settle_err(freq, exc)

    def _try_replay(self, freq: _FleetRequest, failed_idx: int,
                    exc: BaseException) -> None:
        """Replay one failed/stranded request under the RetryPolicy budget
        and the client deadline; exhaustion settles the client with the
        triggering failure. Cross-replica replay does NOT back off — the
        incident was the replica, not the request."""
        policy = RetryPolicy.from_config()
        now = time.perf_counter()
        with freq.lock:
            if freq.client.done():
                return
            freq.attempts += 1
            attempts = freq.attempts
        expired = freq.deadline_ts is not None and now >= freq.deadline_ts
        if expired or policy.give_up(
            attempts, now - freq.enqueue_ts, site="serving_replay"
        ):
            self._settle_err(freq, exc)
            return
        counter_inc("serving.replayed", 1, model=self.name)
        _obs_event(
            "serving_replay", model=self.name, replica=failed_idx,
            attempt=attempts, error=type(exc).__name__,
        )
        if freq.trace is not None:
            freq.trace.add_event(
                "failover_replay", model=self.name, replica=failed_idx,
                attempt=attempts, error=type(exc).__name__,
            )
        try:
            self._dispatch(freq, exclude=(failed_idx,))
        except Exception as e:
            self._settle_err(freq, e)

    # ------------------------------------------------------------- settlement

    def _settle_ok(self, freq: _FleetRequest, out: Dict[str, Any],
                   winner_idx: int) -> bool:
        losers: List[Future] = []
        with freq.lock:
            if freq.client.done():
                return False
            ok = freq.client.set_running_or_notify_cancel()
            if ok:
                freq.client.set_result(out)
            losers = [
                f for i, f in freq.inflight.items() if i != winner_idx
            ]
        self._finalize(freq)
        for f in losers:
            f.cancel()  # cancel the hedge/replay loser
        return ok

    def _settle_err(self, freq: _FleetRequest, exc: BaseException) -> None:
        with freq.lock:
            if not freq.client.done():
                if freq.client.set_running_or_notify_cancel():
                    freq.client.set_exception(exc)
        self._finalize(freq)

    def _finalize(self, freq: _FleetRequest) -> None:
        with self._lock:
            self._outstanding.discard(freq)
        with freq.lock:
            if freq.released:
                return
            freq.released = True
        self.router.release(freq.tenant)

    # ---------------------------------------------------------------- parking

    def _park(self, freq: _FleetRequest) -> None:
        """No routable replica: hold the request for the monitor to replay
        once a restart lands, bounded by the fleet-wide admission cap."""
        with self._lock:
            over = len(self._parked) >= int(_config.get("serving.queue_depth"))
            if not over:
                self._parked.append(freq)
        if over:
            self._settle_err(freq, self.router.no_live())
        else:
            counter_inc("serving.parked", 1, model=self.name)

    def _drain_parked(self) -> None:
        with self._lock:
            if not self._parked:
                return
            parked, self._parked = self._parked, []
        now = time.perf_counter()
        for freq in parked:
            with freq.lock:
                if freq.client.done():
                    continue
            if freq.deadline_ts is not None and now >= freq.deadline_ts:
                if freq.trace is not None:
                    freq.trace.add_event("deadline_expired", at="parked",
                                         model=self.name)
                self._settle_err(freq, DeadlineExpired(
                    "request deadline expired while no replica was live"
                ))
                continue
            if not self.router.has_routable():
                with self._lock:
                    self._parked.append(freq)
                continue
            try:
                self._dispatch(freq)
            except Exception as e:
                self._settle_err(freq, e)

    # ---------------------------------------------------------------- hedging

    def _p99_estimate(self) -> Optional[float]:
        lat = sorted(self._latencies)
        if len(lat) < _HEDGE_MIN_SAMPLES:
            return None
        return lat[min(len(lat) - 1, int(0.99 * len(lat)))]

    def _maybe_hedge(self) -> None:
        frac = _hedge_frac()
        if frac <= 0:
            return
        p99 = self._p99_estimate()
        if p99 is None:
            return
        cutoff = frac * p99
        now = time.perf_counter()
        with self._lock:
            outstanding = list(self._outstanding)
        for freq in outstanding:
            with freq.lock:
                if (
                    freq.hedged or freq.client.done()
                    or len(freq.inflight) != 1
                    or now - freq.enqueue_ts <= cutoff
                    or (freq.deadline_ts is not None
                        and now >= freq.deadline_ts)
                ):
                    continue
                current = next(iter(freq.inflight))
                freq.hedged = True
            rep2 = self.router.pick((current,))
            if rep2 is None:
                with freq.lock:
                    freq.hedged = False  # nobody to hedge onto; try later
                continue
            counter_inc("serving.hedges", 1, model=self.name)
            _obs_event(
                "serving_hedge", model=self.name, replica=rep2.index,
                waited_s=round(now - freq.enqueue_ts, 4),
            )
            if freq.trace is not None:
                freq.trace.add_event(
                    "hedge_issued", model=self.name, replica=rep2.index,
                    waited_s=round(now - freq.enqueue_ts, 4),
                )
            try:
                self._enqueue_on(rep2, freq)
            except Exception:  # hedge is optional: the primary is still live
                with freq.lock:
                    freq.hedged = False

    # ---------------------------------------------------------------- monitor

    def _tick_s(self) -> float:
        hb = float(_config.get("serving.heartbeat_timeout_s"))
        return min(max(hb / 4.0, 0.01), 0.1)

    def _monitor_loop(self) -> None:
        while not self._stop:
            time.sleep(self._tick_s())
            if self._stop:
                return
            try:
                self._monitor_once()
            except Exception as e:  # the monitor must outlive any incident
                _logger.warning(
                    "fleet monitor error for '%s': %s: %s",
                    self.name, type(e).__name__, e,
                )

    def _monitor_once(self) -> None:
        hb = float(_config.get("serving.heartbeat_timeout_s"))
        for rep in self._replicas:
            if self._stop:
                return
            if rep.state == DEAD:
                self._restart(rep)
                continue
            if rep.state == RECOVERING:
                continue
            try:
                fault_point("serving_heartbeat", batch=rep.index)
                chaos_point(
                    "serving_heartbeat", replica=rep.index, batch=rep.index
                )
            except ReplicaKilled:
                self._declare_dead(rep, "chaos-heartbeat")
                continue
            except Exception as e:
                # an unanswerable probe is indistinguishable from a hang
                self._declare_dead(rep, f"heartbeat-{type(e).__name__}")
                continue
            assert rep.batcher is not None
            stale = rep.batcher.heartbeat_age_s() > hb
            busy = rep.outstanding > 0 or rep.batcher.pending() > 0
            if not rep.batcher.alive() or (stale and busy):
                self._declare_dead(
                    rep,
                    "thread-death" if not rep.batcher.alive()
                    else "heartbeat-timeout",
                )
        self._maybe_hedge()
        self._drain_parked()

    # --------------------------------------------------------------- lifecycle

    def close(self) -> None:
        """Stop the monitor, drain+join every replica dispatcher, fail parked
        requests, drop every replica weight stream."""
        self._stop = True
        self._monitor.join(timeout=2.0)
        with self._lock:
            parked, self._parked = self._parked, []
        for freq in parked:
            self._settle_err(
                freq, ServingError(f"fleet '{self.name}' is shutting down")
            )
        for rep in self._replicas:
            if rep.batcher is not None:
                rep.batcher.stop()
            try:
                self._retire(rep.index)
            except Exception:  # noqa: fence/silent-except — teardown best-effort
                pass

    # -------------------------------------------------------------------- views

    def pending(self) -> int:
        with self._lock:
            parked = len(self._parked)
        return parked + sum(
            rep.batcher.pending() for rep in self._replicas
            if rep.batcher is not None
        )

    def health_view(self) -> List[Dict[str, Any]]:
        """Per-replica health for stats()/healthz: the state machine's word
        on who is serving."""
        out = []
        for rep in self._replicas:
            b = rep.batcher
            out.append({
                "replica": rep.index,
                "state": rep.state,
                "outstanding": rep.outstanding,
                "pending": b.pending() if b is not None else 0,
                "heartbeat_age_s": (
                    round(b.heartbeat_age_s(), 3) if b is not None else None
                ),
                "consec_failures": rep.consec_failures,
                "restarts": rep.restarts,
                "batches": rep.batches,
                "devices": [str(d) for d in self.device_groups[rep.index]],
            })
        return out

    def live_count(self) -> int:
        return sum(1 for r in self._replicas if r.routable())


__all__ = [
    "DEAD",
    "DEGRADED",
    "LIVE",
    "RECOVERING",
    "NoLiveReplicas",
    "ReplicaFleet",
    "ReplicaHandle",
    "resolve_replicas",
]

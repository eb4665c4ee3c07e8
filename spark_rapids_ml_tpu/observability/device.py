#
# Device-performance plane: XLA cost-analysis capture, HBM telemetry, and
# compile accounting (docs/design.md §6f).
#
# Two things live here:
#
#   * compiled_kernel — the one choke point for every jitted kernel the library
#     compiles. It wraps jax.jit with an AOT lower().compile() cache keyed by
#     (kernel name, shape/dtype/sharding signature, static values): each NEW
#     signature is compiled exactly once with its wall time recorded
#     (`device.compile_s{kernel=}`), and the compiled executable's
#     cost_analysis() (flops, bytes accessed, transcendentals) and
#     memory_analysis() (argument/output/temp bytes) are captured per
#     executable. Calls then run the cached executable directly and ATTRIBUTE
#     the analyzed flops/bytes to the innermost open trace span, so FitRun /
#     TransformRun span nodes carry the compiler's count of the work, not just
#     wall time. Inlines through the plain jitted call under tracing
#     (vmap/grad/nested jit) or when `observability.device_enabled` is off. An
#     AOT compile or executable-call failure RAISES: there is no silent jit
#     fallback, so the compile accounting always describes what actually runs.
#
#   * HBM telemetry — `local_devices()[*].memory_stats()` sampled at span
#     boundaries (rate-limited) into the `device.hbm_bytes_in_use` gauge plus a
#     per-run `device.hbm_peak_bytes` gauge, cross-checkable against the batch
#     cache's `cache.bytes_resident`. Platforms without memory_stats (CPU,
#     older runtimes) are detected ONCE and the gauges are simply absent — no
#     warning spam.
#
# The records are counts, never shares of a peak: XLA's HLO cost analysis counts
# a dynamic-trip-count while_loop body ONCE (lloyd_fit's 30 iterations as one),
# and span wall time is the host's. What share of the chip a kernel reaches is
# the benchmark's question, answered from the device trace against
# `cellbench/peaks.json`.
#
# The analyzer (fence/device-analysis-off-plane) bans direct `.cost_analysis()` /
# `.memory_stats()` calls outside this module so the capture contract (and its
# graceful-degrade guarantees) cannot be bypassed.
#

from __future__ import annotations

import functools
import inspect
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import weakref

from .. import config as _config
from ..utils import get_logger
from . import comm as _comm
from . import runs as _runs

_logger = get_logger("observability.device")

_lock = threading.RLock()

# every live CompiledKernel, so reset_device_plane can drop executable caches
# (tests; a stale cache would report zero compiles for work a fresh process
# would have compiled)
_kernels: "weakref.WeakSet[CompiledKernel]" = weakref.WeakSet()

# (kernel name, signature key) -> cost record dict; process-global like the
# shape-bucket registry (inference.py) — executables are process-global too
_records: Dict[Tuple[str, Any], Dict[str, Any]] = {}

# membership cap mirroring inference._MAX_TRACKED_SIGS: a fully-ragged caller
# must not grow the record table forever (each unseen signature still counts
# its compile; it just stops being remembered)
_MAX_RECORDS = 4096

# monotone process-wide compile count: unlike len(_records) (capped, clearable
# per test) this NEVER decreases, so a before/after delta is a reliable
# "did the warm path compile anything?" probe (trace plane compile-vs-cached)
_compiles_total = 0

# memory_stats support: None = unknown, False = probed and absent (never
# re-probed, never warned — the graceful-degrade contract), True = live
_hbm_supported: Optional[bool] = None
_hbm_last_sample = 0.0
# consecutive EXCEPTIONS from the probe (distinct from a clean "no stats"
# verdict): transient backend-init errors retry; persistent ones give up
_hbm_probe_errors = 0
_HBM_MAX_PROBE_ERRORS = 3

# per-run HBM peaks, keyed by run_id while the run is open
_run_peaks: Dict[str, int] = {}

_errors_logged: set = set()


def _enabled() -> bool:
    return bool(_config.get("observability.device_enabled"))


def _log_once(key: str, msg: str, *args: Any) -> None:
    with _lock:
        if key in _errors_logged:
            return
        _errors_logged.add(key)
    _logger.warning(msg, *args)


def reset_device_plane() -> None:
    """Clear all process-global device-plane state (tests)."""
    global _hbm_supported, _hbm_last_sample, _hbm_probe_errors
    with _lock:
        _records.clear()
        _run_peaks.clear()
        _errors_logged.clear()
        _hbm_supported = None
        _hbm_last_sample = 0.0
        _hbm_probe_errors = 0
        _sharding_reprs.clear()
        for kernel in list(_kernels):
            kernel._cache.clear()


# ------------------------------------------------------------- compiled_kernel


# repr(sharding) is the expensive part of per-call signature capture, and
# sharding objects are shared across arrays/calls: cache reprs by identity.
# Values keep the sharding object ALIVE so a recycled id() can never alias a
# different sharding to a stale repr (bounded; a few thousand tiny objects).
_sharding_reprs: Dict[int, Tuple[Any, str]] = {}
_MAX_SHARDING_REPRS = 4096


def _sharding_key(x: Any) -> str:
    sh = getattr(x, "sharding", None)
    if sh is None:
        return "host"
    cached = _sharding_reprs.get(id(sh))
    if cached is not None and cached[0] is sh:
        return cached[1]
    try:
        r = repr(sh)
    except Exception:
        r = "?"
    if len(_sharding_reprs) < _MAX_SHARDING_REPRS:
        _sharding_reprs[id(sh)] = (sh, r)
    return r


def _leaf_key(x: Any) -> Tuple[Any, ...]:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return ("a", tuple(shape), str(dtype), _sharding_key(x))
    if isinstance(x, (bool, int, float, complex)):
        # python scalars are weak-typed dynamic args: one compile per TYPE,
        # never per value (keying on the value would manufacture a compile
        # storm jit itself does not have)
        return ("s", type(x).__name__)
    return ("o", type(x).__name__, repr(x)[:200])


class CompiledKernel:
    """Instrumented drop-in for a jitted kernel (see module header). The
    wrapped callable preserves jit semantics — same args, statics, donation —
    while owning the AOT executable cache and the cost capture."""

    def __init__(self, name: str, fn: Callable, jit_kwargs: Dict[str, Any]):
        self.name = name
        self._fn = fn
        self._jit_kwargs = jit_kwargs
        self._jit = self._make_jit(fn, jit_kwargs)
        # the trace epoch at which self._jit last traced (None: not yet)
        self._jit_epoch: Optional[Tuple[Tuple[str, str], ...]] = None
        self._cache: Dict[Any, Dict[str, Any]] = {}
        self._klock = threading.RLock()
        static_argnums = jit_kwargs.get("static_argnums") or ()
        static_argnames = jit_kwargs.get("static_argnames") or ()
        if isinstance(static_argnums, int):
            static_argnums = (static_argnums,)
        if isinstance(static_argnames, str):
            static_argnames = (static_argnames,)
        try:
            self._sig_obj: Optional[inspect.Signature] = inspect.signature(fn)
            params = list(self._sig_obj.parameters)
            self._params_list = list(self._sig_obj.parameters.values())
            if any(
                p.kind not in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
                for p in self._params_list
            ):
                # *args/**kwargs/keyword-only defy canonical positional form
                self._sig_obj = None
                self._params_list = []
        except (TypeError, ValueError):
            self._sig_obj = None
            self._params_list = []
        self._static_idx = set(int(i) for i in static_argnums)
        for nm in static_argnames:
            if nm in params:
                self._static_idx.add(params.index(nm))
        self._static_names = set(static_argnames) | {
            params[i] for i in self._static_idx if i < len(params)
        }
        functools.update_wrapper(self, fn)
        _kernels.add(self)

    @staticmethod
    def _make_jit(fn: Callable, jit_kwargs: Dict[str, Any]):
        import jax

        return jax.jit(fn, **jit_kwargs)

    def _jit_at_epoch(self):
        """The jit object to trace through. jit answers `lower()` and nested
        calls from its own trace cache, which is keyed by the wrapped function
        and the argument types and knows nothing of the trace epoch: once a
        shape has traced, a changed `parity_precision` would be answered with
        the old program. So when the epoch differs from the one this jit last
        traced at, it is replaced by a jit of a fresh wrapper of the same
        function (a fresh `jax.jit` of the same function object would hit the
        same cache), which has traced nothing. With an unchanged epoch this is
        the jit built at decoration, and nothing re-traces."""
        epoch = _trace_epoch()
        with self._klock:
            if self._jit_epoch != epoch:
                if self._jit_epoch is not None:
                    fn = self._fn

                    @functools.wraps(fn)  # same name, so the same program name
                    def retraced(*args: Any, **kwargs: Any):
                        return fn(*args, **kwargs)

                    self._jit = self._make_jit(retraced, self._jit_kwargs)
                self._jit_epoch = epoch
            return self._jit

    @property
    def jitted(self):
        """The underlying jax.jit-wrapped function (AOT helpers, tests)."""
        return self._jit_at_epoch()

    def __reduce__(self):
        # pickle BY REFERENCE (module attribute lookup), never by value: the
        # executable cache and the PjitFunction inside are not picklable, and
        # a shipped copy would be the wrong object anyway — barrier/UDF
        # closures must resolve to the worker process's own kernel
        return (_resolve_kernel, (self.__module__, self.__qualname__))

    def lower(self, *args: Any, **kwargs: Any):
        return self._jit_at_epoch().lower(*args, **kwargs)

    # ---- signature ----

    def _canon_positional(self, args):
        """Fast path for fully-positional calls — the hot-kernel call shape;
        skips inspect.Signature.bind on every streamed-batch invocation.
        Semantics identical to _canonicalize with empty kwargs."""
        ps = self._params_list
        if len(args) > len(ps):
            return None
        tail = []
        for p in ps[len(args):]:
            if (
                p.name in self._static_names
                and p.default is not inspect.Parameter.empty
            ):
                tail.append(p.default)
            else:
                break  # omitted DYNAMIC default: must stay omitted (baked)
        norm = tuple(args) + tuple(tail)
        statics_key = tuple(
            (ps[i].name, repr(norm[i]))
            for i in sorted(self._static_idx)
            if i < len(norm)
        )
        for p in ps[len(norm):]:
            if (
                p.name in self._static_names
                and p.default is not inspect.Parameter.empty
            ):
                statics_key += ((p.name, repr(p.default)),)
        return norm, statics_key

    def _canonicalize(self, args, kwargs):
        """Normalize a call to ONE positional form so call style (positional
        vs keyword vs omitted-default statics) cannot split the executable
        cache: `predict(X, C)` and `predict(X, C, cosine=False)` must be one
        signature, one compile. Returns (norm_args, statics_key), or None for
        call shapes that defy the canonical positional form (gaps after an
        omitted dynamic default, *args/**kwargs/keyword-only params) — those
        fall back to the style-sensitive split."""
        sig = self._sig_obj
        if sig is None:
            return None
        if not kwargs:
            return self._canon_positional(args)
        try:
            bound = sig.bind(*args, **kwargs)
        except TypeError:
            return None
        arguments = bound.arguments
        norm: List[Any] = []
        seen = set()
        for p in sig.parameters.values():
            if p.kind not in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
                return None
            if p.name in arguments:
                norm.append(arguments[p.name])
                seen.add(p.name)
            elif (
                p.name in self._static_names
                and p.default is not inspect.Parameter.empty
            ):
                # statics are compile-time values: applying the default here
                # is exactly what jit's signature binding does
                norm.append(p.default)
                seen.add(p.name)
            else:
                break  # omitted DYNAMIC default: must stay omitted (baked)
        if any(name not in seen for name in arguments):
            return None
        statics_key = tuple(
            (p.name, repr(arguments.get(p.name, p.default)))
            for p in sig.parameters.values()
            if p.name in self._static_names
            and (
                p.name in arguments
                or p.default is not inspect.Parameter.empty
            )
        )
        return tuple(norm), statics_key

    def _split(self, args: Tuple[Any, ...], kwargs: Dict[str, Any]):
        dyn_args = tuple(
            a for i, a in enumerate(args) if i not in self._static_idx
        )
        dyn_kwargs = {
            k: v for k, v in kwargs.items() if k not in self._static_names
        }
        statics = tuple(
            (f"@{i}", repr(args[i]))
            for i in sorted(self._static_idx)
            if i < len(args)
        ) + tuple(
            (k, repr(v))
            for k, v in sorted(kwargs.items())
            if k in self._static_names
        )
        return dyn_args, dyn_kwargs, statics

    def _signature(self, dyn_args, dyn_kwargs, statics):
        import jax

        leaves, treedef = jax.tree_util.tree_flatten((dyn_args, dyn_kwargs))
        if any(isinstance(l, jax.core.Tracer) for l in leaves):
            return None  # under trace: inline through the plain jit path
        # trace-affecting config rides in the signature (the trace epoch):
        # a kernel body that reads one of these keys at trace time can never
        # serve a STALE bake — changing the key re-keys the AOT cache, and
        # _compile_and_capture lowers through _jit_at_epoch, which re-traces
        # with the new value (lower() alone would not: it answers from jit's
        # trace cache). This is what licenses the one sanctioned trace-time
        # config read (ops/_precision.py::parity_precision).
        return (tuple(_leaf_key(l) for l in leaves), treedef,
                statics + _trace_epoch())

    # ---- compile + capture ----

    def _compile_and_capture(self, sig, args, kwargs) -> Dict[str, Any]:
        t0 = time.perf_counter()
        lowered = self._jit_at_epoch().lower(*args, **kwargs)
        exe = lowered.compile()
        compile_s = time.perf_counter() - t0
        cost = _extract_cost(exe, lowered)
        record = {
            "kernel": self.name,
            "signature": _sig_str(sig),
            "compile_s": round(compile_s, 6),
            "calls": 0,
            **cost,
        }
        # communication plane (§6h): walk the compiled module's HLO ONCE per
        # signature for collective ops/bytes/replica-groups; None (no HLO
        # surface on this runtime) just means no collective accounting
        try:
            collectives = _comm.collectives_from_executable(exe)
        except Exception as e:
            _log_once(f"comm:{self.name}",
                      "kernel %s: collective extraction failed (%s)",
                      self.name, e)
            collectives = None
        if collectives:
            record["collectives"] = collectives
        global _compiles_total
        with _lock:
            if len(_records) < _MAX_RECORDS:
                _records[(self.name, sig)] = record
            _compiles_total += 1
        _runs.counter_inc("device.compile", 1, kernel=self.name)
        _runs.observe("device.compile_s", compile_s, kernel=self.name)
        if not cost.get("analyzed", False):
            _runs.counter_inc("device.analysis_unavailable", 1, kernel=self.name)
        return {"exe": exe, "record": record}

    def __call__(self, *args: Any, **kwargs: Any):
        if not _enabled():
            return self._jit_at_epoch()(*args, **kwargs)
        canon = self._canonicalize(args, kwargs)
        if canon is not None:
            call_args, statics = canon
            call_kwargs: Dict[str, Any] = {}
            dyn_args = tuple(
                a for i, a in enumerate(call_args)
                if i not in self._static_idx
            )
            dyn_kwargs: Dict[str, Any] = {}
        else:
            call_args, call_kwargs = args, kwargs
            dyn_args, dyn_kwargs, statics = self._split(args, kwargs)
        sig = self._signature(dyn_args, dyn_kwargs, statics)
        if sig is None:  # tracer inputs: inline through the enclosing trace
            return self._jit_at_epoch()(*args, **kwargs)
        entry = self._cache.get(sig)
        if entry is None:
            with self._klock:
                entry = self._cache.get(sig)
                if entry is None:
                    # a compile refusal (Mosaic, VMEM, HBM) raises to the
                    # caller: `device.compile{kernel=}` must describe what runs
                    entry = self._compile_and_capture(
                        sig, call_args, call_kwargs
                    )
                    self._cache[sig] = entry
        record = entry["record"]
        out = entry["exe"](*dyn_args, **dyn_kwargs)
        with _lock:
            record["calls"] += 1
        _attribute_call(self.name, record)
        return out


# config keys whose values a kernel body may read AT TRACE TIME (today only
# parity_precision — ops/_precision.py). Folding the current value into every
# AOT signature makes such reads stale-proof: see CompiledKernel._signature
# and _jit_at_epoch (which covers the plain-jit paths too: the device plane
# switched off, and a kernel called under another's trace).
_TRACE_EPOCH_KEYS = ("parity_precision",)


def _trace_epoch() -> Tuple[Tuple[str, str], ...]:
    return tuple(
        (f"cfg:{k}", repr(_config.get(k))) for k in _TRACE_EPOCH_KEYS
    )


def _sig_str(sig) -> str:
    leaves, treedef, statics = sig
    arrays = ",".join(
        f"{l[1]}:{l[2]}" for l in leaves if l and l[0] == "a"
    )
    st = ",".join(f"{k}={v}" for k, v in statics)
    return f"[{arrays}]" + (f"{{{st}}}" if st else "")


def _extract_cost(exe: Any, lowered: Any) -> Dict[str, Any]:
    """Flops/bytes/transcendentals + memory breakdown from the compiled
    executable (falling back to the unoptimized-HLO analysis on the Lowered).
    Missing APIs degrade to analyzed=False — gauges/keys absent, no spam."""
    out: Dict[str, Any] = {
        "flops": 0.0, "bytes_accessed": 0.0, "transcendentals": 0.0,
        "analyzed": False,
    }
    ca = None
    for src in (exe, lowered):
        try:
            ca = src.cost_analysis()
        except Exception:
            ca = None
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if isinstance(ca, Mapping):
            break
        ca = None
    if isinstance(ca, Mapping):
        out["flops"] = max(float(ca.get("flops", 0.0) or 0.0), 0.0)
        out["bytes_accessed"] = max(
            float(ca.get("bytes accessed", 0.0) or 0.0), 0.0
        )
        out["transcendentals"] = max(
            float(ca.get("transcendentals", 0.0) or 0.0), 0.0
        )
        out["analyzed"] = True
    try:
        ma = exe.memory_analysis()
        arg_b = int(getattr(ma, "argument_size_in_bytes", 0))
        out_b = int(getattr(ma, "output_size_in_bytes", 0))
        tmp_b = int(getattr(ma, "temp_size_in_bytes", 0))
        out["argument_bytes"] = arg_b
        out["output_bytes"] = out_b
        out["temp_bytes"] = tmp_b
        out["peak_bytes"] = arg_b + out_b + tmp_b
    except Exception:  # noqa: fence/silent-except — memory_analysis absent here
        pass
    return out


def _attribute_call(kernel: str, record: Mapping[str, Any]) -> None:
    """Per-call metric + span attribution: counters into the fan-out, analyzed
    flops/bytes onto the innermost open span of THIS thread."""
    flops = float(record.get("flops", 0.0))
    bytes_accessed = float(record.get("bytes_accessed", 0.0))
    _runs.counter_inc("device.kernel_calls", 1, kernel=kernel)
    if flops:
        _runs.counter_inc("device.flops_total", int(flops), kernel=kernel)
    if bytes_accessed:
        _runs.counter_inc("device.bytes_total", int(bytes_accessed),
                          kernel=kernel)
    # collective accounting (§6h): per call, each kind's analyzed ops/bytes
    # aggregate like flops do — uniform `comm.*` names across every kernel
    comm_bytes = 0.0
    collectives = record.get("collectives")
    if collectives:
        for kind, st in collectives.items():
            _runs.counter_inc("comm.collective_ops", int(st.get("ops", 0)),
                              kind=kind, kernel=kernel)
            b = int(st.get("bytes", 0))
            if b:
                _runs.counter_inc("comm.collective_bytes", b,
                                  kind=kind, kernel=kernel)
            comm_bytes += b
    stack = _runs._span_stack()
    if not stack:
        return
    node = stack[-1]
    dev = node.attrs.get("device")
    if dev is None:
        dev = node.attrs["device"] = {
            "flops": 0.0, "bytes": 0.0, "transcendentals": 0.0,
            "comm_bytes": 0.0, "calls": 0, "kernels": {},
        }
    dev["flops"] += flops
    dev["bytes"] += bytes_accessed
    dev["transcendentals"] += float(record.get("transcendentals", 0.0))
    dev["comm_bytes"] = dev.get("comm_bytes", 0.0) + comm_bytes
    dev["calls"] += 1
    dev["kernels"][kernel] = dev["kernels"].get(kernel, 0) + 1


def _resolve_kernel(module: str, qualname: str) -> "CompiledKernel":
    """Unpickle hook: resolve a kernel back to the live module-level instance."""
    import importlib

    obj: Any = importlib.import_module(module)
    for part in qualname.split("."):
        obj = getattr(obj, part)
    return obj


def compiled_kernel(name: str, **jit_kwargs: Any) -> Callable:
    """Decorator factory: `@compiled_kernel("ops.foo", static_argnames=(...))`
    replaces `@functools.partial(jax.jit, static_argnames=(...))` for every
    kernel the library compiles — same call semantics, plus compile accounting,
    cost/memory analysis capture and span attribution."""

    def wrap(fn: Callable) -> CompiledKernel:
        return CompiledKernel(name, fn, jit_kwargs)

    return wrap


# ------------------------------------------------------------- record surface


def kernel_cost_records() -> List[Dict[str, Any]]:
    """Snapshot of every captured (kernel, signature) cost record."""
    with _lock:
        return [dict(r) for r in _records.values()]


def kernel_cost(name: str) -> Optional[Dict[str, Any]]:
    """The most recently COMPILED record for a kernel name (None when the
    kernel never compiled under the device plane)."""
    with _lock:
        recs = [r for (k, _), r in _records.items() if k == name]
    return dict(recs[-1]) if recs else None


def compile_count(name: str) -> int:
    """Distinct compiled signatures recorded for a kernel name."""
    with _lock:
        return sum(1 for (k, _) in _records if k == name)


def compiles_total() -> int:
    """Monotone process-wide compile count (never reset; see the module-level
    `_compiles_total` note). A zero before/after delta across a code path is
    the compile-vs-cached verdict trace execute spans report."""
    with _lock:
        return _compiles_total


def device_report_section(registry: Any = None) -> Optional[Dict[str, Any]]:
    """The `device` section of a run report: the platform + the cost
    records of the kernels THIS run actually called (filtered via the run's
    `device.kernel_calls{kernel=}` counters — a long-lived serving process
    must not serialize the whole process-global record table into every
    transform report). Without a registry, every record is returned (the
    process-global surface)."""
    if not _enabled():
        return None
    records = kernel_cost_records()
    run_calls: Optional[Dict[str, Any]] = None
    if registry is not None:
        from .registry import split_label_key

        run_calls = {}
        for key, v in (
            registry.snapshot().get("counters") or {}
        ).items():
            name, labels = split_label_key(key)
            if name == "device.kernel_calls" and labels.get("kernel"):
                run_calls[labels["kernel"]] = v
        records = [r for r in records if r["kernel"] in run_calls]
    # the in-memory record's `calls` is PROCESS-cumulative (it outlives runs);
    # a per-run report must not present it as this run's count — rename it and
    # attach the run-scoped count from the registry
    for r in records:
        r["process_calls"] = r.pop("calls", 0)
        if run_calls is not None:
            r["run_calls"] = run_calls.get(r["kernel"], 0)
    if not records:
        return None
    import jax

    return {
        "platform": str(jax.local_devices()[0].platform),
        "kernels": records,
    }


# -------------------------------------------------------------- HBM telemetry


def sample_hbm(force: bool = False) -> Optional[int]:
    """Sample local devices' memory_stats() into the hbm gauges; returns total
    bytes in use, or None when unsupported/rate-limited. First probe returning
    no stats on any device marks the platform unsupported permanently: gauges
    simply never appear (no warning spam — CPU is the common case)."""
    global _hbm_supported, _hbm_last_sample, _hbm_probe_errors
    if not _enabled() or not bool(_config.get("observability.hbm_sampling")):
        return None
    if _hbm_supported is False or "jax" not in sys.modules:
        return None
    now = time.monotonic()
    interval = float(_config.get("observability.hbm_sample_interval_s"))
    if not force and now - _hbm_last_sample < interval:
        return None
    _hbm_last_sample = now
    try:
        import jax

        totals = []
        for d in jax.local_devices():
            ms = getattr(d, "memory_stats", None)
            stats = ms() if callable(ms) else None
            if stats and "bytes_in_use" in stats:
                totals.append(int(stats["bytes_in_use"]))
    except Exception as e:
        # a TRANSIENT probe error (backend still initializing) must not take
        # the unsupported-platform fast path permanently; give up only after
        # several consecutive failures
        _hbm_probe_errors += 1
        _log_once("hbm", "memory_stats sampling failed: %s", e)
        if _hbm_probe_errors >= _HBM_MAX_PROBE_ERRORS:
            _hbm_supported = False
        return None
    _hbm_probe_errors = 0
    if not totals:
        # clean probe, no stats on any device: genuinely unsupported (CPU)
        _hbm_supported = False
        return None
    _hbm_supported = True
    total = sum(totals)
    _runs.gauge_set("device.hbm_bytes_in_use", total)
    _runs._flight().note_hbm(total)
    with _lock:
        for run_id, peak in list(_run_peaks.items()):
            if total > peak:
                _run_peaks[run_id] = total
    return total


def hbm_free_bytes(device: Any) -> Optional[int]:
    """What `device`'s allocator has left of its limit, None where it does not
    say (the CPU backend). A direct reading for a placement that decides on
    it, neither sampled nor rate-limited: `parallel/partitioner.py`'s chunked
    upload needs room for a second table for a moment."""
    stats = device.memory_stats() or {}
    if "bytes_limit" not in stats or "bytes_in_use" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats["bytes_in_use"])


def note_run_start(run: Any) -> None:
    """FitRun/TransformRun __enter__ hook: open a per-run HBM peak tracker."""
    total = sample_hbm(force=True)
    with _lock:
        _run_peaks[run.run_id] = total or 0


def note_run_end(run: Any) -> None:
    """Run __exit__ hook: final sample, then land the run-scoped peak gauge in
    THAT run's registry (a global gauge cannot be run-scoped)."""
    sample_hbm(force=True)
    with _lock:
        peak = _run_peaks.pop(run.run_id, None)
    if peak:
        try:
            run.registry.gauge("device.hbm_peak_bytes").set(int(peak))
        except Exception as e:
            _log_once("peak_gauge", "hbm peak gauge failed: %s", e)


# ------------------------------------------------------------ span close hook


def on_span_close(node: Any) -> None:
    """runs.span close hook: keep the HBM gauge fresh (rate-limited). Must
    never raise — it sits inside every span's finally."""
    try:
        sample_hbm()
    except Exception as e:
        _log_once("span_close", "device span hook failed: %s", e)

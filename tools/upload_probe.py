"""Probe, outside any cell of BENCHMARK.json: what bounds the host→device
upload of a 4.29 GB float32 table on this host (ROADMAP S12(b)). It changes
nothing in the program: every reading is taken with the program's own span
primitive, flagged `waits` (observability/runs.py), so each line carries
seconds, GB/s and the host's usage over the interval: the whole process's CPU
seconds (user, sys), the calling thread's, page faults and context switches.
One JSON line an operation (also appended to chiprun_out/upload_probe.jsonl);
refuses a CPU backend at the default size.

    chiprun -- python -m tools.upload_probe [rows cols [part ...]]

Defaults: 357376 3000 (the wide cells' table), every part:

    host      cores, page size, transparent huge pages, NUMA nodes, how much
              of the table numpy's allocation got in huge pages, and the
              process's rusage so far: a kernel that counts faults has counted
              one a page of the table by then (a sandboxed one may count none)
    span      the primitive's own cost: microseconds from open to close of an
              unflagged and of a flagged span, outside and inside a run scope
    program   the program's own put and wait (`Partitioner.shard(site="fit")`,
              then `h2d.wait`), eight times over: the per-operation table that
              shows a pause if one falls in it; since PR 37 the put goes up
              in row chunks (`put_chunks`, `gate`), so GB/s is over put AND
              wait, with the placed array's layout and the device's peak
    same      one put and wait of the same array, three times
    fresh     of a fresh copy of it each time (made outside the reading)
    aligned   of a page-aligned, pre-touched source (anonymous mmap, 2 MiB
              aligned, huge pages asked for)
    chunks    4, 16 and 64 row chunks, all put from one thread and then
              waited for, and put and waited for by four threads
    assembled the same chunks made ONE device array again, which is what every
              fit function and predict kernel takes: row chunks of 64, 32, 16 and 8
              MiB (whole multiples of 1,024 rows, the last one the rest), all
              put from the calling thread, then assembled on the device in
              one of two forms and waited for: `concat`, one compiled
              `concatenate` of the chunks (a second table in HBM until it
              has run), and `dus`, a preallocated buffer donated to a
              compiled `dynamic_update_slice` a chunk, each running as its
              chunk lands (`dusc`: the same with the offset carried on the
              device and no scalar put a chunk). Beside them `whole`, one
              `device_put`. Every line: seconds (put, assembly and wait),
              the seconds the dispatch alone took the caller, GB/s, the
              host's usage, the device's `bytes_in_use` before and
              `peak_bytes_in_use` after (a lifetime peak: the forms run in
              the order whole, dus, dusc, concat), the result's
              `format.layout` and the first chunk's (`rep` -1 compiles);
              last, a form a line, whether the result equals the whole put
              on the device, and its layout beside the whole put's
    mesh      the table row-sharded over EVERY device of the host (one on a
              one-chip machine, four with `chiprun --chips 4`): one sharded
              `device_put` (`whole`), the program's put (`Partitioner.shard`:
              chunked on one device, the same sharded put on several, where
              its gate says `devices`) and what the program does NOT do on
              several: each device's rows in 32 MiB chunks of their own,
              written into that device's array and the arrays joined under
              the sharding, device after device (`per_device`) or a chunk a
              device in turn (`interleaved`). Three readings each, and
              whether each equals the whole put on the devices. The reading
              a PR needs before it lets several devices past the gate
    sizes     where chunking starts to pay: the table's first 64 MiB to 2 GiB
              put whole and in 16 and 64 MiB chunks assembled either way,
              five readings each, the median a line
    second    a fit puts two arrays (the table, then its row weights): the
              seconds the SECOND put's dispatch takes by how long after the
              first it comes (0, 0.2, 2 and 20 ms), and what one
              `getrusage` costs while the table is in flight
    small     what the sampling costs an operation that is not 0.4 s long: a
              KMeans model (k=20) fitted on the table's first 4096 rows, then
              `transform` of 1, 256 and 4096 rows (two samples each: the
              flagged `h2d.wait` and the run scope) and a served request of 4
              rows (serving/registry.py: no run scope, `h2d.wait` alone), each
              400 times with the sampling on and off in turn (off:
              `_host_sample` gives None, which is all a platform without
              `resource` does). PERF.md's readings are of a narrow table:
              `python -m tools.upload_probe 4096 128 small`
    fold      a fit's one pass riding the upload (ROADMAP S12(c)): the
              program's put and wait of the table alone (`none`); the same put
              with the centred Gram's part step dispatched on each chunk as it
              lands (`ops/linalg.py::GramFold`, PCA's `gram` and the normal
              equations' form), then the wait for the table and for the
              statistics; the resident pass after the upload (`after`:
              `weighted_covariance`, `normal_equations`); and the fold's parts
              alone over chunks already on the device (`resident`). Five
              readings each (`rep` -1 compiles): seconds of dispatch, of the
              upload (put and wait), of what the statistics took past it, and
              of the whole
"""

from __future__ import annotations

import concurrent.futures
import json
import mmap
import os
import resource
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from spark_rapids_ml_tpu import observability as obs
from spark_rapids_ml_tpu.observability import runs
from spark_rapids_ml_tpu.parallel.partitioner import active_partitioner

PARTS = ("host", "span", "program", "same", "fresh", "aligned", "chunks", "assembled", "mesh",
         "sizes", "second", "small", "fold")
HUGE = 2 << 20


def _say(**line):
    line["platform"] = jax.devices()[0].platform
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/upload_probe.jsonl", "a") as out:
        out.write(json.dumps(line) + "\n")


def _usage(counters, span):
    """The four `host.*` numbers one flagged span wrote, by short names."""
    out = {}
    for key, value in counters.items():
        name, labels = obs.split_label_key(key)
        if name.startswith("host.") and labels.get("span") == span:
            short = {"host.cpu_seconds": "cpu_", "host.thread_cpu_seconds": "caller_cpu_s",
                     "host.page_faults": "faults_", "host.ctx_switches": "switches_"}[name]
            out[short + labels.get("mode", labels.get("kind", ""))] = value
        elif name == "span.seconds" and labels.get("span") == span:
            out["seconds"] = value
    return out


def _reading(part, rep, nbytes, upload):
    """One operation under a flagged span of the probe's own; `upload` puts
    and waits, and returns what to free."""
    with obs.worker_scope() as scope:
        with obs.span("probe.upload", {"waits": "upload"}):
            placed = upload()
    for a in placed:
        a.delete()
    line = _usage(scope.registry.snapshot()["counters"], "probe.upload")
    _say(part=part, rep=rep, gb_per_s=nbytes / line["seconds"] / 1e9, **line)


def _put_and_wait(x):
    return [jax.block_until_ready(jax.device_put(x))]


def host(X):
    def read(path):
        try:
            with open(path) as f:
                return f.read().strip()
        except OSError:
            return None

    rollup = read("/proc/self/smaps_rollup") or ""
    huge_kb = [int(ln.split()[1]) for ln in rollup.splitlines() if ln.startswith("AnonHugePages")]
    nodes = [d for d in os.listdir("/sys/devices/system/node")
             if d.startswith("node")] if os.path.isdir("/sys/devices/system/node") else None
    ru = resource.getrusage(resource.RUSAGE_SELF)
    _say(part="host", cores=len(os.sched_getaffinity(0)), page_size=mmap.PAGESIZE,
         rusage_so_far={"utime": ru.ru_utime, "stime": ru.ru_stime, "minflt": ru.ru_minflt,
                        "majflt": ru.ru_majflt, "nvcsw": ru.ru_nvcsw, "nivcsw": ru.ru_nivcsw},
         thp_enabled=read("/sys/kernel/mm/transparent_hugepage/enabled"),
         thp_defrag=read("/sys/kernel/mm/transparent_hugepage/defrag"),
         numa_nodes=nodes, table_bytes=int(X.nbytes),
         table_address_mod_page=int(X.ctypes.data % mmap.PAGESIZE),
         anon_huge_bytes=huge_kb[0] * 1024 if huge_kb else None)


def span_cost(n=20000):
    def mean_us(attrs):
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("probe.cost", attrs):
                pass
        return (time.perf_counter() - t0) / n * 1e6

    for where in ("outside_a_run", "inside_a_run"):
        with obs.FitRun("Probe", max_spans=16) if where == "inside_a_run" else obs.worker_scope():
            mean_us(None)  # the histogram and the counters exist from here on
            _say(part="span", where=where, spans=n, unflagged_us=mean_us(None),
                 flagged_us=mean_us({"waits": "none"}))


def program(X, reps=8):
    part = active_partitioner(1)
    for rep in range(reps):
        with obs.worker_scope() as scope:
            placed = part.shard(X, site="fit")
            with obs.span("h2d.wait", {"site": "fit", "waits": "upload"}):
                jax.block_until_ready(placed)
        layout = _layout(placed)
        placed.delete()
        counters = scope.registry.snapshot()["counters"]
        wait = _usage(counters, "h2d.wait")
        put = {"put_" + k: v for k, v in _usage(counters, "h2d.put").items()}
        seconds = wait["seconds"] + put["put_seconds"]  # the chunks' dispatch is part of the upload
        _say(part="program", rep=rep, gb_per_s=X.nbytes / seconds / 1e9, put_and_wait_s=seconds,
             layout=layout, peak_bytes_in_use=_memory()[1], **_chunking(counters), **wait, **put)


def _chunking(counters):
    """What the program's put did: chunks dispatched, and the gate's word."""
    return {"put_chunks": counters.get("h2d.chunks{site=fit}", 0),
            "gate": [key for key in counters if key.startswith("h2d.chunk_gate")]}


def aligned_copy(X):
    """X in an anonymous mapping: 2 MiB aligned, huge pages asked for, every
    page written (by the copy) before any reading."""
    buf = mmap.mmap(-1, X.nbytes + HUGE)
    if hasattr(mmap, "MADV_HUGEPAGE"):
        buf.madvise(mmap.MADV_HUGEPAGE)
    whole = np.frombuffer(buf, dtype=np.uint8)
    start = (-whole.ctypes.data) % HUGE
    out = whole[start:start + X.nbytes].view(X.dtype).reshape(X.shape)
    out[...] = X
    return out


def chunks(X, reps=3):
    def all_from_one_thread(parts):
        return jax.block_until_ready([jax.device_put(p) for p in parts])

    def by_four_threads(parts):
        with concurrent.futures.ThreadPoolExecutor(4) as pool:
            return [f.result()[0] for f in [pool.submit(_put_and_wait, p) for p in parts]]

    for n in (4, 16, 64):
        parts = np.array_split(X, n)  # row chunks: views, each contiguous
        for name, upload in (("one_thread", all_from_one_thread), ("four_threads", by_four_threads)):
            for rep in range(reps):
                _reading(f"chunks{n}_{name}", rep, X.nbytes, lambda: upload(parts))


def _row_chunks(X, chunk_bytes, align=1024):
    """Contiguous row ranges of `chunk_bytes` at most, whole multiples of
    `align` rows but the last."""
    per = max(align, chunk_bytes // (X.nbytes // X.shape[0]) // align * align)
    return [X[s:s + per] for s in range(0, X.shape[0], per)]


def h2d_assemble(*chunks):
    return jnp.concatenate(chunks, axis=0)


def h2d_place(buf, chunk, start):
    return lax.dynamic_update_slice_in_dim(buf, chunk, start, axis=0)


def h2d_place_next(buf, chunk, start):
    return h2d_place(buf, chunk, start), start + chunk.shape[0]


_equal = jax.jit(lambda a, b: jnp.all(a == b))  # no (n, d) array of booleans beside the two
_concat = jax.jit(h2d_assemble)
_place = jax.jit(h2d_place, donate_argnums=0)
_place_next = jax.jit(h2d_place_next, donate_argnums=(0, 2))


def _upload_whole(X, chunk_bytes):
    return jax.device_put(X), None


def _upload_concat(X, chunk_bytes):
    placed = [jax.device_put(c) for c in _row_chunks(X, chunk_bytes)]
    return _concat(*placed), placed[0]


def _upload_dus(X, chunk_bytes):
    """The offset of each chunk goes up as a scalar of its own."""
    out = jnp.empty(X.shape, X.dtype)
    start, first = 0, None
    for c in _row_chunks(X, chunk_bytes):
        placed = jax.device_put(c)
        first = placed if first is None else first
        out = _place(out, placed, np.int32(start))
        start += c.shape[0]
    return out, first


def _upload_dusc(X, chunk_bytes):
    """The offset stays on the device, carried from chunk to chunk."""
    out, start, first = jnp.empty(X.shape, X.dtype), jnp.zeros((), jnp.int32), None
    for c in _row_chunks(X, chunk_bytes):
        placed = jax.device_put(c)
        first = placed if first is None else first
        out, start = _place_next(out, placed, start)
    return out, first


FORMS = (("whole", _upload_whole), ("dus", _upload_dus), ("dusc", _upload_dusc),
         ("concat", _upload_concat))


def _layout(a):
    lay = a.format.layout
    return None if lay is None else {"major_to_minor": list(lay.major_to_minor),
                                     "tiling": [list(t) for t in lay.tiling]}


def _memory():
    stats = jax.devices()[0].memory_stats() or {}  # noqa: fence/device-analysis-off-plane
    return stats.get("bytes_in_use"), stats.get("peak_bytes_in_use")


def _assembled_reading(X, chunk_bytes, upload):
    """One upload under a flagged span: (seconds by the span, dispatch seconds,
    the host's usage, the placed array, its first chunk)."""
    with obs.worker_scope() as scope:
        with obs.span("probe.upload", {"waits": "upload"}):
            t0 = time.perf_counter()
            out, first = upload(X, chunk_bytes)
            dispatch_s = time.perf_counter() - t0
            jax.block_until_ready(out)
    line = _usage(scope.registry.snapshot()["counters"], "probe.upload")
    line["dispatch_s"] = dispatch_s
    return line, out, first


def assembled(X, reps=3, chunk_mibs=(64, 32, 16, 8)):
    for form, upload in FORMS:
        for chunk_mib in chunk_mibs if form != "whole" else (None,):
            chunk_bytes = (chunk_mib or 0) << 20
            for rep in range(-1 if chunk_mib else 0, reps):  # -1 compiles
                in_use, _ = _memory()
                line, out, first = _assembled_reading(X, chunk_bytes, upload)
                _say(part=f"assembled_{form}" + (f"_{chunk_mib}MiB" if chunk_mib else ""), rep=rep,
                     chunks=len(_row_chunks(X, chunk_bytes)) if chunk_mib else 1,
                     gb_per_s=X.nbytes / line["seconds"] / 1e9, bytes_in_use_before=in_use,
                     peak_bytes_in_use=_memory()[1], layout=_layout(out),
                     chunk_layout=None if first is None else _layout(first),
                     committed=out.committed, **line)
                out.delete()
                del out, first
    # last, so that no reading's peak holds a comparison's two tables
    whole = jax.device_put(X)
    for form, upload in FORMS[1:]:
        out, _ = upload(X, chunk_mibs[0] << 20)
        _say(part=f"assembled_check_{form}", equals_whole=bool(_equal(out, whole)),
             layout=_layout(out), whole_layout=_layout(whole),
             same_layout_as_whole=out.format.layout == whole.format.layout)
        out.delete()
    whole.delete()


def _upload_per_device(X, sharding, chunk_bytes, interleaved):
    """Each device's rows of `X` in chunks of their own, joined under
    `sharding`: every chunk of one device before the next device's, or a
    chunk a device in turn."""
    shares = [(device, _row_chunks(X[index], chunk_bytes), X[index].shape)
              for device, index in sharding.addressable_devices_indices_map(X.shape).items()]
    state = [[jnp.empty(shape, X.dtype, device=device), jnp.zeros((), jnp.int32, device=device)]
             for device, _, shape in shares]
    turns = [(i, c) for i, (_, chunks_, _) in enumerate(shares) for c in range(len(chunks_))]
    if interleaved:
        turns.sort(key=lambda turn: (turn[1], turn[0]))
    for i, c in turns:
        device, chunks_, _ = shares[i]
        state[i] = list(_place_next(state[i][0], jax.device_put(chunks_[c], device), state[i][1]))
    return jax.make_array_from_single_device_arrays(X.shape, sharding, [out for out, _ in state])


def mesh(X, reps=3, chunk_bytes=32 << 20):
    part = active_partitioner()
    rows = X[:len(X) // (8 * part.num_workers) * 8 * part.num_workers]
    sharding = part.data_sharding(2)
    uploads = (("whole", lambda: jax.device_put(rows, sharding)),
               ("program", lambda: part.shard(rows, site="fit")),
               ("per_device", lambda: _upload_per_device(rows, sharding, chunk_bytes, False)),
               ("interleaved", lambda: _upload_per_device(rows, sharding, chunk_bytes, True)))
    for name, upload in uploads:
        for rep in range(-1, reps):  # -1 compiles
            with obs.worker_scope() as scope:
                with obs.span("probe.upload", {"waits": "upload"}):
                    t0 = time.perf_counter()
                    placed = upload()
                    dispatch_s = time.perf_counter() - t0
                    jax.block_until_ready(placed)
            counters = scope.registry.snapshot()["counters"]
            line = _usage(counters, "probe.upload")
            _say(part=f"mesh_{name}", rep=rep, devices=part.num_workers, dispatch_s=dispatch_s,
                 gb_per_s=rows.nbytes / line["seconds"] / 1e9, layout=_layout(placed),
                 peak_bytes_in_use=_memory()[1], **_chunking(counters), **line)
            placed.delete()
    whole = uploads[0][1]()
    for name, upload in uploads[1:]:
        placed = upload()
        _say(part=f"mesh_check_{name}", devices=part.num_workers,
             same_sharding=whole.sharding == placed.sharding,
             same_layout=whole.format.layout == placed.format.layout,
             equals_whole=bool(_equal(whole, placed)))
        placed.delete()
    whole.delete()


def sizes(X, reps=5):
    import statistics

    row_bytes = X.nbytes // X.shape[0]
    for total_mib in (64, 128, 256, 512, 1024, 2048):
        part = X[:max(1024, (total_mib << 20) // row_bytes // 1024 * 1024)]
        if part.shape[0] == X.shape[0] and total_mib << 20 > X.nbytes:
            break
        for form, upload in FORMS:
            for chunk_mib in (None,) if form == "whole" else (16, 64):
                if chunk_mib and chunk_mib << 20 >= part.nbytes:
                    continue
                seconds = []
                for rep in range(-1, reps):
                    line, out, _ = _assembled_reading(part, (chunk_mib or 0) << 20, upload)
                    out.delete()
                    if rep >= 0:
                        seconds.append(line["seconds"])
                med = statistics.median(seconds)
                _say(part="sizes", form=form, bytes=int(part.nbytes), chunk_mib=chunk_mib,
                     chunks=len(_row_chunks(part, chunk_mib << 20)) if chunk_mib else 1,
                     median_s=med, min_s=min(seconds), max_s=max(seconds),
                     gb_per_s=part.nbytes / med / 1e9)


def second_put(X, reps=3):
    weights = np.ones(X.shape[0], dtype=np.float32)
    for delay in (0.0, 0.0002, 0.002, 0.02):
        for rep in range(reps):
            first = jax.device_put(X)
            until = time.perf_counter() + delay
            while time.perf_counter() < until:
                pass
            t0 = time.perf_counter()
            resource.getrusage(resource.RUSAGE_SELF)
            t1 = time.perf_counter()
            second = jax.device_put(weights)
            t2 = time.perf_counter()
            jax.block_until_ready([first, second])
            t3 = time.perf_counter()
            first.delete()
            second.delete()
            _say(part="second", delay_s=delay, rep=rep, getrusage_in_flight_us=(t1 - t0) * 1e6,
                 second_put_dispatch_s=t2 - t1, both_resident_s=t3 - t0 + delay)


def small_batch(X, reps=400, blocks=8):
    import statistics

    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.serving.registry import ModelRegistry

    model = KMeans(k=20, maxIter=5, seed=1).fit(X[:4096])
    registry = ModelRegistry()
    registry.register("probe", model)
    operations = [(f"transform_{n}_rows", lambda n=n: model.transform(X[:n])) for n in (1, 256, 4096)]
    operations.append(("served_4_rows", lambda: registry.predict("probe", X[:4])))
    sample, no_sample = runs._host_sample, lambda: None
    try:
        for name, operation in operations:
            for _ in range(20):  # every shape compiled, every counter there
                operation()
            seconds = {"on": [], "off": []}
            for block in range(blocks):
                for which in ("on", "off") if block % 2 == 0 else ("off", "on"):
                    runs._host_sample = sample if which == "on" else no_sample
                    for _ in range(reps // blocks):
                        t0 = time.perf_counter()
                        operation()
                        seconds[which].append(time.perf_counter() - t0)
            med = {k: statistics.median(v) * 1e6 for k, v in seconds.items()}
            _say(part="small", operation=name, each=len(seconds["on"]),
                 median_us_sampling_on=med["on"], median_us_sampling_off=med["off"],
                 mean_us_sampling_on=statistics.fmean(seconds["on"]) * 1e6,
                 mean_us_sampling_off=statistics.fmean(seconds["off"]) * 1e6,
                 added_us=med["on"] - med["off"], added_share=med["on"] / med["off"] - 1)
    finally:
        runs._host_sample = sample
        registry.close()


def fold(X, reps=5):
    from spark_rapids_ml_tpu.ops import linalg, linear

    part = active_partitioner(1)
    w = part.shard(np.ones(len(X), np.float32))
    y = part.shard(X[:, 0] * 2.0 + 1.0)
    forms = {"gram": (linalg.weighted_covariance_part, linalg.weighted_covariance_finish, None,
                      lambda a: linalg.weighted_covariance(a, w)),
             "normal_equations": (linear.normal_equations_part, linear.normal_equations_finish, y,
                                  lambda a: linear.normal_equations(a, y, w))}
    jax.block_until_ready((w, y))

    def timed(form, rep, put, then):
        t0 = time.perf_counter()
        placed, stats, parts = put()
        t1 = time.perf_counter()
        jax.block_until_ready(placed)
        t2 = time.perf_counter()
        stats = then(placed, stats)
        jax.block_until_ready(stats)
        t3 = time.perf_counter()
        placed.delete()
        _say(part="fold", form=form, rep=rep, parts=parts, dispatch_s=t1 - t0, upload_s=t2 - t0,
             stats_past_upload_s=t3 - t2, total_s=t3 - t0, gb_per_s=X.nbytes / (t2 - t0) / 1e9)

    def folded(step, finish, label):
        def put():
            consumer = linalg.GramFold(form, step, finish, w, label)
            placed = part.shard(X, site="fit", consume=consumer)
            return placed, consumer.close(), consumer.parts
        return put

    for rep in range(-1, reps):
        timed("none", rep, lambda: (part.shard(X, site="fit"), None, 0), lambda a, s: None)
        for form, (step, finish, label, resident) in forms.items():
            timed(form, rep, folded(step, finish, label), lambda a, s: s)
            timed(form + ".after", rep, lambda: (part.shard(X, site="fit"), None, 0),
                  lambda a, s, resident=resident: resident(a))
    per = 2048 if X.shape[1] == 3000 else 1024
    chunks = [jax.device_put(X[s:s + per]) for s in range(0, len(X), per)]
    jax.block_until_ready(chunks)
    for form, (step, finish, label, _) in forms.items():
        for rep in range(-1, reps):
            t0 = time.perf_counter()
            consumer = linalg.GramFold(form, step, finish, w, label)
            for c in chunks:
                consumer(c)
            jax.block_until_ready(consumer.close())
            seconds = time.perf_counter() - t0
            _say(part="fold", form=form + ".resident", rep=rep, parts=len(chunks),
                 total_s=seconds, part_ms=1e3 * seconds / len(chunks))
    for c in chunks:
        c.delete()


def main(argv):
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and len(argv) < 2:
        print("upload_probe: refusing the default size off a TPU (give rows cols for a "
              "rehearsal: its lines say `platform`)", file=sys.stderr)
        return 2
    rows, cols = (int(argv[0]), int(argv[1])) if len(argv) >= 2 else (357376, 3000)
    parts = argv[2:] or PARTS
    unknown = [p for p in parts if p not in PARTS]
    if unknown:
        print(f"upload_probe: no part {unknown}; parts are {PARTS}", file=sys.stderr)
        return 2
    X = np.random.default_rng(36).standard_normal((rows, cols), dtype=np.float32)
    _put_and_wait(X[:1024])[0].delete()  # the client and its first transfer exist
    if "host" in parts:
        host(X)
    if "span" in parts:
        span_cost()
    if "program" in parts:
        program(X)
    if "same" in parts:
        for rep in range(3):
            _reading("same", rep, X.nbytes, lambda: _put_and_wait(X))
    if "fresh" in parts:
        for rep in range(3):
            fresh = X.copy()
            _reading("fresh", rep, X.nbytes, lambda: _put_and_wait(fresh))
            del fresh
    if "aligned" in parts:
        A = aligned_copy(X)
        for rep in range(3):
            _reading("aligned", rep, X.nbytes, lambda: _put_and_wait(A))
        del A
    if "chunks" in parts:
        chunks(X)
    if "assembled" in parts:
        assembled(X)
    if "mesh" in parts:
        mesh(X)
    if "sizes" in parts:
        sizes(X)
    if "second" in parts:
        second_put(X)
    if "small" in parts:
        small_batch(X)
    if "fold" in parts:
        fold(X)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""One run of one cell: build the store from the seed, warm its probe, run
the closed loop for the window, check every answer against the reference,
and reduce what was measured to the cell's metrics.

Order of a run:

1. find the cell, its configuration and its traffic by name;
2. set-up (``setup_s``): make the records from the seed, start the
   background compactor, load the records through the store's write path
   in load order (YCSB's load phase: the store flushes a table each time
   its write buffer fills and compacts by its own settings), wait until
   no compaction is due, warm the probe shape the cell's reads use;
3. draw the window's requests;
4. the window: ``clients`` threads, each a closed loop that sends its next
   request when the last one returned, for ``seconds``; with ``trace`` the
   middle half of it is traced;
5. read back every key written, through the store, after the window;
6. check every answer and the read-back against ``reference``; the
   numbers compared are ``checks``, each with limit 0.
"""
from __future__ import annotations

import gc
import itertools
import sys
import tempfile
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from chipbench import device, reference, spec, traces, ycsb

READ, UPDATE = (ycsb.KINDS.index(k) for k in ycsb.KINDS)
CALL = {READ: "client.get_batch", UPDATE: "client.put_batch"}
TRACE_FROM, TRACE_TO = 0.25, 0.75        # traced share of the window


def _log(line: str) -> None:
    print(line, flush=True)


@dataclass
class Run:
    """Everything a metric reader may read of one run."""

    cell: dict
    config: dict
    traffic: dict
    seconds: float
    setup_s: float
    kind: np.ndarray            # per request started in the window
    start_ns: np.ndarray        # since the window opened
    end_ns: np.ndarray
    ok: np.ndarray
    ops: np.ndarray             # YCSB operations in the request
    live_keys: int
    stats0: dict | None = None  # store counters as the window opened
    stats1: dict | None = None  # ... and closed
    compiles_in_window: int | None = None
    store_device_bytes: int | None = None   # allocator, at the window's end
    bank_bytes: int | None = None           # the generation's filter bank
    peaks: dict | None = None
    trace: traces.TraceSummary | None = None
    probe_segments: list = field(default_factory=list)   # [(keys, chains)]

    @property
    def window_ns(self) -> int:
        return int(self.seconds * 1e9)

    def latencies_ms(self, kinds) -> np.ndarray:
        sel = np.isin(self.kind, kinds)
        return (self.end_ns[sel] - self.start_ns[sel]) * 1e-6

    def stat_delta(self, name: str):
        if self.stats0 is None or self.stats1 is None:
            return None
        return self.stats1[name] - self.stats0[name]

    @staticmethod
    def kernel(name: str):
        return spec.module("kernels", name)


def build_store(cfg: dict, keys: np.ndarray, vals: np.ndarray, seed: int):
    """The store under test with the configuration's settings, its
    filters' hash seed among them (``seed`` made the records), loaded as
    YCSB's load phase loads it: every record in load order through
    ``put_batch``, in batches of one write buffer, so the store flushes
    and compacts exactly as it would for single inserts. Returns once no
    compaction is due."""
    from repro.storage.lsm_store import LsmStore
    params = dict(cfg["store"])
    background = params.pop("background_compaction")
    store = LsmStore(**params)
    if background:
        store.start_background()
    batch = int(params["memtable_capacity"])
    for a in range(0, len(keys), batch):
        store.put_batch(keys[a:a + batch], vals[a:a + batch])
    if not store.wait_compaction_idle(timeout_s=600.0):
        raise RuntimeError("compaction still due after the load")
    return store


class _ProbeLog:
    """Probed-key counts and the probe's table descriptors at each publish
    during the traced stretch, so a kernel's bytes follow the generation."""

    def __init__(self, store):
        self.store = store
        self.marks: list = []
        self.active = False

    def mark(self, chains=None) -> None:
        gen_chains = self.store.generation.chains if chains is None else chains
        self.marks.append((self.store.stats.probed, gen_chains))

    def hook(self, store, gen) -> None:
        if self.active:
            self.mark(gen.chains)

    def segments(self) -> list:
        return [(b[0] - a[0], a[1]) for a, b in zip(self.marks, self.marks[1:])]


def _client(store, pool, counter, clock, out, errors, annotate):
    P = len(pool)
    clock["go"].wait()
    origin, deadline = clock["origin"], clock["deadline"]
    keys = pool.keys
    while True:
        t0 = time.perf_counter_ns()
        if t0 >= deadline:
            return
        i = next(counter)
        j = i % P
        k = int(pool.kind[j])
        r = int(pool.row[j])
        res = None
        try:
            if k == READ:
                with annotate(CALL[k]):
                    found, vals, _ = store.get_batch(keys["read"][r])
                res = (found, vals)
            else:
                wk = keys["update"][r]
                res = ycsb.write_value(i, len(wk))
                with annotate(CALL[k]):
                    store.put_batch(wk, res)
            ok = True
        except Exception as exc:         # a failed request is counted, not fatal
            ok = False
            if len(errors) < 5:
                errors.append(f"{ycsb.KINDS[k]} request {i}: {exc!r}")
        t1 = time.perf_counter_ns()
        out.append((i, k, t0 - origin, t1 - origin, ok, res))


def _window(store, pool, clients: int, seconds: float, trace: bool,
            probe_log: _ProbeLog | None):
    """Run the closed loop; returns (per-request log, errors, trace events,
    traced seconds, the window's origin on ``perf_counter_ns``)."""
    counter = itertools.count()
    clock = {"go": threading.Event()}
    outs = [[] for _ in range(clients)]
    errors: list = []
    if trace:
        import jax
        annotate = jax.profiler.TraceAnnotation
    else:
        annotate = lambda name: nullcontext()       # noqa: E731
    threads = [threading.Thread(target=_client, name=f"client-{c}",
                                args=(store, pool, counter, clock, outs[c],
                                      errors, annotate))
               for c in range(clients)]
    for t in threads:
        t.start()
    events, traced_s = [], None
    origin = time.perf_counter_ns()
    clock["origin"], clock["deadline"] = origin, origin + int(seconds * 1e9)
    clock["go"].set()
    try:
        if trace:
            import jax
            with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as d:
                _sleep_until(origin + int(TRACE_FROM * seconds * 1e9))
                jax.profiler.start_trace(d, profiler_options=traces.record_options())
                t_a = time.perf_counter_ns()
                if probe_log is not None:
                    probe_log.mark()
                    probe_log.active = True
                _sleep_until(origin + int(TRACE_TO * seconds * 1e9))
                if probe_log is not None:
                    probe_log.active = False
                    probe_log.mark()
                t_b = time.perf_counter_ns()
                jax.profiler.stop_trace()
                traced_s = (t_b - t_a) * 1e-9
                events = traces.load_events(d)
    finally:
        for t in threads:
            t.join()
    log = sorted(itertools.chain.from_iterable(outs), key=lambda e: e[0])
    return log, errors, events, traced_s, origin


def _sleep_until(t_ns: int) -> None:
    while True:
        left = t_ns - time.perf_counter_ns()
        if left <= 0:
            return
        time.sleep(min(left * 1e-9, 0.05))


def _write_log(log, pool) -> reference.WriteLog:
    """Acknowledged-or-not writes of the window: a write that raised may
    still have been applied, so it stays in the log. Within a request the
    last write of a key is the one a store keeps."""
    ks, vs, ss, es = [], [], [], []
    for i, k, t0, t1, ok, res in log:
        if k != UPDATE or res is None:
            continue
        j = i % len(pool)
        wk = pool.keys["update"][pool.row[j]]
        _, last = np.unique(wk[::-1], return_index=True)
        keep = len(wk) - 1 - last
        ks.append(wk[keep])
        vs.append(res[keep])
        ss.append(np.full(len(keep), t0, dtype=np.int64))
        es.append(np.full(len(keep), t1, dtype=np.int64))
    if not ks:
        e = np.empty(0, dtype=np.int64)
        return reference.WriteLog(np.empty(0, np.uint64), np.empty(0, np.uint64), e, e)
    return reference.WriteLog(np.concatenate(ks), np.concatenate(vs),
                              np.concatenate(ss), np.concatenate(es))


def _readback(store, keys: np.ndarray, batch: int, origin_ns: int):
    """Read every written key back through the store after the window, in
    requests of the window's read size."""
    out = []
    for a in range(0, len(keys), batch):
        q = keys[a:a + batch]
        t0 = time.perf_counter_ns() - origin_ns
        found, vals, _ = store.get_batch(q)
        t1 = time.perf_counter_ns() - origin_ns
        out.append((q, t0, t1, found, vals))
    return out


def _check(base: reference.ReferenceStore, wlog: reference.WriteLog, log,
           pool, readback) -> dict:
    """The numbers compared with the reference, each with limit 0."""
    keys, starts, ends, found, vals = [], [], [], [], []
    for i, k, t0, t1, ok, res in log:
        if k != READ or not ok:
            continue
        q = pool.keys["read"][pool.row[i % len(pool)]]
        keys.append(q)
        starts.append(np.full(len(q), t0, dtype=np.int64))
        ends.append(np.full(len(q), t1, dtype=np.int64))
        found.append(res[0])
        vals.append(res[1])
    wrong_reads = 0
    if keys:
        wrong_reads = int(reference.check_reads(
            base, wlog, np.concatenate(keys), np.concatenate(starts),
            np.concatenate(ends), np.concatenate(found),
            np.concatenate(vals)).sum())
    lost = 0
    if readback:
        q = np.concatenate([r[0] for r in readback])
        s = np.concatenate([np.full(len(r[0]), r[1], np.int64) for r in readback])
        e = np.concatenate([np.full(len(r[0]), r[2], np.int64) for r in readback])
        f = np.concatenate([r[3] for r in readback])
        v = np.concatenate([r[4] for r in readback])
        lost = int(reference.check_reads(base, wlog, q, s, e, f, v).sum())
    return {"wrong_reads": wrong_reads, "lost_writes": lost}


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = spec.ROOT, bench: dict | None = None,
             cfg: dict | None = None, store_factory=None,
             require_chip: bool = True, log=_log) -> dict:
    """One run; returns the result line's object (``checks`` last)."""
    import jax
    bench = bench if bench is not None else spec.load_benchmark(root)
    w = spec.cell(bench, cell_name)
    cfg = cfg if cfg is not None else spec.config(bench, w["config"], root)
    traffic = spec.traffic(w["traffic"])
    devices = jax.devices()
    peaks = device.require_chips(devices, w["chips"]) if require_chip else None
    dev = devices[0]
    from chipbench.compiles import CompileCounter
    counter = CompileCounter()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    mem0 = device.memory(dev).get("bytes_in_use")

    # ---- set-up: records from the seed, bulk load, warm-up
    t_setup = time.perf_counter()
    n = int(cfg["record_count"])
    rec_keys = ycsb.record_keys(np.arange(n), seed)
    rec_vals = ycsb.load_values(rec_keys, seed)
    store = (store_factory or build_store)(cfg, rec_keys, rec_vals, seed)
    warm_rng = np.random.default_rng([seed & ycsb.MASK64, 0x7761726D])
    if traffic["mix"].get("read", 0) > 0:
        for _ in range(int(traffic.get("warm_requests", 4))):
            store.get_batch(rec_keys[warm_rng.integers(0, n, traffic["ops"]["read"])])
    setup_s = time.perf_counter() - t_setup
    log(f"setup: {setup_s:.3f} s, {getattr(store, 'n_tables', 0)} tables, "
        f"{counter.compiled} compiled, "
        f"{counter.cache_hits} loaded from the cache, "
        f"{counter.seconds:.3f} s in compile calls")

    # ---- the window's requests
    t_draw = time.perf_counter()
    pool = ycsb.draw_pool(traffic, n, seed, seconds)
    log(f"drew {len(pool)} requests in {time.perf_counter() - t_draw:.3f} s")

    # ---- the window
    stats = getattr(store, "stats", None)
    probe_log = _ProbeLog(store) if trace and stats is not None else None
    if probe_log is not None:
        store.add_publish_hook(probe_log.hook)
    stats0 = stats.as_dict() if stats is not None else None
    c0 = counter.compiled
    req_log, errors, events, traced_s, origin = _window(
        store, pool, int(traffic["clients"]), seconds, trace, probe_log)
    compiled_in_window = counter.compiled - c0
    stats1 = stats.as_dict() if stats is not None else None
    mem1 = device.memory(dev).get("bytes_in_use")
    gen = getattr(store, "generation", None)
    bank_bytes = int(gen.tables.nbytes) if gen is not None else None
    for e in errors:
        print(f"request failed: {e}", file=sys.stderr, flush=True)

    # ---- read back every written key, then free the store
    kind = np.array([e[1] for e in req_log], dtype=np.int8)
    start = np.array([e[2] for e in req_log], dtype=np.int64)
    end = np.array([e[3] for e in req_log], dtype=np.int64)
    ok = np.array([e[4] for e in req_log], dtype=bool)
    ops = np.array([pool.ops[ycsb.KINDS[k]] for k in kind], dtype=np.int64)
    wlog = _write_log(req_log, pool)
    written = np.unique(wlog.keys)
    readback = _readback(store, written, int(traffic["ops"].get("read", 128)),
                         origin)
    bg_errors = list(getattr(store, "background_errors", []))
    if hasattr(store, "stop_background"):
        store.stop_background()
    for e in bg_errors:
        print(f"background compaction failed: {e!r}", file=sys.stderr, flush=True)
    mem = device.memory(dev)
    peak = mem.get("peak_bytes_in_use")
    log(f"device peak_bytes_in_use: {peak}")
    if probe_log is not None:
        store.remove_publish_hook(probe_log.hook)
    del store
    gc.collect()

    # ---- the reference check
    t_check = time.perf_counter()
    base = reference.ReferenceStore(rec_keys, rec_vals)
    checks = _check(base, wlog, req_log, pool, readback)
    checks["failed_requests"] = int((~ok).sum()) + len(bg_errors)
    # a window that outran the pool replayed requests: not the traffic
    checks["pool_wraps"] = max(len(req_log) - 1, 0) // max(len(pool), 1)
    log(f"check: {time.perf_counter() - t_check:.3f} s over "
        f"{int(ops[kind == READ].sum())} reads, {len(written)} written keys; "
        f"{len(req_log)} requests of a pool of {len(pool)}")

    summary = None
    if trace:
        summary = traces.reduce(events, traced_s) if events else None
    run = Run(cell=w, config=cfg, traffic=traffic, seconds=seconds,
              setup_s=setup_s, kind=kind, start_ns=start, end_ns=end, ok=ok,
              ops=ops, live_keys=n, stats0=stats0, stats1=stats1,
              compiles_in_window=compiled_in_window,
              store_device_bytes=(mem1 - mem0 if None not in (mem0, mem1) else None),
              bank_bytes=bank_bytes,
              peaks=peaks, trace=summary,
              probe_segments=probe_log.segments() if probe_log else [])
    metrics = {}
    for m in spec.metrics(bench, cell_name, trace):
        value = spec.module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev_out = device.describe(devices, w["chips"])
    dev_out["memory_peak_bytes"] = peak
    if summary is not None:
        dev_out["busy_s"] = summary.busy_s
        dev_out["window_s"] = summary.window_s
    limits = {name: 0 for name in checks}
    correct = len(req_log) > 0 and all(checks[k] <= limits[k] for k in checks)
    result = {"correct": bool(correct), "attempted": len(req_log),
              "failed": int((~ok).sum()), "metrics": metrics, "device": dev_out}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}
    return result

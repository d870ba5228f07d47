"""Spans and counters at the store's layer boundaries (``repro.trace``):
spans nest on the calling thread in a profiler trace, cost no annotation
with the profiler off, and the counters stay exact under concurrent
callers."""
import gc
import glob
import sys
import threading

import jax
import numpy as np
import pytest

from repro import trace
from repro.storage import LsmStore
from repro.storage.lsm_store import DELTA_ROWS

CAP = 2048
READ_PHASES = ("get_mu_wait_ns", "overlay_ns", "probe_split_ns",
               "probe_h2d_ns", "probe_launch_ns", "probe_d2h_ns",
               "probe_free_ns", "resolve_ns")


@pytest.fixture(scope="module")
def loaded():
    """A chained store with three flushed tables and a warm probe."""
    store = LsmStore(filter_kind="chained", memtable_capacity=CAP, seed=3)
    keys = np.random.default_rng(3).choice(2**62, 3 * CAP, replace=False)
    keys = keys.astype(np.uint64) + np.uint64(1)
    for a in range(0, len(keys), CAP):
        store.put_batch(keys[a:a + CAP], keys[a:a + CAP])
    store.get_batch(keys[:128])
    return store, keys


def _spans(log_dir: str) -> dict:
    """{thread line: [(name, start, end)]} of the store's spans."""
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    out: dict = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("lsm.", "gen.")):
                    out.setdefault((plane.name, line.name), []).append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def _inside(spans, outer: str, inner: str) -> bool:
    return any(o[1] <= i[1] and i[2] <= o[2]
               for o in spans if o[0] == outer
               for i in spans if i[0] == inner)


def test_spans_nest_on_the_calling_thread(loaded, tmp_path):
    store, keys = loaded
    jax.profiler.start_trace(str(tmp_path))
    try:
        store.get_batch(keys[:128])
        store.put_batch(keys[:8], keys[:8])
    finally:
        jax.profiler.stop_trace()
    lines = _spans(str(tmp_path))
    (spans,) = [s for s in lines.values() if any(n == "lsm.get_batch" for n, *_ in s)]
    assert _inside(spans, "lsm.get_batch", "gen.probe.d2h")
    assert _inside(spans, "lsm.get_batch", "lsm.get.mu_wait")
    assert _inside(spans, "lsm.put_batch", "lsm.memtable.merge")


def test_no_annotation_with_the_profiler_off(loaded, monkeypatch):
    store, keys = loaded

    def refuse(*args, **kwargs):
        raise AssertionError("TraceAnnotation made with no profiler running")
    monkeypatch.setattr(trace, "TraceAnnotation", refuse)
    store.get_batch(keys[:128])
    store.put_batch(keys[:8], keys[:8])
    gc.collect()


def test_counters_exact_under_concurrent_readers(loaded):
    store, keys = loaded
    threads, per_thread = 8, 20
    before = store.stats.as_dict()
    rng = np.random.default_rng(5)
    batches = [keys[rng.integers(0, len(keys), 128)] for _ in range(per_thread)]
    errors = []

    def reader():
        try:
            for q in batches:
                store.get_batch(q)
        except Exception as exc:            # reported below
            errors.append(exc)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=reader) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in pool)
    after = store.stats.as_dict()
    d = {k: after[k] - before[k] for k in after}
    calls = threads * per_thread
    assert d["get_calls"] == calls
    assert d["gets"] == calls * 128
    assert d["gets"] == d["memtable_hits"] + d["probed"]
    assert d["sstable_reads"] == d["probed"]        # every key is stored
    assert 0 < d["probed"] <= d["probe_slots"]
    assert d["probe_launches"] == calls
    # the phases account for nearly all of each call's time
    assert 0.9 * d["get_ns"] <= sum(d[k] for k in READ_PHASES) <= d["get_ns"]
    assert calls // 16 <= d["get_cpu_calls"] <= calls // 16 + 1
    assert 0 < d["get_cpu_ns"]


def test_collector_passes_are_counted(loaded):
    before = loaded[0].stats.as_dict()
    gc.collect()
    after = loaded[0].stats.as_dict()
    assert after["gc_collections"] > before["gc_collections"]
    assert after["gc_full_collections"] > before["gc_full_collections"]
    assert after["gc_pause_ns"] > before["gc_pause_ns"]


def test_build_counters(loaded):
    store, _ = loaded
    s = store.stats.as_dict()
    built = s["flushes"] + s["compactions"]
    assert built >= 3
    # a chained filter peels two stages, each at least one layout
    assert s["filter_build_attempts"] >= 2 * built
    assert s["filter_build_ns"] > 0 and s["publish_ns"] > 0
    assert s["first_probes"] >= 1 and s["first_probe_ns"] > 0
    assert s["put_calls"] >= 3 and s["merge_ns"] <= s["put_ns"]


def test_splice_counts_the_memtable_rows_it_copies():
    """A small batch into a big memtable copies only the delta run under
    the small lock: the rows spliced a put stay below ``DELTA_ROWS`` while
    the memtable grows past three times that, and folds carry the rest."""
    store = LsmStore(filter_kind="none", memtable_capacity=10**9)
    keys = np.arange(1, 40_001, 2, dtype=np.uint64)
    store.put_batch(keys, keys)
    assert store.stats.memtable_rows_spliced == 0      # bulk: into the base
    fresh = np.arange(40_001, 40_001 + 2 * 200 * 128, 2, dtype=np.uint64)
    for batch in fresh.reshape(200, 128):
        before = store.stats.memtable_rows_spliced
        store.put_batch(batch, batch)
        assert store.stats.memtable_rows_spliced - before < DELTA_ROWS
    s = store.stats
    assert store.memtable_len == len(keys) + len(fresh) > 2 * DELTA_ROWS
    assert s.put_calls == 201
    # a splice of the whole memtable would copy it on every put
    whole = sum(len(keys) + 128 * i for i in range(200))
    assert 0 < s.memtable_rows_spliced < whole // 4
    assert s.memtable_folds == len(fresh) // DELTA_ROWS
    assert s.fold_rows > len(keys) and s.fold_ns > 0

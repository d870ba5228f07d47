"""Generation/snapshot lifecycle (ISSUE 5): publish immutability, snapshot
pinning across compaction, deferred tombstone GC until release, refcount
hygiene (no generation leaks), old-generation probe-boundary parity,
mid-rebuild read atomicity, and the single-swap-point
contract for the legacy ``compact()`` path (scan cursors started before a
compaction see the pre-compaction key set).
"""
import gc
import weakref

import numpy as np
import pytest

from repro.core import hashing as H
from repro.kernels import common
from repro.kernels.lsm_probe import lsm_probe, pack_chain_params
from repro.serving.filter_service import FilterService
from repro.storage import LsmStore

KEYS = H.random_keys(30_000, seed=37)


def _store(seed=21, kind="chained", **kw):
    kw.setdefault("memtable_capacity", 10 ** 9)
    kw.setdefault("auto_compact", False)
    kw.setdefault("compact_min_run", 2)
    kw.setdefault("compact_size_ratio", 1e9)
    return LsmStore(filter_kind=kind, seed=seed,
                    bits_per_key=8.0 if kind == "bloom" else 10.0, **kw)


def _fill(store, n_tables=3, per=250, val_off=1):
    runs = []
    for i in range(n_tables):
        ks = np.sort(KEYS[i * per:(i + 1) * per])
        store.put_batch(ks, ks + np.uint64(val_off + i))
        store.flush()
        runs.append(ks)
    return runs


# --------------------------------------------------------- publish contract
def test_generation_publish_freezes_arrays():
    """White-box: no generation's arrays are mutable after publish — bank
    buffer, probe-param lanes and every pinned SSTable column are
    read-only, and later publishes leave them bit-identical."""
    store = _store(seed=1)
    _fill(store, 2)
    gen = store.generation
    assert gen.gen_id == 2 and gen.n_tables == 2
    assert not gen.tables.flags.writeable
    assert not gen.params.flags.writeable
    for t in gen.sstables:
        assert not t.keys.flags.writeable
        assert not t.vals.flags.writeable
        assert t.tombs is None or not t.tombs.flags.writeable
    tables_copy = gen.tables.copy()
    params_copy = gen.params.copy()
    key_copies = [t.keys.copy() for t in gen.sstables]
    # flush + compact publish newer generations...
    store.put_batch(np.sort(KEYS[600:900]), KEYS[600:900])
    store.flush()
    store.compact()
    assert store.generation.gen_id > gen.gen_id
    # ...while the old generation's buffers are untouched
    np.testing.assert_array_equal(gen.tables, tables_copy)
    np.testing.assert_array_equal(gen.params, params_copy)
    for t, kc in zip(gen.sstables, key_copies):
        np.testing.assert_array_equal(t.keys, kc)
    with pytest.raises(ValueError):
        gen.tables[0] = 1
    with pytest.raises(ValueError):
        gen.sstables[0].keys[0] = 1


def test_generation_ids_monotonic_one_publish_per_mutation():
    """flush / compact / deferred-GC each publish EXACTLY ONE generation —
    the single-swap-point contract — even when a flush triggers multiple
    internal merge runs."""
    store = _store(seed=2, auto_compact=True, compact_min_run=2,
                   compact_size_ratio=4.0)
    published = []
    orig = LsmStore._publish

    def counted(self):
        orig(self)
        published.append(self.generation.gen_id)

    LsmStore._publish = counted
    try:
        for i in range(6):
            ks = np.sort(KEYS[i * 120:(i + 1) * 120])
            store.put_batch(ks, ks)
            store.flush()            # several flushes compact multiple runs
        n_flush_pubs = len(published)
        assert n_flush_pubs == 6     # one publish per flush, compactions incl.
        store.compact()
        assert len(published) == n_flush_pubs + 1
        assert published == sorted(published)     # monotonically increasing
        assert store.stats.generations_published == len(published)
    finally:
        LsmStore._publish = orig


# ------------------------------------------------------- snapshot lifecycle
def test_snapshot_pins_generation_across_compact():
    """An open snapshot pins its generation across ``compact()``: pinned
    SSTables/filters are not mutated or freed, reads answer from the
    open-time state, and refcounts drop to zero on close."""
    store = _store(seed=3)
    runs = _fill(store, 4, per=200)
    dels = runs[0][:60]
    store.delete_batch(dels)
    store.flush()
    snap = store.snapshot()
    pinned = snap.gen
    assert store.pinned_generations == {pinned.gen_id: 1}
    pre_k, pre_v = snap.scan(0, 2 ** 64)
    pre_get = snap.get_batch(np.concatenate(runs))
    pinned_keys = [t.keys.copy() for t in pinned.sstables]
    pinned_tables = pinned.tables.copy()

    # a second snapshot of the same generation bumps the refcount
    snap2 = store.snapshot()
    assert store.pinned_generations == {pinned.gen_id: 2}
    snap2.close()
    assert store.pinned_generations == {pinned.gen_id: 1}

    # mutate the world underneath: overwrite, delete, flush, compact
    store.put_batch(runs[1][:50], runs[1][:50] + np.uint64(99))
    store.delete_batch(runs[2][:50])
    store.flush()
    store.compact()
    assert store.n_tables == 1
    assert store.generation.gen_id > pinned.gen_id

    # pinned arrays bit-identical, pinned reads answer from open time
    for t, kc in zip(pinned.sstables, pinned_keys):
        np.testing.assert_array_equal(t.keys, kc)
    np.testing.assert_array_equal(pinned.tables, pinned_tables)
    k2, v2 = snap.scan(0, 2 ** 64)
    np.testing.assert_array_equal(k2, pre_k)
    np.testing.assert_array_equal(v2, pre_v)
    g2 = snap.get_batch(np.concatenate(runs))
    for got, exp in zip(g2, pre_get):
        np.testing.assert_array_equal(got, exp)
    assert (g2[2] <= 1).all()          # chained bound holds on pinned reads

    snap.close()
    assert store.pinned_generations == {} and store.open_snapshots == 0
    with pytest.raises(RuntimeError):
        snap.get_batch(runs[0][:4])
    snap.close()                       # idempotent


def test_no_generation_leak_after_open_close_cycles():
    """N open/close cycles leave no pinned generation behind; closed
    snapshots release the last reference to their generation (weakref
    dies once the handle is dropped)."""
    store = _store(seed=4)
    _fill(store, 2)
    refs = []
    for i in range(8):
        snap = store.snapshot()
        snap.get_batch(KEYS[:32])
        refs.append(weakref.ref(snap.gen))
        # mutate so the NEXT snapshot pins a different generation
        ks = np.sort(KEYS[(i + 3) * 250:(i + 4) * 250])
        store.put_batch(ks, ks)
        store.flush()
        snap.close()
        del snap
    assert store.open_snapshots == 0
    assert store.pinned_generations == {}
    assert store.stats.snapshots_opened == store.stats.snapshots_closed == 8
    gc.collect()
    dead = [r() is None for r in refs]
    # every old generation is collectable; the current one may live on
    assert all(dead[:-1]), dead


def test_snapshot_sees_memtable_image_at_open():
    """The snapshot's memtable image is frozen: later puts/deletes
    (including big-memtable splices and folds) and the flush that drains
    the memtable are invisible to it."""
    store = _store(seed=5)
    a = np.sort(KEYS[:300])
    store.put_batch(a, a + np.uint64(1))      # stays in the memtable
    store.delete_batch(a[:20])                # memtable tombstones
    snap = store.snapshot()
    assert snap.gen.n_tables == 0
    f, v, r = snap.get_batch(a)
    assert not f[:20].any() and f[20:].all() and (r == 0).all()
    np.testing.assert_array_equal(v[20:], a[20:] + np.uint64(1))
    # overwrite + drain the live memtable
    store.put_batch(a[20:40], a[20:40] + np.uint64(77))
    store.flush()
    store.put_batch(a[:10], a[:10])
    f2, v2, _ = snap.get_batch(a)
    np.testing.assert_array_equal(f2, f)
    np.testing.assert_array_equal(v2, v)
    ks, vs = snap.scan(0, 2 ** 64)
    np.testing.assert_array_equal(ks, a[20:])
    np.testing.assert_array_equal(vs, a[20:] + np.uint64(1))
    snap.close()


# ------------------------------------------------------------- deferred GC
def test_deferred_tombstone_gc_until_release():
    """Compaction must NOT garbage-collect tombstones an open snapshot
    still observes; release of the last snapshot collects them (and
    republishes). Tombstones NO open snapshot observes stay GC-eligible."""
    store = _store(seed=6)
    runs = _fill(store, 2, per=250)
    dels = runs[0][:80]
    store.delete_batch(dels)
    store.flush()                     # tombstone run on top
    snap = store.snapshot()           # opened AFTER the delete: sees tombs
    assert snap.sees_tombstone(dels).all()
    store.compact()
    assert store.n_tables == 1
    merged = store.sstables[0]
    # deferred: records retained, none GC'd, pending flag set
    assert merged.tombs is not None and merged.tombs.sum() == len(dels)
    assert store.stats.tombstones_gc_deferred == len(dels)
    assert store.stats.tombstones_gced == 0
    # both views agree the keys are deleted (chained: 0 reads everywhere)
    for view in (snap, store):
        f, _, r = view.get_batch(dels)
        assert not f.any() and (r <= 1).all()
    gen_before_release = store.generation.gen_id
    snap.close()                      # last release -> deferred GC sweep
    assert store.open_snapshots == 0
    merged = store.sstables[0]
    assert merged.tombs is None or not merged.tombs.any()
    assert not np.isin(merged.keys, dels).any()
    assert store.stats.tombstones_gced == len(dels)
    assert store.generation.gen_id == gen_before_release + 1   # ONE publish
    # the GC'd keys still fire nothing (negatives ride the rebuild)
    first, mask = store.probe_batch(dels)
    assert (first == store.n_tables).all() and (mask == 0).all()
    f, _, r = store.get_batch(dels)
    assert not f.any() and (r == 0).all()


def test_gc_not_deferred_for_tombstones_no_snapshot_sees():
    """Precision of the visibility rule: a snapshot opened BEFORE a delete
    resolves the key to its LIVE pinned record, so the later tombstone is
    not deferred on its behalf — compaction GCs it immediately while the
    snapshot keeps reading the pre-delete value."""
    store = _store(seed=7)
    runs = _fill(store, 2, per=250)
    snap = store.snapshot()           # opened BEFORE the delete
    dels = runs[0][:80]
    assert not snap.sees_tombstone(dels).any()
    store.delete_batch(dels)
    store.flush()
    store.compact()
    merged = store.sstables[0]
    assert merged.tombs is None or not merged.tombs.any()     # GC ran
    assert store.stats.tombstones_gced == len(dels)
    assert store.stats.tombstones_gc_deferred == 0
    # the pinned view still reads the live pre-delete records
    f, v, _ = snap.get_batch(dels)
    assert f.all()
    np.testing.assert_array_equal(v, dels + np.uint64(1))
    snap.close()


# ------------------------------------------------------------ probe boundary
def test_old_generation_probe_bit_identical_after_rebuild():
    """Probing an old generation's packed bank AFTER a rebuild publishes a
    new one returns bit-identical results to pre-swap probes — straight
    through the fused probe with the old generation's
    own frozen tables/params."""
    store = _store(seed=8)
    _fill(store, 3, per=220)
    gen_a = store.generation
    q = np.concatenate([KEYS[:3 * 220], KEYS[5000:6200]])
    first_pre, mask_pre = gen_a.probe_batch(q)
    # rebuild: new table count -> structural publish of a NEW generation
    ks = np.sort(KEYS[1000:1400])
    store.put_batch(ks, ks)
    store.flush()
    gen_b = store.generation
    assert gen_b.gen_id > gen_a.gen_id
    assert gen_b.chains != gen_a.chains
    first_post, mask_post = gen_a.probe_batch(q)
    np.testing.assert_array_equal(first_post, first_pre)
    np.testing.assert_array_equal(mask_post, mask_pre)
    # and via a raw lsm_probe launch on the generation's own buffers
    hi, lo = H.np_split_u64(q)
    hi2d, lo2d, n = common.blockify(hi, lo)
    first_raw, mask_raw = lsm_probe(gen_a.tables_dev, hi2d, lo2d,
                                    gen_a.params_dev, chains=gen_a.chains)
    np.testing.assert_array_equal(
        np.asarray(common.unblockify(first_raw, n)), first_pre)
    np.testing.assert_array_equal(
        np.asarray(common.unblockify(mask_raw, n)), mask_pre)
    # params plumbing: the generation's frozen lanes == a fresh pack, and a
    # wrong-length params array is rejected at the kernel boundary
    np.testing.assert_array_equal(gen_a.params,
                                  pack_chain_params(gen_a.chains))
    with pytest.raises(ValueError):
        lsm_probe(gen_a.tables_dev, hi2d, lo2d,
                  np.zeros(2 * len(gen_a.params), np.uint32),
                  chains=gen_a.chains)


def test_get_batch_mid_rebuild_sees_one_consistent_generation():
    """A get_batch issued MID-rebuild (while the next bank is being
    prepared, before the publish swap) resolves against the old generation
    and returns exactly the pre-flush answers — it can never observe a
    half-refreshed params array because the swap is one reference
    assignment of a fully-built Generation."""
    store = _store(seed=9)
    runs = _fill(store, 2, per=200)
    q = np.concatenate([runs[0], runs[1], KEYS[7000:7400]])
    pre = store._view_get_batch(store.generation, np.empty(0, np.uint64),
                                np.empty(0, np.uint64), np.empty(0, bool), q,
                                store.stats)
    mid_results = []
    orig_prepare = FilterService.prepare

    def hooked(self, filters, **kw):
        # the store's build-side lists are already edited here, but no
        # publish has happened: reads must still serve the old generation
        mid_results.append(store._view_get_batch(
            store.generation, np.empty(0, np.uint64),
            np.empty(0, np.uint64), np.empty(0, bool), q, store.stats))
        mid_results.append(store.generation.gen_id)
        return orig_prepare(self, filters, **kw)

    FilterService.prepare = hooked
    try:
        ks = np.sort(KEYS[2000:2300])
        store.put_batch(ks, ks)
        store.flush()                 # structural change -> prepare+publish
    finally:
        FilterService.prepare = orig_prepare
    assert len(mid_results) == 2, "rebuild path was not exercised"
    mid, mid_gen = mid_results
    assert mid_gen == 2               # still the pre-flush generation
    for got, exp in zip(mid, pre):
        np.testing.assert_array_equal(got, exp)
    # after the swap the new keys resolve
    f, _, _ = store.get_batch(ks)
    assert f.all()


def test_filter_service_double_buffered_states():
    """prepare/publish: the staged state is invisible until published; a
    captured old state keeps probing bit-identically after the swap; stats
    reset on publish but survive refresh_tables."""
    from repro.core.bloom import BloomFilter
    f1 = BloomFilter.build(KEYS[:500], 0.02, seed=1)
    svc = FilterService([f1])
    v0 = svc.version
    old_state = svc.state
    old_member, _ = svc.probe(KEYS[:2000])
    f2 = BloomFilter.build(KEYS[:900], 0.02, seed=2)
    staged = svc.prepare([f1, f2], warm=True)
    assert svc.state is old_state and svc.version == v0   # not yet visible
    assert staged.version == v0 + 1
    svc.publish(staged)
    assert svc.state is staged and svc.version == v0 + 1
    assert svc.stats.lookups == 0                         # reset on publish
    new_member, _ = svc.probe(KEYS[:2000])
    np.testing.assert_array_equal(new_member[0], old_member[0])
    np.testing.assert_array_equal(new_member[1], f2.query(KEYS[:2000]))
    # the old state is still fully probe-able, bit-identically, and its
    # probes leave the current stats untouched
    lookups_before = svc.stats.lookups
    old_again, _ = svc.probe(KEYS[:2000], state=old_state)
    np.testing.assert_array_equal(old_again, old_member)
    assert svc.stats.lookups == lookups_before
    assert not old_state.bank.tables.flags.writeable
    # content-only refresh: version bumps, probe_fn and stats survive
    f1.insert(KEYS[500:600])
    svc.probe(KEYS[:100])
    lookups = svc.stats.lookups
    pf = svc.state.probe_fn
    svc.refresh_tables([f1, f2])
    assert svc.version == v0 + 2
    assert svc.state.probe_fn is pf
    assert svc.stats.lookups == lookups
    member, _ = svc.probe(KEYS[500:600])
    assert member[0].all()


# ----------------------------------------- single swap point / scan cursors
def test_scan_cursor_survives_interleaved_compaction():
    """Regression for the PR-4 consistency gap: a scan started before
    ``compact()`` sees the pre-compaction key set. The paged cursor pins a
    snapshot; compactions, flushes and overwrites between pages change
    nothing it yields."""
    store = _store(seed=10, kind="chained")
    runs = _fill(store, 4, per=200)
    store.delete_batch(runs[1][:40])
    store.flush()
    expect_k, expect_v = store.scan(0, 2 ** 64)
    cursor = store.scan_iter(0, 2 ** 64, page_size=97)
    pages = [next(cursor)]
    assert store.open_snapshots == 1          # cursor holds a pin
    store.compact()                           # in-place swap would tear here
    assert store.n_tables == 1
    store.put_batch(runs[0][:50], runs[0][:50] + np.uint64(5))
    store.delete_batch(runs[2][:50])
    store.flush()
    pages += list(cursor)
    got_k = np.concatenate([p[0] for p in pages])
    got_v = np.concatenate([p[1] for p in pages])
    np.testing.assert_array_equal(got_k, expect_k)
    np.testing.assert_array_equal(got_v, expect_v)
    assert store.open_snapshots == 0          # pin released at exhaustion
    assert (np.diff(got_k.astype(object)) > 0).all()   # strictly ascending
    # the LIVE scan sees the post-compaction world
    live_k, _ = store.scan(0, 2 ** 64)
    assert not np.isin(runs[2][:50], live_k).any()


def test_scan_iter_pins_eagerly_at_call_time():
    """The cursor's snapshot opens when ``scan_iter`` is CALLED, not at
    first iteration: writes landing between the call and the first page
    are invisible, and bad arguments raise at the call site (without
    leaking a pin)."""
    store = _store(seed=12)
    a = np.sort(KEYS[:100])
    store.put_batch(a, a)
    store.flush()
    cursor = store.scan_iter(0, 2 ** 64, page_size=16)
    assert store.open_snapshots == 1           # pinned before any next()
    late = np.sort(KEYS[200:260])
    store.put_batch(late, late)
    store.flush()
    store.compact()
    got = np.concatenate([p[0] for p in cursor])
    np.testing.assert_array_equal(got, a)      # late keys not yielded
    assert store.open_snapshots == 0
    # eager argument validation, at the CALL, with the pin released
    with pytest.raises(ValueError):
        store.scan_iter(0, 2 ** 64, page_size=0)
    with pytest.raises(ValueError):
        store.scan_iter(0, 2 ** 64 + 1)
    assert store.open_snapshots == 0
    snap = store.snapshot()
    with pytest.raises(ValueError):
        snap.scan_iter(5, 4, page_size=-1)
    snap.close()
    # a cursor closed BEFORE its first page releases the pin (a wrapper
    # generator would skip its finally here and leak it forever)...
    c1 = store.scan_iter(0, 2 ** 64)
    assert store.open_snapshots == 1
    c1.close()
    assert store.open_snapshots == 0
    # ...as does an abandoned cursor, at garbage collection
    c2 = store.scan_iter(0, 2 ** 64)
    assert store.open_snapshots == 1
    del c2
    gc.collect()
    assert store.open_snapshots == 0
    # and the context-manager form, mid-iteration
    with store.scan_iter(0, 2 ** 64, page_size=8) as c3:
        next(c3)
        assert store.open_snapshots == 1
    assert store.open_snapshots == 0 and store.pinned_generations == {}


def test_flush_past_table_cap_preserves_batch():
    """The MAX_TABLES error path must not lose the drained batch: the
    build-side lists are installed before the raise (reads stay on the
    last published generation — stale but consistent), and the compact()
    the error demands surfaces everything."""
    from repro.kernels.lsm_probe import MAX_TABLES
    store = LsmStore(filter_kind="chained", seed=14, auto_compact=False,
                     memtable_capacity=10 ** 9, compact_min_run=2,
                     compact_size_ratio=1e9)
    per = 20
    for i in range(MAX_TABLES):
        ks = np.sort(KEYS[i * per:(i + 1) * per])
        store.put_batch(ks, ks)
        store.flush()
    last = np.sort(KEYS[MAX_TABLES * per:(MAX_TABLES + 1) * per])
    dels = KEYS[:10]                       # tombstones ride the lost batch
    store.put_batch(last, last)
    store.delete_batch(dels)
    with pytest.raises(RuntimeError, match="compact") as exc_info:
        store.flush()
    # the overflow error is typed backpressure now: still a RuntimeError
    # for pre-typed callers, but carrying the install-time table count
    from repro.storage import WriteStall
    assert isinstance(exc_info.value, WriteStall)
    assert exc_info.value.n_tables == MAX_TABLES + 1
    assert store.n_tables == MAX_TABLES + 1       # batch NOT lost
    # reads still serve the last published (consistent) generation
    f, _, _ = store.get_batch(last)
    assert not f.any()
    store.compact()                               # the prescribed recovery
    assert store.n_tables <= MAX_TABLES
    f, v, r = store.get_batch(last)
    assert f.all() and (r <= 1).all()
    np.testing.assert_array_equal(v, last)
    fd, _, _ = store.get_batch(np.asarray(dels, np.uint64))
    assert not fd.any()                           # tombstones survived too
    ks, _ = store.scan(0, 2 ** 64)
    assert not np.isin(np.asarray(dels, np.uint64), ks).any()


def test_snapshot_reads_accounted_separately():
    """Snapshot-handle traffic lands in ``snap_stats``, never in the
    live-read ``stats`` — gated metrics derived from live accounting
    cannot be contaminated by pinned-view reads."""
    store = _store(seed=13)
    a = np.sort(KEYS[:200])
    store.put_batch(a, a)
    store.flush()
    store.get_batch(a[:50])
    store.scan(0, 2 ** 64)
    live_gets, live_scans = store.stats.gets, store.stats.scans
    live_reads = store.stats.sstable_reads
    with store.snapshot() as snap:
        snap.get_batch(a)
        snap.scan(0, 2 ** 64)
        list(snap.scan_iter(0, 2 ** 64, page_size=32))
    assert store.stats.gets == live_gets
    assert store.stats.scans == live_scans
    assert store.stats.sstable_reads == live_reads
    assert store.snap_stats.gets == len(a)
    assert store.snap_stats.scans == 2         # scan + scan_iter
    assert store.snap_stats.sstable_reads > 0
    # the store-level cursor IS live traffic: it counts one live scan
    list(store.scan_iter(0, 2 ** 64, page_size=64))
    assert store.stats.scans == live_scans + 1


@pytest.mark.parametrize("kind", ["bloom", "none"])
def test_snapshot_reads_baseline_kinds(kind):
    """Snapshot pinning is filter-kind agnostic: bloom and filterless
    stores answer snapshot reads from the pinned state too."""
    store = _store(seed=11, kind=kind)
    runs = _fill(store, 3, per=150)
    snap = store.snapshot()
    q = np.concatenate([np.concatenate(runs), KEYS[9000:9400]])
    pre = snap.get_batch(q)
    pre_scan = snap.scan(0, 2 ** 64)
    store.delete_batch(runs[0])
    store.flush()
    store.compact()
    for got, exp in zip(snap.get_batch(q), pre):
        np.testing.assert_array_equal(got, exp)
    for got, exp in zip(snap.scan(0, 2 ** 64), pre_scan):
        np.testing.assert_array_equal(got, exp)
    f, _, _ = store.get_batch(runs[0])
    assert not f.any()
    snap.close()
    assert store.pinned_generations == {}

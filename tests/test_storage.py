"""Batched LSM storage engine (ISSUE 2): Othello/LSM-chain packed-table
roundtrips, fused ``lsm_probe`` kernel parity, LsmStore vs the host-side
``LsmLevelChained`` reference (exact found/reads match, property-tested
over random flush/query sequences), size-tiered compaction invariants,
baseline read policies, and workload generator determinism.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import hashing as H
from repro.core.lsm import ChainedTableFilter, LsmLevelChained, SSTable
from repro.core.othello import DynamicExactFilter, pack_bitmap, unpack_bitmap
from repro.core.tables import TABLE_ALIGN
from repro.kernels import common
from repro.kernels.lsm_probe import lsm_probe
from repro.serving.filter_service import FilterBank, FilterService
from repro.storage import (LsmStore, LatencyAccountant, mixed_read_write,
                           uniform_write_heavy, zipfian_read_heavy,
                           crud_mixed, run_workload)

KEYS = H.random_keys(50_000, seed=29)


# ------------------------------------------------------------ SSTable search
def test_sstable_contains_searchsorted():
    keys = np.sort(KEYS[:500])
    t = SSTable(keys)
    for k in keys[::50]:
        assert t.contains(int(k))
    assert not t.contains(int(KEYS[600]))
    # boundary: probe above the largest key must not read out of range
    assert not t.contains(int(np.uint64(2**64 - 1)))


def test_sstable_contains_many_and_get_many():
    keys = np.sort(KEYS[:500])
    vals = keys >> np.uint64(9)
    t = SSTable(keys, vals)
    q = np.concatenate([keys[::7], KEYS[600:900]])
    got = t.contains_many(q)
    exp = np.isin(q, keys)
    np.testing.assert_array_equal(got, exp)
    hit, v, dead = t.get_many(q)
    np.testing.assert_array_equal(hit, exp)
    np.testing.assert_array_equal(v[hit], q[hit] >> np.uint64(9))
    assert (v[~hit] == 0).all()
    assert not dead.any()                     # no tombstones in this table
    # tombstoned rows report dead (and no value), not live
    tombs = np.zeros(len(keys), dtype=bool)
    tombs[::3] = True
    td = SSTable(keys, vals, tombs)
    live, v2, dead2 = td.get_many(q)
    np.testing.assert_array_equal(dead2, exp & np.isin(q, keys[tombs]))
    np.testing.assert_array_equal(live, exp & ~dead2)
    assert (v2[dead2] == 0).all()
    # empty table edge
    empty = SSTable(np.empty(0, np.uint64))
    assert not empty.contains_many(q).any()
    l0, _, d0 = empty.get_many(q)
    assert not l0.any() and not d0.any()


# ----------------------------------------------------- Othello packed tables
def test_pack_unpack_bitmap_roundtrip():
    rng = np.random.default_rng(3)
    for m in (1, 31, 32, 33, 1000):
        bits = rng.integers(0, 2, m).astype(np.uint8)
        np.testing.assert_array_equal(unpack_bitmap(pack_bitmap(bits), m), bits)


def test_othello_tables_roundtrip_and_shift():
    f = DynamicExactFilter.build(KEYS[:700], KEYS[700:2000], seed=5)
    tables, lay = f.to_tables()
    assert tables.dtype == np.uint32 and len(tables) % TABLE_ALIGN == 0
    g = DynamicExactFilter.from_tables(tables, lay)
    np.testing.assert_array_equal(f.query(KEYS[:4000]), g.query(KEYS[:4000]))
    shifted = np.concatenate([np.zeros(2 * TABLE_ALIGN, np.uint32), tables])
    h = DynamicExactFilter.from_tables(shifted, lay.shift(2 * TABLE_ALIGN))
    np.testing.assert_array_equal(f.query(KEYS[:4000]), h.query(KEYS[:4000]))


def test_chained_table_filter_roundtrip():
    f = ChainedTableFilter.build(KEYS[:600], KEYS[600:2500], seed1=7, seed2=8)
    tables, lay = f.to_tables()
    g = ChainedTableFilter.from_tables(tables, lay)
    np.testing.assert_array_equal(f.query(KEYS[:5000]), g.query(KEYS[:5000]))
    # exactness over the build universe
    assert f.query(KEYS[:600]).all()
    assert not f.query(KEYS[600:2500]).any()


def test_filter_service_dispatches_lsm_layouts():
    cf = ChainedTableFilter.build(KEYS[:600], KEYS[600:2500], seed1=1, seed2=2)
    dyn = DynamicExactFilter.build(KEYS[:400], KEYS[400:1200], seed=3)
    svc = FilterService([cf, dyn])
    q = KEYS[:4096]
    member, probes = svc.probe(q)
    np.testing.assert_array_equal(member[0], cf.query(q))
    np.testing.assert_array_equal(member[1], dyn.query(q))
    # sequential accounting: stage 2 touched only when stage 1 fires
    assert set(np.unique(probes[0])) <= {1, 2}
    assert set(np.unique(probes[1])) == {1}


# ------------------------------------------------------- fused kernel parity
def _flush_level(n_tables, per, seed):
    lvl = LsmLevelChained(seed=seed)
    for i in range(n_tables):
        lvl.flush(KEYS[i * per:(i + 1) * per])
    return lvl


def test_lsm_probe_matches_host_filters():
    lvl = _flush_level(4, 400, seed=9)
    bank = FilterBank.pack(lvl.filters)
    chains = tuple(lay.probe_params() for lay in bank.layouts)
    q = KEYS[:4 * 400 + 2500]
    hi2d, lo2d, n = common.blockify(*H.np_split_u64(q))
    first, mask = lsm_probe(bank.tables, hi2d, lo2d, chains=chains)
    first = np.asarray(common.unblockify(first, n))
    mask = np.asarray(common.unblockify(mask, n))
    hits = np.stack([f.query(q) for f in lvl.filters], axis=1)
    np.testing.assert_array_equal(
        mask, (hits.astype(np.int64) << np.arange(4)).sum(axis=1))
    np.testing.assert_array_equal(
        first, np.where(hits.any(1), hits.argmax(1), 4))


def test_lsm_probe_rejects_bad_table_counts():
    hi2d, lo2d, _ = common.blockify(*H.np_split_u64(KEYS[:8]))
    with pytest.raises(ValueError):
        lsm_probe(np.zeros(128, np.uint32), hi2d, lo2d, chains=())


# --------------------------------------------- store vs host-model reference
def _reference(lvl: LsmLevelChained, q: np.ndarray):
    ref = [lvl.point_query(int(k)) for k in q]
    return (np.array([r[0] for r in ref]), np.array([r[1] for r in ref]))


def test_get_batch_matches_reference_basic():
    store = LsmStore(seed=5, memtable_capacity=10 ** 9, auto_compact=False)
    lvl = LsmLevelChained(seed=5)
    per = 300
    for i in range(3):
        ks = KEYS[i * per:(i + 1) * per]
        store.put_batch(ks, ks)
        store.flush()
        lvl.flush(ks)
    q = np.concatenate([KEYS[:3 * per], KEYS[3 * per:3 * per + 1200]])
    found, vals, reads = store.get_batch(q)
    ref_found, ref_reads = _reference(lvl, q)
    np.testing.assert_array_equal(found, ref_found)
    np.testing.assert_array_equal(reads, ref_reads)
    np.testing.assert_array_equal(vals[:3 * per], q[:3 * per])
    assert (reads <= 1).all()                      # §5.4 ≤ 1 read per query


@given(st.integers(1, 4), st.integers(80, 220), st.integers(0, 60),
       st.integers(0, 1))
@settings(max_examples=5, deadline=None)
def test_get_batch_matches_reference_property(n_tables, per, seed, overlap):
    """Exact found/reads parity between the batched fused-kernel path and
    the host discrete-event model across random flush sequences (optionally
    with overlapping key ranges — updated keys shadowed by newer tables)."""
    store = LsmStore(seed=seed, memtable_capacity=10 ** 9, auto_compact=False)
    lvl = LsmLevelChained(seed=seed)
    step = per - (per // 3 if overlap else 0)
    for i in range(n_tables):
        ks = KEYS[i * step:i * step + per]
        store.put_batch(ks, ks)
        store.flush()
        lvl.flush(ks)
    hi = (n_tables - 1) * step + per
    q = np.concatenate([KEYS[:hi:3], KEYS[hi:hi + 400]])
    found, _, reads = store.get_batch(q)
    ref_found, ref_reads = _reference(lvl, q)
    np.testing.assert_array_equal(found, ref_found)
    np.testing.assert_array_equal(reads, ref_reads)


def test_from_parts_reference_shares_store_filters():
    """LsmLevelChained.from_parts wraps the store's own tables/filters as a
    host model — the cross-check used by benchmarks/lsm_pointquery."""
    store = LsmStore(seed=8, memtable_capacity=10 ** 9, auto_compact=False)
    for i in range(3):
        ks = KEYS[i * 250:(i + 1) * 250]
        store.put_batch(ks, ks)
        store.flush()
    lvl = LsmLevelChained.from_parts(store.sstables, store.filters, seed=8)
    q = np.concatenate([KEYS[:750:5], KEYS[800:1400]])
    found, _, reads = store.get_batch(q)
    ref_found, ref_reads = _reference(lvl, q)
    np.testing.assert_array_equal(found, ref_found)
    np.testing.assert_array_equal(reads, ref_reads)


# --------------------------------------------------------------- compaction
def test_compaction_preserves_contents_and_read_bound():
    store = LsmStore(seed=2, memtable_capacity=10 ** 9, compact_min_run=3)
    n_flushes, per, step = 8, 260, 200       # 60-key overlap between flushes
    for i in range(n_flushes):
        ks = KEYS[i * step:i * step + per]
        store.put_batch(ks, ks + np.uint64(i))
        store.flush()
    assert store.stats.compactions > 0
    assert store.n_tables < n_flushes
    hi = (n_flushes - 1) * step + per
    allk = KEYS[:hi]
    found, vals, reads = store.get_batch(allk)
    assert found.all()
    assert (reads == 1).all()                 # exactness survives compaction
    # newest-wins shadowing: key i was last written by flush min(i//step, last)
    exp_flush = np.minimum(np.arange(hi) // step, n_flushes - 1)
    np.testing.assert_array_equal(vals, allk + exp_flush.astype(np.uint64))
    # misses still pay <= 1 wasted read
    fm, _, rm = store.get_batch(KEYS[20000:22000])
    assert not fm.any() and (rm <= 1).all()


def test_auto_compact_enforces_probe_table_cap():
    """When no size-tiered run qualifies, flush must still keep the store
    under the probe kernel's table cap by force-merging the oldest run."""
    from repro.kernels.lsm_probe import MAX_TABLES
    store = LsmStore(seed=12, memtable_capacity=10 ** 9, compact_min_run=99)
    n_flushes, per = MAX_TABLES + 3, 24
    for i in range(n_flushes):
        ks = KEYS[i * per:(i + 1) * per]
        store.put_batch(ks, ks)
        store.flush()
    assert store.n_tables <= MAX_TABLES
    found, _, reads = store.get_batch(KEYS[:n_flushes * per])
    assert found.all() and (reads == 1).all()


def test_compact_min_run_one_terminates():
    """A 1-table run must never 'merge' into itself (would loop forever)."""
    store = LsmStore(seed=13, memtable_capacity=10 ** 9, compact_min_run=1)
    for i in range(3):
        ks = KEYS[i * 100:(i + 1) * 100]
        store.put_batch(ks, ks)
        store.flush()                       # must return, runs of >= 2 merge
    assert store.n_tables == 1
    found, _, reads = store.get_batch(KEYS[:300])
    assert found.all() and (reads == 1).all()


def test_manual_compact_to_single_table():
    store = LsmStore(seed=3, memtable_capacity=10 ** 9, auto_compact=False,
                     compact_min_run=2, compact_size_ratio=100.0)
    for i in range(4):
        ks = KEYS[i * 200:(i + 1) * 200]
        store.put_batch(ks, ks)
        store.flush()
    assert store.n_tables == 4
    store.compact()
    assert store.n_tables == 1
    found, _, reads = store.get_batch(KEYS[:800])
    assert found.all() and (reads == 1).all()


# ------------------------------------------------------- baseline read paths
@pytest.mark.parametrize("kind,bpk", [("bloom", 8.0), ("none", 0.0)])
def test_baseline_store_read_policies(kind, bpk):
    store = LsmStore(filter_kind=kind, bits_per_key=bpk, seed=4,
                     memtable_capacity=10 ** 9, auto_compact=False)
    per = 300
    for i in range(3):
        ks = KEYS[i * per:(i + 1) * per]
        store.put_batch(ks, ks)
        store.flush()
    found, vals, reads = store.get_batch(KEYS[:3 * per])
    assert found.all()
    np.testing.assert_array_equal(vals, KEYS[:3 * per])
    assert (reads >= 1).all()
    fm, _, rm = store.get_batch(KEYS[5000:6000])
    assert not fm.any()
    if kind == "none":
        # no filter: every miss reads every table
        assert (rm == 3).all()
    else:
        # Bloom misses read one table per false positive — unbounded by the
        # chain rule, bounded by N
        assert (rm <= 3).all()


def test_memtable_hits_cost_zero_reads():
    store = LsmStore(seed=6, memtable_capacity=10 ** 9)
    ks = KEYS[:400]
    store.put_batch(ks, ks)
    found, vals, reads = store.get_batch(ks)
    assert found.all() and (reads == 0).all()
    np.testing.assert_array_equal(vals, ks)
    store.flush()
    store.put(int(ks[0]), 123)               # overwrite: memtable wins
    f, v, r = store.get(int(ks[0]))
    assert (f, v, r) == (True, 123, 0)
    assert store.stats.memtable_hits > 0


def test_get_batch_empty_and_cold():
    store = LsmStore(seed=7)
    found, vals, reads = store.get_batch(np.empty(0, np.uint64))
    assert len(found) == len(vals) == len(reads) == 0
    found, _, reads = store.get_batch(KEYS[:16])    # no memtable, no tables
    assert not found.any() and (reads == 0).all()


# ----------------------------------------------- tombstone deletes + scans
def _filled_store(seed=31, kind="chained", **kw):
    kw.setdefault("memtable_capacity", 10 ** 9)
    kw.setdefault("auto_compact", False)
    store = LsmStore(filter_kind=kind, seed=seed,
                     bits_per_key=8.0 if kind == "bloom" else 10.0, **kw)
    a, b = np.sort(KEYS[:300]), np.sort(KEYS[300:600])
    store.put_batch(a, a + np.uint64(1))
    store.flush()
    store.put_batch(b, b + np.uint64(2))
    store.flush()
    return store, a, b


def test_lsm_probe_ignores_tombstone_only_tables():
    """Probe boundary: a table whose ONLY physical match
    for a key is a tombstone must contribute neither its hits_mask bit nor
    the first-hit index — the deleted key's exclusion happens at filter
    build/update time and the fused kernel must observe it."""
    store, a, b = _filled_store(seed=41)
    dels = np.concatenate([a[:80], b[:40]])
    store.delete_batch(dels)
    store.flush()                       # tombstone-only newest table
    assert store.n_tables == 3
    assert store.sstables[0].tombs is not None and store.sstables[0].tombs.all()
    # straight through the fused kernel, same call probe_batch makes
    hi, lo = H.np_split_u64(dels)
    hi2d, lo2d, n = common.blockify(hi, lo)
    first, mask = lsm_probe(store._tables_dev, hi2d, lo2d,
                            chains=store._chains)
    first = np.asarray(common.unblockify(first, n))
    mask = np.asarray(common.unblockify(mask, n))
    assert (mask == 0).all()            # no table's filter fires at all
    assert (first == store.n_tables).all()
    # live keys still first-hit their owning tables
    live = np.concatenate([a[80:], b[40:]])
    first2, _ = store.probe_batch(live)
    np.testing.assert_array_equal(
        first2, np.where(np.isin(live, b), 1, 2))   # 0 = tombstone table


def test_delete_get_agrees_with_model_and_read_bound():
    from model import ReferenceStore
    store, a, b = _filled_store(seed=42)
    model = ReferenceStore()
    model.put_batch(a, a + np.uint64(1))
    model.put_batch(b, b + np.uint64(2))
    dels = np.concatenate([a[::3], b[::5]])
    store.delete_batch(dels)
    model.delete_batch(dels)
    q = np.concatenate([a, b, KEYS[5000:5500]])
    found, vals, reads = store.get_batch(q)       # memtable tombstones
    exp_found, exp_vals = model.get_batch(q)
    np.testing.assert_array_equal(found, exp_found)
    np.testing.assert_array_equal(vals, exp_vals)
    store.flush()                                 # flushed tombstones
    found, vals, reads = store.get_batch(q)
    np.testing.assert_array_equal(found, exp_found)
    np.testing.assert_array_equal(vals, exp_vals)
    assert (reads <= 1).all()                     # §5.4 bound survives deletes
    assert (reads[np.isin(q, dels)] == 0).all()   # deleted keys fire nothing


def test_filters_never_enroll_tombstoned_keys():
    """exclude_new / ChainedTableFilter.build / exclude_deleted invariant:
    a tombstoned key is enrolled as a stage-2 POSITIVE in no table."""
    store, a, b = _filled_store(seed=43)
    dels = np.concatenate([a[:150], b[:60]])
    store.delete_batch(dels)
    store.flush()
    for t, filt in enumerate(store.filters):
        assert not np.intersect1d(filt.f2.positive_keys, dels).size, t
    # direct build: dead keys passed as negatives can never fire
    f = ChainedTableFilter.build(a, np.concatenate([b, dels]),
                                 seed1=3, seed2=4)
    assert not f.query(dels[np.isin(dels, b)]).any()
    # direct exclude_deleted: kills OWN keys (true positives) too
    f2 = ChainedTableFilter.build(a, b, seed1=5, seed2=6)
    assert f2.query(a[:50]).all()
    f2.exclude_deleted(a[:50])
    assert not f2.query(a[:50]).any()
    assert f2.query(a[50:]).all()                 # untouched keys unaffected
    assert not np.intersect1d(f2.f2.positive_keys, a[:50]).size


def test_compaction_gc_invariants():
    """After full compaction to one run: no tombstone records remain, store
    contents equal the reference model, and total filter bits SHRINK (the
    deleted keys no longer burn filter space)."""
    from model import ReferenceStore
    store, a, b = _filled_store(seed=44, compact_min_run=2,
                                compact_size_ratio=1e9)
    model = ReferenceStore()
    model.put_batch(a, a + np.uint64(1))
    model.put_batch(b, b + np.uint64(2))
    bits_before = store.filter_bits
    dels = np.concatenate([a[:200], b[:200]])
    store.delete_batch(dels)
    model.delete_batch(dels)
    store.flush()
    store.compact()
    assert store.n_tables == 1
    t = store.sstables[0]
    assert t.tombs is None or not t.tombs.any()   # GC ate every tombstone
    assert store.stats.tombstones_gced == len(dels)
    assert not np.isin(t.keys, dels).any()        # records gone, not masked
    assert store.filter_bits < bits_before        # fewer keys -> fewer bits
    assert store.key_count == len(model)
    ks, vs = store.scan(0, 2 ** 64 - 1)
    ek, ev = model.scan(0, 2 ** 64 - 1)
    np.testing.assert_array_equal(ks, ek)
    np.testing.assert_array_equal(vs, ev)
    found, vals, reads = store.get_batch(np.concatenate([a, b]))
    ef, ev2 = model.get_batch(np.concatenate([a, b]))
    np.testing.assert_array_equal(found, ef)
    np.testing.assert_array_equal(vals, ev2)
    assert (reads <= 1).all()
    # deleted keys are fully GC'd AND pinned negatives: they fire nothing
    first, mask = store.probe_batch(dels)
    assert (first == store.n_tables).all() and (mask == 0).all()


def test_useless_tombstones_gc_at_flush():
    """Deleting never-written keys leaves no SSTable rows behind."""
    store = LsmStore(seed=45, memtable_capacity=10 ** 9)
    store.delete_batch(KEYS[:64])
    store.flush()
    assert store.n_tables == 0                    # nothing worth freezing
    assert store.stats.tombstones_gced == 64
    ks = np.sort(KEYS[100:200])
    store.put_batch(ks, ks)
    store.flush()
    store.delete_batch(KEYS[:64])                 # still absent
    store.delete_batch(ks[:10])                   # these DO shadow
    store.flush()
    assert store.n_tables == 2
    newest = store.sstables[0]
    np.testing.assert_array_equal(newest.keys, ks[:10])
    assert newest.tombs.all()


def test_scan_fences_and_newest_wins():
    store = LsmStore(seed=46, memtable_capacity=10 ** 9, auto_compact=False)
    lo_run = np.sort(KEYS[:200])
    hi_run = np.sort(KEYS[200:400])
    store.put_batch(lo_run, lo_run)
    store.flush()
    store.put_batch(hi_run, hi_run)
    store.flush()
    # overwrite some keys (newer table wins) + delete some (masked out)
    over = lo_run[:50]
    store.put_batch(over, over + np.uint64(9))
    store.delete_batch(lo_run[50:80])
    store.flush()
    ks, vs = store.scan(0, 2 ** 64 - 1)
    expect = {int(k): int(k) for k in np.concatenate([lo_run, hi_run])}
    for k in over:
        expect[int(k)] = int(k) + 9
    for k in lo_run[50:80]:
        del expect[int(k)]
    np.testing.assert_array_equal(ks, np.sort(np.array(list(expect), np.uint64)))
    np.testing.assert_array_equal(vs, [expect[int(k)] for k in ks])
    # fence pruning: a window entirely inside one run never slices the other
    pruned0 = store.stats.scan_tables_pruned
    t0 = store.sstables[1]                       # the hi_run table (index 1)
    sub_lo, sub_hi = int(t0.keys[10]), int(t0.keys[40])
    ks2, _ = store.scan(sub_lo, sub_hi)
    assert store.stats.scan_tables_pruned > pruned0
    assert ((ks2 >= sub_lo) & (ks2 < sub_hi)).all()
    # empty + inverted windows
    k0, _ = store.scan(5, 5)
    assert len(k0) == 0
    k1, _ = store.scan(int(hi_run[-1]) + 1, int(hi_run[-1]) + 2)
    assert len(k1) == 0


def test_scan_reaches_max_uint64_key():
    """hi == 2**64 makes the window cover the maximum key — the one record
    a [lo, hi) window with uint64 bounds could never include."""
    top = np.uint64(2 ** 64 - 1)
    store = LsmStore(seed=48, memtable_capacity=10 ** 9)
    ks = np.sort(np.concatenate([KEYS[:50], [top]]))
    store.put_batch(ks, ks)
    store.flush()
    full_k, full_v = store.scan(0, 2 ** 64)
    np.testing.assert_array_equal(full_k, ks)
    assert full_k[-1] == top
    part_k, _ = store.scan(0, 2 ** 64 - 1)        # exclusive: top dropped
    np.testing.assert_array_equal(part_k, ks[:-1])
    with pytest.raises(ValueError):
        store.scan(0, 2 ** 64 + 1)
    store.delete(int(top))
    store.flush()
    gone_k, _ = store.scan(0, 2 ** 64)
    np.testing.assert_array_equal(gone_k, ks[:-1])


def test_memtable_tombstone_costs_zero_reads():
    store, a, b = _filled_store(seed=47)
    store.delete_batch(a[:20])
    f, v, r = store.get_batch(a[:20])
    assert not f.any() and (r == 0).all() and (v == 0).all()
    # re-insert resurrects through the memtable at 0 reads
    store.put_batch(a[:5], a[:5] + np.uint64(3))
    f, v, r = store.get_batch(a[:5])
    assert f.all() and (r == 0).all()
    np.testing.assert_array_equal(v, a[:5] + np.uint64(3))


# ---------------------------------------------------------------- workloads
@pytest.mark.parametrize("gen", [uniform_write_heavy, zipfian_read_heavy,
                                 mixed_read_write, crud_mixed])
def test_workloads_deterministic(gen):
    a, b = gen(12, batch=64, seed=21), gen(12, batch=64, seed=21)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.kind == y.kind
        np.testing.assert_array_equal(x.keys, y.keys)
        assert (x.lo, x.hi) == (y.lo, y.hi)
    c = gen(12, batch=64, seed=22)
    assert any((x.keys != y.keys).any() for x, y in zip(a, c)
               if x.kind != "scan" and len(x.keys) == len(y.keys))


def test_workload_phases_have_independent_streams():
    """Per-phase RNG split: the i-th mixed-phase KEY batch must be a pure
    function of (seed, i) — changing the op-kind mix (write_frac) must not
    reshuffle which keys get drawn."""
    a = zipfian_read_heavy(16, batch=32, n_keys=256, write_frac=0.0, seed=9)
    b = zipfian_read_heavy(16, batch=32, n_keys=256, write_frac=1.0, seed=9)
    mixed_a = [op for op in a if op.kind in ("get", "put")][256 // 32:]
    mixed_b = [op for op in b if op.kind in ("get", "put")][256 // 32:]
    assert [op.kind for op in mixed_a] != [op.kind for op in mixed_b]
    for x, y in zip(mixed_a, mixed_b):
        np.testing.assert_array_equal(x.keys, y.keys)


def test_run_workload_crud_mixed():
    store = LsmStore(seed=10, memtable_capacity=256, compact_min_run=3)
    ops = crud_mixed(30, batch=96, seed=6)
    kinds = {op.kind for op in ops}
    assert kinds >= {"put", "del", "scan"}
    rep = run_workload(store, ops, LatencyAccountant())
    assert store.stats.deletes > 0 and store.stats.scans > 0
    assert rep["scanned_keys"] > 0
    if rep["n"]:
        assert rep["max_reads"] <= 1          # chained bound under deletes
    # deleted prefix really is gone
    deleted = np.concatenate(
        [op.keys for op in ops if op.kind == "del"])
    found, _, reads = store.get_batch(deleted)
    assert not found.any()
    assert (reads <= 1).all()


def test_run_workload_reports_percentiles():
    store = LsmStore(seed=9, memtable_capacity=256, compact_min_run=3)
    rep = run_workload(store, mixed_read_write(24, batch=128, seed=5),
                       LatencyAccountant())
    for key in ("n", "avg_reads", "p50_us", "p95_us", "p99_us", "hit_rate"):
        assert key in rep
    assert rep["n"] > 0
    assert rep["max_reads"] <= 1              # chained store: ≤ 1 read/get
    assert 0.0 < rep["hit_rate"] <= 1.0

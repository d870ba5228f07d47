"""The reference agrees with the store, and the check's rules."""
import numpy as np
import pytest

from chipbench import reference, ycsb
from chipbench.harness import build_store

SEED = 2**31 + 5


@pytest.mark.parametrize("cell", ["ycsb_c.kv8m_chained", "ycsb_c.kv8m_bloom10"])
def test_reference_agrees_with_store_on_seeded_mix(small_config, cell):
    cfg = small_config(cell)
    cfg["store"]["background_compaction"] = False
    n = cfg["record_count"]
    keys = ycsb.record_keys(np.arange(n), SEED)
    vals = ycsb.load_values(keys, SEED)
    store = build_store(cfg, keys, vals, SEED)
    assert store.n_tables == 2 and store.stats.flushes == 2
    store.memtable_capacity = 512          # later writes flush in the loop
    ref = reference.ReferenceStore(keys, vals)
    rng = np.random.default_rng(SEED)
    for step in range(40):
        if step % 3 == 0:                 # updates, and writes of new keys
            new = ycsb.record_keys(n + rng.integers(0, 500, 64), SEED)
            old = keys[rng.integers(0, n, 64)]
            wk = np.concatenate([old, new])
            wv = ycsb.write_value(step, len(wk))
            store.put_batch(wk, wv)
            ref.put_batch(wk, wv)
        q = np.concatenate([keys[rng.integers(0, n, 100)],
                            ycsb.record_keys(n + rng.integers(0, 1000, 28), SEED)])
        f1, v1, _ = store.get_batch(q)
        f2, v2, _ = ref.get_batch(q)
        assert np.array_equal(f1, f2) and np.array_equal(v1[f1], v2[f2])
    assert store.stats.flushes > 2


def _log(writes):
    k, v, s, e = zip(*writes) if writes else ((), (), (), ())
    return reference.WriteLog(np.array(k, np.uint64), np.array(v, np.uint64),
                              np.array(s, np.int64), np.array(e, np.int64))


def test_check_reads_rules():
    base = reference.ReferenceStore(np.array([10, 20], np.uint64),
                                    np.array([1, 2], np.uint64))
    w1 = int(ycsb.write_value(1, 1)[0])
    w2 = int(ycsb.write_value(2, 1)[0])
    log = _log([(10, w1, 100, 200), (10, w2, 300, 400), (30, w1 + 1, 100, 200)])

    def bad(key, s, e, found, val):
        return bool(reference.check_reads(base, log, np.array([key], np.uint64),
                                          np.array([s]), np.array([e]),
                                          np.array([found]), np.array([val], np.uint64))[0])
    assert not bad(10, 0, 50, True, 1)          # loaded value before any write
    assert not bad(10, 150, 160, True, 1)       # overlaps the first write
    assert not bad(10, 150, 160, True, w1)
    assert bad(10, 250, 260, True, 1)           # first write finished: stale
    assert not bad(10, 250, 260, True, w1)
    assert not bad(10, 350, 360, True, w2)      # overlapping the second
    assert bad(10, 500, 510, True, w1)          # overwritten before the read
    assert bad(10, 50, 60, True, w2)            # a write from the future
    assert bad(20, 500, 510, True, w1)          # another key's write
    assert bad(20, 0, 10, True, 2 | (1 << 40))  # wrong value
    assert bad(20, 0, 10, False, 0)             # a loaded key missing
    assert not bad(30, 50, 60, False, 0)        # insert not yet begun
    assert not bad(30, 150, 160, False, 0)      # insert in flight
    assert bad(30, 250, 260, False, 0)          # insert acknowledged: lost
    assert not bad(30, 250, 260, True, w1 + 1)
    assert not bad(40, 0, 10, False, 0)         # never written


def test_control_store_cuts_values():
    keys = np.array([1, 2], np.uint64)
    vals = np.array([(1 << 40) + 5, 7], np.uint64)
    f, v, _ = reference.ReferenceStore(keys, vals, value_bits=32).get_batch(keys)
    assert list(v) == [5, 7]

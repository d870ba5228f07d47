"""The memtable as immutable sorted runs (delta → sealed → base): small
batches splice into the delta under the small lock, a writer folds a full
delta into the base outside it, and every read path resolves the runs
newest-wins. Checked against the dict model with folds engaged, across a
flush that drains all three runs, for a fold a flush overtakes, and under
concurrent writers and readers."""
import threading

import numpy as np

from model import ReferenceStore
from repro.storage import LsmStore
from repro.storage import lsm_store
from repro.storage.lsm_store import DELTA_ROWS

U64 = np.uint64


def _keys(n: int, seed: int) -> np.ndarray:
    """``n`` distinct keys spread over the whole uint64 range."""
    rng = np.random.default_rng(seed)
    return np.unique(rng.integers(0, 2**64 - 1, 2 * n, dtype=U64,
                                  endpoint=True))[:n][rng.permutation(n)]


def _store() -> LsmStore:
    # no filters and no flush: the memtable alone answers every read
    return LsmStore(filter_kind="none", memtable_capacity=10**9)


def _assert_matches(store, model, probe: np.ndarray, window: tuple) -> None:
    found, vals, reads = store.get_batch(probe)
    mf, mv = model.get_batch(probe)
    np.testing.assert_array_equal(found, mf)
    np.testing.assert_array_equal(vals, mv)
    assert not reads.any()
    for got, want in zip(store.scan(*window), model.scan(*window)):
        np.testing.assert_array_equal(got, want)
    assert store.key_count == len(model)


def test_runs_match_the_dict_model_with_folds_engaged():
    store, model = _store(), ReferenceStore()
    rng = np.random.default_rng(15)
    universe = _keys(60_000, 15)
    loaded = universe[:20_000]
    store.put_batch(loaded, loaded)                    # bulk: into the base
    model.put_batch(loaded, loaded)
    touched = set(loaded.tolist())                     # keys with a record
    snaps = [(store.snapshot(), model.snapshot())]     # before any fold
    nxt, folded_at = 20_000, None
    for step in range(400):
        if step % 4 == 3:
            # deletes: tombstones over base rows, delta rows and absent keys
            ks = rng.choice(universe[:nxt + 500], 96, replace=False)
            store.delete_batch(ks)
            model.delete_batch(ks)
        else:
            new = universe[nxt:nxt + 100]
            nxt += 100
            ks = np.concatenate([new, rng.choice(universe[:nxt], 28)])
            vs = rng.integers(1, 2**63, len(ks), dtype=U64)
            store.put_batch(ks, vs)
            model.put_batch(ks, vs)
        touched.update(ks.tolist())
        assert store.pressure["write_queue_depth"] == len(touched)
        if step % 50 == 49:
            probe = rng.choice(universe, 512, replace=False)
            lo = int(rng.integers(0, 2**63))
            _assert_matches(store, model, probe, (lo, lo + 2**61))
            assert dict(store.memtable) == model._data
        if folded_at is None and store.stats.memtable_folds:
            folded_at = step
        if step == (folded_at or -9) + 5:           # a fold, then a delta
            snaps.append((store.snapshot(), model.snapshot()))
    assert store.stats.memtable_folds >= 2 and len(snaps) == 2
    assert store.memtable_len == len(touched) > 2 * DELTA_ROWS
    _assert_matches(store, model, universe, (0, 2**64))
    for snap, msnap in snaps:
        f, v, _ = snap.get_batch(universe)
        mf, mv = msnap.get_batch(universe)
        np.testing.assert_array_equal(f, mf)
        np.testing.assert_array_equal(v, mv)
        for got, want in zip(snap.scan(0, 2**64), msnap.scan(0, 2**64)):
            np.testing.assert_array_equal(got, want)
        snap.close()


def _during_fold(monkeypatch, store, action) -> None:
    """Run ``action`` once inside the next fold, after its merge and before
    its swap, while the sealed delta is still in place."""
    real, ran = lsm_store._merge_runs, []

    def merge(new, old):
        out = real(new, old)
        if not ran and new is store._sealed:           # the fold's merge
            ran.append(True)
            action()
        return out

    monkeypatch.setattr(lsm_store, "_merge_runs", merge)


def _fill_to_a_fold(store, keys, start: int, value: int) -> int:
    """Write 128-key batches of ``keys[start:]`` until a fold has run;
    returns the index after the last key written."""
    folds = store.stats.memtable_folds
    while store.stats.memtable_folds == folds:
        store.put_batch(keys[start:start + 128], np.full(128, value, U64))
        start += 128
    return start


def test_flush_drains_all_three_runs_sorted_and_deduplicated(monkeypatch):
    store = _store()
    keys = _keys(40_000, 16)
    store.put_batch(keys[:20_000], np.full(20_000, 1, U64))    # the base
    over = np.concatenate([keys[:64], keys[20_000:20_064], keys[-64:]])
    runs, seen = [], []

    def overwrite_then_flush():
        store.put_batch(over, np.full(len(over), 3, U64))     # the delta
        runs.extend((store._delta, store._sealed, store._base))
        seen.append(store.get_batch(keys)[:2])
        seen.append(store.scan(0, 2**64))
        store.flush()

    _during_fold(monkeypatch, store, overwrite_then_flush)
    end = _fill_to_a_fold(store, keys, 20_000, 2)             # the sealed
    assert len(runs) == 3 and all(len(r[0]) for r in runs)
    assert store.memtable_len == 0 and store.n_tables == 1
    t = store.sstables[0]
    assert (t.keys[1:] > t.keys[:-1]).all()
    want = {int(k): 1 for k in keys[:20_000]}
    want.update((int(k), 2) for k in keys[20_000:end])
    want.update((int(k), 3) for k in over)
    assert dict(zip(t.keys.tolist(), t.vals.tolist())) == want
    # reads over the three runs, before the flush, saw the same records
    (found, vals), (sk, sv) = seen
    assert dict(zip(keys[found].tolist(), vals[found].tolist())) == want
    assert dict(zip(sk.tolist(), sv.tolist())) == want


def test_fold_whose_runs_a_flush_drained_is_dropped(monkeypatch):
    store, model = _store(), ReferenceStore()
    keys = _keys(40_000, 17)
    store.put_batch(keys[:20_000], keys[:20_000])

    def flush_then_delete():
        store.flush()                     # drains the fold's runs
        store.delete_batch(keys[:64])     # a newer base the fold must keep

    _during_fold(monkeypatch, store, flush_then_delete)
    end = _fill_to_a_fold(store, keys, 20_000, 5)
    monkeypatch.undo()
    assert store.stats.memtable_folds == 1 and store.n_tables == 1
    assert store.memtable_len == 64 and store._sealed is None  # dropped
    store.put_batch(keys[end:end + 128], keys[end:end + 128])
    store.flush()
    model.put_batch(keys[:20_000], keys[:20_000])
    model.put_batch(keys[20_000:end], np.full(end - 20_000, 5, U64))
    model.delete_batch(keys[:64])
    model.put_batch(keys[end:end + 128], keys[end:end + 128])
    assert store.key_count == len(model)
    got = store.scan(0, 2**64)
    for a, b in zip(got, model.scan(0, 2**64)):
        np.testing.assert_array_equal(a, b)


def test_writers_keep_splicing_while_a_fold_runs(monkeypatch):
    """Writes that land while a fold runs splice into a fresh delta, past
    ``DELTA_ROWS`` rows, and seal no second run; the next write after the
    fold seals that delta, and every record stays readable."""
    store, model = _store(), ReferenceStore()
    keys = _keys(60_000, 19)
    store.put_batch(keys[:20_000], keys[:20_000])
    model.put_batch(keys[:20_000], keys[:20_000])
    during = keys[40_000:40_000 + DELTA_ROWS + 1024]
    sealed = []

    def write_a_delta_and_more():
        sealed.append(store._sealed)
        for a in range(0, len(during), 128):
            store.put_batch(during[a:a + 128], during[a:a + 128])
        assert store._sealed is sealed[0]
        assert len(store._delta[0]) == len(during) > DELTA_ROWS

    _during_fold(monkeypatch, store, write_a_delta_and_more)
    end = _fill_to_a_fold(store, keys, 20_000, 6)
    monkeypatch.undo()
    model.put_batch(keys[20_000:end], np.full(end - 20_000, 6, U64))
    model.put_batch(during, during)
    assert store.stats.memtable_folds == 1 and store._sealed is None
    _assert_matches(store, model, keys, (0, 2**64))
    store.put_batch(keys[end:end + 128], keys[end:end + 128])
    model.put_batch(keys[end:end + 128], keys[end:end + 128])
    assert store.stats.memtable_folds == 2 and not len(store._delta[0])
    _assert_matches(store, model, keys, (0, 2**64))


def test_concurrent_reads_see_every_acknowledged_write():
    """4 writers, each owning a quarter of the keys, overwrite them with
    rising versions while 4 readers read: a read returns, for each key, a
    version no older than the newest one acknowledged before it began."""
    store = _store()
    keys = _keys(32_000, 18)
    idx = np.arange(len(keys), dtype=U64)
    store.put_batch(keys, idx + (U64(1) << U64(32)))   # version 1, the base
    acked = np.ones(len(keys), dtype=np.int64)
    done, errors, reads = threading.Event(), [], []

    def writer(w: int) -> None:
        own = np.arange(w, len(keys), 4)
        try:
            for version in range(2, 5):
                for a in range(0, len(own), 128):
                    sel = own[a:a + 128]
                    store.put_batch(keys[sel],
                                    idx[sel] + (U64(version) << U64(32)))
                    acked[sel] = version
        except Exception as exc:                       # reported below
            errors.append(exc)

    def reader(r: int) -> None:
        rng = np.random.default_rng(r)
        try:
            while not done.is_set():
                sel = rng.choice(len(keys), 128, replace=False)
                before = acked[sel].copy()
                found, vals, _ = store.get_batch(keys[sel])
                assert found.all()
                assert ((vals & U64(2**32 - 1)) == idx[sel]).all()
                assert ((vals >> U64(32)).astype(np.int64) >= before).all()
                reads.append(r)
        except Exception as exc:                       # reported below
            errors.append(exc)

    writers = [threading.Thread(target=writer, args=(w,)) for w in range(4)]
    readers = [threading.Thread(target=reader, args=(r,)) for r in range(4)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    done.set()
    for t in readers:
        t.join()
    assert not errors, errors
    assert len(set(reads)) == 4
    assert store.stats.memtable_folds >= 3
    assert store.memtable_len == len(keys)
    found, vals, _ = store.get_batch(keys)
    assert found.all()
    np.testing.assert_array_equal(vals, idx + (U64(4) << U64(32)))

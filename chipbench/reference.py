"""The plain reference: a sorted-array key-value store, and the check that
holds the store under test to it.

``ReferenceStore`` answers ``get_batch`` and ``put_batch`` from the
loaded records (one sorted array) and the writes made since (a second,
small sorted array), under one lock: no memtable, no tables, no filters.
It shares no code with the program. ``value_bits`` below 64 keeps only
the low bits of each value: that is the control, which breaks the
guarantee that a read returns the value that was written.

``check_reads`` holds point answers to the guarantees a single-node store
gives (read-your-writes, newest write wins, exact answers) under
concurrency: a read of key ``k`` that ran over ``[s, e]`` may return the
value of a write to ``k`` that began before ``e`` and was not overwritten
by another write that ended before ``s``, or the loaded value if no write
to ``k`` ended before ``s``; it may say "absent" only where neither the
load nor such a write holds ``k``. Times are the clients' own, taken
before each call and after it returned, so each interval contains the
moment the store applied the operation.
"""
from __future__ import annotations

import threading

import numpy as np

from chipbench import ycsb

U64 = np.uint64
_BIG = np.int64(1 << 40)          # > any time in ns since the window opened


class ReferenceStore:
    """Sorted-array store with the calls the harness makes of a store."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray, value_bits: int = 64):
        order = np.argsort(keys, kind="stable")
        self.keys = np.asarray(keys, dtype=U64)[order]
        self.vals = np.asarray(vals, dtype=U64)[order]
        self._mask = U64((1 << value_bits) - 1)
        self._new_k = np.empty(0, dtype=U64)
        self._new_v = np.empty(0, dtype=U64)
        self._lock = threading.Lock()
        self.vals = self.vals & self._mask
        if len(self.keys) > 1 and not (np.diff(self.keys) > 0).all():
            raise ValueError("loaded keys must be distinct")

    @staticmethod
    def _find(sorted_keys: np.ndarray, q: np.ndarray):
        if not len(sorted_keys):
            return np.zeros(len(q), dtype=bool), np.zeros(len(q), dtype=np.int64)
        pos = np.minimum(np.searchsorted(sorted_keys, q), len(sorted_keys) - 1)
        return sorted_keys[pos] == q, pos

    def get_batch(self, keys: np.ndarray):
        """(found bool [n], values uint64 [n], reads int32 [n])."""
        keys = np.asarray(keys, dtype=U64)
        hit, pos = self._find(self.keys, keys)
        vals = np.where(hit, self.vals[pos], U64(0))
        with self._lock:
            nk, nv = self._new_k, self._new_v
        nhit, npos = self._find(nk, keys)
        if nhit.any():
            vals[nhit] = nv[npos[nhit]]
        return hit | nhit, vals, np.zeros(len(keys), dtype=np.int32)

    def put_batch(self, keys: np.ndarray, vals: np.ndarray) -> None:
        """Upsert; within a batch the last write of a key wins."""
        keys = np.asarray(keys, dtype=U64)
        vals = np.asarray(vals, dtype=U64) & self._mask
        uk, last = np.unique(keys[::-1], return_index=True)
        uv = vals[::-1][last]
        hit, pos = self._find(self.keys, uk)
        with self._lock:
            self.vals[pos[hit]] = uv[hit]
            k = np.concatenate([uk[~hit], self._new_k])
            v = np.concatenate([uv[~hit], self._new_v])
            k, first = np.unique(k, return_index=True)
            self._new_k, self._new_v = k, v[first]


class WriteLog:
    """Every write the clients made: key, value, and the client's start and
    end times (ns since the window opened), indexed for ``check_reads``."""

    def __init__(self, keys, vals, starts, ends):
        self.keys = np.asarray(keys, dtype=U64)
        self.vals = np.asarray(vals, dtype=U64)
        self.starts = np.asarray(starts, dtype=np.int64)
        self.ends = np.asarray(ends, dtype=np.int64)
        ids = ycsb.decode_write(self.vals)
        if (ids < 0).any():
            raise ValueError("a logged write has no write id")
        self._by_id = np.argsort(ids, kind="stable")
        self._ids = ids[self._by_id]
        # group id per distinct key; writes ordered by (key, end) with the
        # running maximum of start times inside each key's group
        self.group_keys, gid = np.unique(self.keys, return_inverse=True)
        comp = gid.astype(np.int64) * _BIG + self.ends
        order = np.argsort(comp, kind="stable")
        self._comp = comp[order]
        self._gid = gid[order]
        self._max_start = np.maximum.accumulate(
            self.starts[order] + gid[order].astype(np.int64) * _BIG
        ) - gid[order].astype(np.int64) * _BIG

    def __len__(self) -> int:
        return len(self.keys)

    def last_overwrite_start(self, keys, starts):
        """Per read: the latest start among writes to its key that ended
        before the read began (-1 where none did)."""
        out = np.full(len(keys), -1, dtype=np.int64)
        if not len(self.keys):
            return out
        g = np.searchsorted(self.group_keys, keys)
        g_c = np.minimum(g, len(self.group_keys) - 1)
        has = self.group_keys[g_c] == keys
        idx = np.searchsorted(self._comp, g_c.astype(np.int64) * _BIG + starts,
                              side="left") - 1
        ok = has & (idx >= 0)
        ok[ok] &= self._gid[idx[ok]] == g_c[ok]
        out[ok] = self._max_start[idx[ok]]
        return out

    def lookup(self, write_ids):
        """Index into the log of each write id (-1 where unknown)."""
        if not len(self._ids):
            return np.full(len(write_ids), -1, dtype=np.int64)
        pos = np.minimum(np.searchsorted(self._ids, write_ids), len(self._ids) - 1)
        found = self._ids[pos] == write_ids
        return np.where(found, self._by_id[pos], -1)


def check_reads(base: ReferenceStore, log: WriteLog, keys, starts, ends,
                found, vals) -> np.ndarray:
    """bool [n]: which point answers break the guarantees (see the module
    docstring). ``starts``/``ends`` are each read's client times."""
    keys = np.asarray(keys, dtype=U64)
    starts = np.asarray(starts, dtype=np.int64)
    ends = np.asarray(ends, dtype=np.int64)
    found = np.asarray(found, dtype=bool)
    vals = np.asarray(vals, dtype=U64)
    in_base, pos = base._find(base.keys, keys)
    base_val = np.where(in_base, base.vals[pos], U64(0))
    prior = log.last_overwrite_start(keys, starts)
    overwritten = prior >= 0
    wid = ycsb.decode_write(vals)
    from_write = wid >= 0
    ok = np.zeros(len(keys), dtype=bool)
    # "absent": only where neither the load nor a finished write holds it
    ok |= ~found & ~in_base & ~overwritten
    # the loaded value, while no write to the key has finished
    ok |= found & ~from_write & in_base & ~overwritten & (vals == base_val)
    # a write's value: the same key, begun before the read ended, and not
    # overwritten by a write that ended before the read began
    w = np.full(len(keys), -1, dtype=np.int64)
    w[from_write] = log.lookup(wid[from_write])
    known = found & (w >= 0)
    wk = np.where(known, w, 0)
    if len(log):
        ok |= (known & (log.keys[wk] == keys) & (log.starts[wk] < ends)
               & (log.ends[wk] >= prior))
    return ~ok

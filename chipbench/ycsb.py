"""YCSB core-workload traffic, drawn in bulk from the run's seed.

Records are numbered ``0 .. n-1`` in load order; record ``i`` has the key
``record_keys(i, seed)``, a bijective 64-bit mix of the record number, so
distinct records have distinct keys and the load order is hashed across
the key space (YCSB's ``insertorder=hashed``). Keys and values are 8-byte
integers. Loaded values have their top bit clear; a value written in the
window has it set and names the write (``write_value``), so every answer
a read gives can be traced to the write that produced it.

Key popularity follows YCSB's scrambled Zipfian: a popularity rank is
drawn from Zipf(``theta``) over the ``n`` loaded records, by a precomputed
CDF and ``searchsorted``, and the rank is hashed with YCSB's 64-bit
FNV-1a (``Utils.fnvhash64``) onto a record, so hot keys fall across the
whole key space and in every table of the store.

A traffic file (``chipbench/traffic/<name>.json``) gives the request mix;
``draw_pool`` turns it into a fixed pool of distinct requests before the
window opens, ``pool_requests_per_s`` for each second of the window.
Clients take requests from the pool in order. A window that used the
whole pool would start over from its head and replay requests, so the
harness refuses such a run (the ``pool_wraps`` check).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

U64 = np.uint64
MASK64 = (1 << 64) - 1
FNV_OFFSET = 0xCBF29CE484222325
FNV_PRIME = 1099511628211
TOP = U64(1 << 63)
OP_BITS = 7                       # write_value: op index inside a request
KINDS = ("read", "update")


def splitmix64(x: np.ndarray) -> np.ndarray:
    """SplitMix64's finaliser (a bijection of uint64; arithmetic wraps)."""
    with np.errstate(over="ignore"):
        z = np.asarray(x, dtype=U64) + U64(0x9E3779B97F4A7C15)
        z = (z ^ (z >> U64(30))) * U64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> U64(27))) * U64(0x94D049BB133111EB)
        return z ^ (z >> U64(31))


def fnv1a64(x: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1a over the 8 bytes of each value,
    low byte first."""
    x = np.asarray(x, dtype=U64)
    h = np.full(x.shape, FNV_OFFSET, dtype=U64)
    with np.errstate(over="ignore"):
        for i in range(8):
            h = (h ^ ((x >> U64(8 * i)) & U64(0xFF))) * U64(FNV_PRIME)
    return h


def _seed_word(seed: int, salt: int) -> np.uint64:
    return splitmix64(np.array([(seed ^ salt) & MASK64], dtype=U64))[0]


def record_keys(idx: np.ndarray, seed: int) -> np.ndarray:
    """The uint64 key of each record number (injective for a fixed seed)."""
    with np.errstate(over="ignore"):
        return splitmix64(np.asarray(idx, dtype=U64) + _seed_word(seed, 0x6B6579))


def load_values(keys: np.ndarray, seed: int) -> np.ndarray:
    """The value each record is loaded with: top bit clear."""
    return splitmix64(keys ^ _seed_word(seed, 0x76616C)) & ~TOP


def write_value(request: int, ops: int) -> np.ndarray:
    """The values of request ``request``'s ``ops`` writes: top bit set,
    then the request number and the op's index inside it."""
    return TOP | (U64(request) << U64(OP_BITS)) | np.arange(ops, dtype=U64)


def decode_write(values: np.ndarray) -> np.ndarray:
    """Inverse of ``write_value``: the write id ``request << OP_BITS | op``,
    or -1 for a value that no write in the window made."""
    values = np.asarray(values, dtype=U64)
    ids = (values & ~TOP).astype(np.int64)
    return np.where((values & TOP) != 0, ids, -1)


class Zipfian:
    """Zipf(theta) ranks over ``n`` items by inverse CDF."""

    def __init__(self, n: int, theta: float):
        self.n = int(n)
        w = np.arange(1, self.n + 1, dtype=np.float64) ** -float(theta)
        self.cdf = np.cumsum(w)
        self.cdf /= self.cdf[-1]

    def ranks(self, rng: np.random.Generator, size: int) -> np.ndarray:
        """``size`` i.i.d. ranks. The uniforms are searched in sorted order
        (cache-friendly) and then shuffled, which leaves the sample i.i.d."""
        u = np.sort(rng.random(size))
        r = np.minimum(np.searchsorted(self.cdf, u, side="right"), self.n - 1)
        rng.shuffle(r)
        return r.astype(np.int64)


def scrambled(ranks: np.ndarray, n: int) -> np.ndarray:
    """YCSB's ScrambledZipfian step: hash a popularity rank onto a record."""
    return (fnv1a64(ranks) % U64(n)).astype(np.int64)


@dataclass
class Pool:
    """A fixed list of distinct requests. ``kind[i]`` indexes ``KINDS``;
    ``row[i]`` is the request's row in ``keys[kind]`` (uint64 [rows, ops])."""

    kind: np.ndarray
    row: np.ndarray
    keys: dict
    ops: dict

    def __len__(self) -> int:
        return len(self.kind)


def draw_pool(traffic: dict, n_records: int, seed: int, seconds: float) -> Pool:
    """Draw the window's requests from ``seed``: as many as
    ``traffic['pool_requests_per_s'] * seconds``, each of a kind chosen by
    the traffic's request shares (``mix``)."""
    rng = np.random.default_rng([seed & MASK64, 0x706F6F6C])
    mix = traffic["mix"]
    if set(mix) - set(KINDS):
        raise ValueError(f"request kinds must be among {KINDS}, got {sorted(mix)}")
    names = [k for k in KINDS if mix.get(k, 0) > 0]
    shares = np.array([mix[k] for k in names], dtype=np.float64)
    if not math.isclose(shares.sum(), 1.0, abs_tol=1e-9):
        raise ValueError(f"request shares must sum to 1, got {shares.sum()}")
    n_req = max(1, int(math.ceil(traffic["pool_requests_per_s"] * seconds)))
    pick = np.searchsorted(np.cumsum(shares), rng.random(n_req), side="right")
    pick = np.minimum(pick, len(names) - 1)
    kind = np.array([KINDS.index(k) for k in names], dtype=np.int8)[pick]
    ops = {k: int(traffic["ops"][k]) for k in names}
    zipf = Zipfian(n_records, traffic["zipfian_constant"])
    # the key of each popularity rank, made once: rank -> record -> key
    rank_key = record_keys(scrambled(np.arange(n_records), n_records), seed)
    row = np.zeros(n_req, dtype=np.int64)
    keys: dict = {}
    for k in names:
        sel = np.flatnonzero(kind == KINDS.index(k))
        row[sel] = np.arange(len(sel))
        ranks = zipf.ranks(rng, len(sel) * ops[k])
        keys[k] = rank_key[ranks].reshape(len(sel), ops[k])
    return Pool(kind=kind, row=row, keys=keys, ops=ops)

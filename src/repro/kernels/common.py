"""Shared plumbing for the filter probe hot path.

Every probe is one jitted XLA program. The packed FilterBank buffer
(core.tables) stays in device memory (HBM on a TPU) and each probe gathers
the few words a key needs from it; query keys travel as (hi, lo) uint32
lanes padded to whole (8, 128) tiles. A store's bank grows with its key
count (tens of MiB at 10^7 keys), so it is not pinned in VMEM: the TPU
compiler refuses a whole-bank VMEM block of 16 MiB, and refuses a vector
gather from a multi-word VMEM table inside a Pallas kernel.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import hashing as H
from repro.core.tables import pad_words

# (sublane, lane) tile of the TPU VPU for 32-bit elements
BLOCK_ROWS = 8
BLOCK_COLS = 128
BLOCK = BLOCK_ROWS * BLOCK_COLS


# ---------------------------------------------------------------------------
# packed-table lookups — shared by every probe program
#
# All helpers take a word ``offset`` into a packed FilterBank buffer
# (core.tables), so N heterogeneous filters can live in ONE device-resident
# uint32 array and each probe gathers from its own slice. offset=0 recovers
# the single-filter case.
# ---------------------------------------------------------------------------

def bloom_hit(words, hi, lo, *, m_bits: int, k: int, seed: int,
              offset: int = 0):
    """Bloom membership over a packed word buffer -> bool, shape of (hi, lo)."""
    out = jnp.ones(hi.shape, dtype=bool)
    for i in range(k):  # static unroll: k is small (≤ 16)
        idx = H.jx_hash_to_range(hi, lo, seed * 1000 + i, m_bits)
        w = jnp.take(words, offset + (idx >> 5), axis=0)
        out &= ((w >> (idx & 31).astype(jnp.uint32)) & 1) == 1
    return out


def xor_slots(hi, lo, *, mode: str, seed: int, seg_len: int, n_seg: int,
              offset: int = 0):
    """The three Bloomier slot indices (uniform or fuse layout), pre-offset."""
    if mode == "uniform":
        return tuple(offset + i * seg_len
                     + H.jx_hash_to_range(hi, lo, seed * 7919 + i, seg_len)
                     for i in range(3))
    start = H.jx_hash_to_range(hi, lo, seed * 7919 + 3, n_seg - 2)
    return tuple(offset + (start + i) * seg_len
                 + H.jx_hash_to_range(hi, lo, seed * 7919 + i, seg_len)
                 for i in range(3))


def xor_lookup(table, hi, lo, *, mode: str, seed: int, seg_len: int,
               n_seg: int, alpha: int, offset: int = 0):
    """BloomierTable.lookup over a packed buffer -> α-bit uint32 values."""
    s0, s1, s2 = xor_slots(hi, lo, mode=mode, seed=seed, seg_len=seg_len,
                           n_seg=n_seg, offset=offset)
    v = (jnp.take(table, s0, axis=0) ^ jnp.take(table, s1, axis=0)
         ^ jnp.take(table, s2, axis=0))
    return v & jnp.uint32((1 << alpha) - 1)


def pad_table(table: np.ndarray, multiple: int = BLOCK_COLS) -> np.ndarray:
    return pad_words(table, multiple)


def blockify(hi: np.ndarray, lo: np.ndarray):
    """Pad key lanes to a whole number of (8,128) blocks; returns
    (hi2d, lo2d, n_valid)."""
    n = len(hi)
    pad = (-n) % BLOCK
    if pad:
        z = np.zeros(pad, dtype=np.uint32)
        hi = np.concatenate([np.asarray(hi, np.uint32), z])
        lo = np.concatenate([np.asarray(lo, np.uint32), z])
    rows = len(hi) // BLOCK_COLS
    return (np.asarray(hi, np.uint32).reshape(rows, BLOCK_COLS),
            np.asarray(lo, np.uint32).reshape(rows, BLOCK_COLS), n)


def unblockify(out2d: jnp.ndarray, n_valid: int) -> jnp.ndarray:
    return out2d.reshape(-1)[:n_valid]

"""Bloomier/XOR-filter probe (3 gathers + XOR + compare): jitted XLA.

Covers both the approximate (α-bit fingerprint) and exact (1-bit, strategy
a/b) Bloomier variants — the exact case is the α=1 path with the fingerprint
replaced by the strategy bit. The slot/lookup math lives in common.py
(shared with the fused chained and cascade probes) and takes a static
``offset`` so the table may be a slice of a packed FilterBank buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hashing as H
from .common import xor_lookup


@functools.partial(jax.jit, static_argnames=("mode", "seed", "seg_len", "n_seg",
                                             "alpha", "fp_seed", "offset"))
def xor_probe(table, hi2d, lo2d, *, mode: str, seed: int, seg_len: int,
              n_seg: int, alpha: int, fp_seed: int, offset: int = 0):
    """-> int32 [R, 128] (1 = fingerprint match)."""
    v = xor_lookup(table, hi2d, lo2d, mode=mode, seed=seed, seg_len=seg_len,
                   n_seg=n_seg, alpha=alpha, offset=offset)
    fp = H.jx_hash_u32(hi2d, lo2d, fp_seed) & jnp.uint32((1 << alpha) - 1)
    return (v == fp).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("mode", "seed", "seg_len", "n_seg",
                                             "strategy", "bit_seed", "offset"))
def exact_probe(table, hi2d, lo2d, *, mode: str, seed: int, seg_len: int,
                n_seg: int, strategy: str, bit_seed: int, offset: int = 0):
    """-> int32 [R, 128] (1 = member of the exact Bloomier's positive set)."""
    v = xor_lookup(table, hi2d, lo2d, mode=mode, seed=seed, seg_len=seg_len,
                   n_seg=n_seg, alpha=1, offset=offset)
    if strategy == "a":
        tgt = H.jx_hash_u32(hi2d, lo2d, bit_seed) & jnp.uint32(1)
    else:
        tgt = jnp.uint32(1)
    return (v == tgt).astype(jnp.int32)

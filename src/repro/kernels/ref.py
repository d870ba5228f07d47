"""Pure-jnp oracles for every filter probe. These are the ground truth the
fused probe programs in this package must match bit-for-bit across
shape/dtype sweeps (tests/test_kernels.py). They are written per filter,
on unpacked tables and without offsets, independently of the fused code."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import hashing as H


def bloom_probe_ref(words: jnp.ndarray, hi: jnp.ndarray, lo: jnp.ndarray,
                    *, m_bits: int, k: int, seed: int) -> jnp.ndarray:
    """Bloom query oracle -> bool, any shape of (hi, lo)."""
    out = jnp.ones(hi.shape, dtype=bool)
    for i in range(k):
        idx = H.jx_hash_to_range(hi, lo, seed * 1000 + i, m_bits)
        w = jnp.take(words, idx >> 5, axis=0)
        out &= ((w >> (idx & 31).astype(jnp.uint32)) & 1) == 1
    return out


def _slots(hi, lo, *, mode: str, seed: int, seg_len: int, n_seg: int):
    s = seed
    if mode == "uniform":
        return tuple(i * seg_len + H.jx_hash_to_range(hi, lo, s * 7919 + i, seg_len)
                     for i in range(3))
    start = H.jx_hash_to_range(hi, lo, s * 7919 + 3, n_seg - 2)
    return tuple((start + i) * seg_len + H.jx_hash_to_range(hi, lo, s * 7919 + i, seg_len)
                 for i in range(3))


def xor_lookup_ref(table: jnp.ndarray, hi, lo, *, mode: str, seed: int,
                   seg_len: int, n_seg: int, alpha: int) -> jnp.ndarray:
    """BloomierTable.lookup oracle -> alpha-bit uint32 values."""
    s0, s1, s2 = _slots(hi, lo, mode=mode, seed=seed, seg_len=seg_len, n_seg=n_seg)
    v = jnp.take(table, s0, axis=0) ^ jnp.take(table, s1, axis=0) ^ jnp.take(table, s2, axis=0)
    return v & jnp.uint32((1 << alpha) - 1)


def xor_probe_ref(table: jnp.ndarray, hi, lo, *, mode: str, seed: int,
                  seg_len: int, n_seg: int, alpha: int, fp_seed: int) -> jnp.ndarray:
    """XorFilter.query oracle -> bool."""
    v = xor_lookup_ref(table, hi, lo, mode=mode, seed=seed, seg_len=seg_len,
                       n_seg=n_seg, alpha=alpha)
    fp = H.jx_hash_u32(hi, lo, fp_seed) & jnp.uint32((1 << alpha) - 1)
    return v == fp


def exact_bloomier_ref(table: jnp.ndarray, hi, lo, *, mode: str, seed: int,
                       seg_len: int, n_seg: int, strategy: str,
                       bit_seed: int) -> jnp.ndarray:
    got = xor_lookup_ref(table, hi, lo, mode=mode, seed=seed, seg_len=seg_len,
                         n_seg=n_seg, alpha=1)
    if strategy == "a":
        h1b = H.jx_hash_u32(hi, lo, bit_seed) & jnp.uint32(1)
        return got == h1b
    return got == jnp.uint32(1)


def chained_probe_ref(t1: jnp.ndarray, t2: jnp.ndarray, hi, lo, *,
                      l1: dict, l2: dict, alpha: int, fp_seed: int,
                      strategy: str, bit_seed: int) -> jnp.ndarray:
    """Fused ChainedFilterAnd.query oracle: stage1 & stage2."""
    s1 = xor_probe_ref(t1, hi, lo, alpha=alpha, fp_seed=fp_seed, **l1)
    s2 = exact_bloomier_ref(t2, hi, lo, strategy=strategy, bit_seed=bit_seed, **l2)
    return s1 & s2


def cascade_probe_ref(layer_words: list, layer_params: list, hi, lo) -> jnp.ndarray:
    """ChainedFilterCascade.query oracle: first-zero-layer parity."""
    L = len(layer_words)
    qs = [bloom_probe_ref(layer_words[i], hi, lo, **layer_params[i]) for i in range(L)]
    q = jnp.stack(qs, axis=-1)
    idx = jnp.where(~q, jnp.arange(1, L + 1), L + 1)
    first_zero = idx.min(axis=-1)
    member = first_zero % 2 == 0
    return jnp.where(first_zero == L + 1, (L % 2 == 1), member)

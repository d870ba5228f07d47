"""TPU-native 32-bit lane hashing for membership filters.

The paper uses MurmurHash3 over 64-bit keys on a Xeon. The TPU VPU has no
64-bit integer lanes, so keys are carried as two uint32 lanes ``(hi, lo)``
and mixed with murmur3-style fmix32 avalanche steps. Range reduction uses
Lemire "fastrange" built from 16-bit partial products (``mulhi32``) because
there is no 32x32→64 widening multiply either.

Every function has twin implementations: ``numpy`` (host, used for filter
*construction*) and ``jax.numpy`` (device, used for *query* paths and as the
reference for the fused device probes). Both wrap modulo 2^32 silently.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

U32 = np.uint32
_FMIX_C1 = 0x85EB_CA6B
_FMIX_C2 = 0xC2B2_AE35
_GOLDEN = 0x9E37_79B9


# ---------------------------------------------------------------------------
# numpy (host / construction) path
# ---------------------------------------------------------------------------

def np_split_u64(keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """uint64 keys -> (hi, lo) uint32 lanes."""
    keys = np.asarray(keys, dtype=np.uint64)
    lo = (keys & np.uint64(0xFFFF_FFFF)).astype(U32)
    hi = (keys >> np.uint64(32)).astype(U32)
    return hi, lo


def np_fmix32(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, dtype=U32)
    with np.errstate(over="ignore"):
        x ^= x >> U32(16)
        x = (x * U32(_FMIX_C1)) & U32(0xFFFF_FFFF)
        x ^= x >> U32(13)
        x = (x * U32(_FMIX_C2)) & U32(0xFFFF_FFFF)
        x ^= x >> U32(16)
    return x


def np_hash_u32(hi: np.ndarray, lo: np.ndarray, seed: int) -> np.ndarray:
    """Avalanche hash of a (hi, lo) key pair with a seed; returns uint32."""
    with np.errstate(over="ignore"):
        h = np_fmix32(lo ^ U32(seed & 0xFFFF_FFFF))
        h = np_fmix32(h ^ hi ^ (U32(seed & 0xFFFF_FFFF) * U32(_GOLDEN)))
    return h


def np_fastrange(h: np.ndarray, n: int) -> np.ndarray:
    """Map uint32 hash uniformly onto [0, n) via the 64-bit trick (host has
    real uint64 so no partial products needed)."""
    return ((h.astype(np.uint64) * np.uint64(n)) >> np.uint64(32)).astype(np.int64)


def np_hash_to_range(hi, lo, seed: int, n: int) -> np.ndarray:
    return np_fastrange(np_hash_u32(hi, lo, seed), n)


# ---------------------------------------------------------------------------
# jax (device / query) path — must mirror numpy bit-for-bit
# ---------------------------------------------------------------------------

def jx_fmix32(x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_FMIX_C1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_FMIX_C2)
    x = x ^ (x >> 16)
    return x


def jx_hash_u32(hi: jnp.ndarray, lo: jnp.ndarray, seed: int) -> jnp.ndarray:
    s = jnp.uint32(seed & 0xFFFF_FFFF)
    h = jx_fmix32(lo.astype(jnp.uint32) ^ s)
    h = jx_fmix32(h ^ hi.astype(jnp.uint32) ^ (s * jnp.uint32(_GOLDEN)))
    return h


def jx_mulhi32(a: jnp.ndarray, b_const: int) -> jnp.ndarray:
    """floor((a * b) / 2^32) for uint32 a and python-int b, via 16-bit
    partial products (no 64-bit lanes on the TPU VPU)."""
    a = a.astype(jnp.uint32)
    b = int(b_const) & 0xFFFF_FFFF
    a_lo = a & jnp.uint32(0xFFFF)
    a_hi = a >> 16
    b_lo = jnp.uint32(b & 0xFFFF)
    b_hi = jnp.uint32(b >> 16)
    ll = a_lo * b_lo
    lh = a_lo * b_hi
    hl = a_hi * b_lo
    hh = a_hi * b_hi
    mid = (ll >> 16) + (lh & jnp.uint32(0xFFFF)) + (hl & jnp.uint32(0xFFFF))
    return hh + (lh >> 16) + (hl >> 16) + (mid >> 16)


def jx_fastrange(h: jnp.ndarray, n: int) -> jnp.ndarray:
    return jx_mulhi32(h, n).astype(jnp.int32)


def jx_hash_to_range(hi, lo, seed: int, n: int) -> jnp.ndarray:
    return jx_fastrange(jx_hash_u32(hi, lo, seed), n)


# ---------------------------------------------------------------------------
# key helpers
# ---------------------------------------------------------------------------

def random_keys(n: int, seed: int = 0) -> np.ndarray:
    """n distinct uint64 keys (the paper's '64-bit pre-generated random
    integers')."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 2**64, size=int(n * 1.1) + 16, dtype=np.uint64)
    keys = np.unique(keys)
    while keys.size < n:  # pragma: no cover — astronomically unlikely
        extra = rng.integers(0, 2**64, size=n, dtype=np.uint64)
        keys = np.unique(np.concatenate([keys, extra]))
    return keys[:n]


def keys_to_lanes_jax(keys: np.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    hi, lo = np_split_u64(keys)
    return jnp.asarray(hi), jnp.asarray(lo)

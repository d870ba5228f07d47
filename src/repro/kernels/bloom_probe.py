"""Batched Bloom-filter probe: one jitted XLA program.

All k probes are unrolled — k is small (≤ 16) and static — so the program
is k word gathers from the device-resident word array plus bitwise lane
math, with no scalar loop.

``words`` may be a packed FilterBank buffer (core.tables): the static
``offset`` selects this filter's word slice of the shared buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import bloom_hit


@functools.partial(jax.jit, static_argnames=("m_bits", "k", "seed", "offset"))
def bloom_probe(words: jnp.ndarray, hi2d: jnp.ndarray, lo2d: jnp.ndarray,
                *, m_bits: int, k: int, seed: int, offset: int = 0
                ) -> jnp.ndarray:
    """words: uint32 [W] (W % 128 == 0); hi2d/lo2d: uint32 [R, 128] with
    R % 8 == 0. Returns int32 [R, 128] (1 = maybe-member)."""
    return bloom_hit(words, hi2d, lo2d, m_bits=m_bits, k=k, seed=seed,
                     offset=offset).astype(jnp.int32)

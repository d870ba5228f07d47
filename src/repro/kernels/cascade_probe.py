"""Fused ChainedFilterCascade probe (paper §4 Alg. 2): one jitted XLA program.

``ChainedFilterCascade.query_jax`` probes its Bloom layers one device op at
a time and stacks the results — L·k dispatches plus an [n, L] intermediate.
Here ALL layers are evaluated in one program: the packed layer bitmaps
(core.tables CascadeLayout) are a single device-resident uint32 buffer, the
key lanes are loaded once, and the first-zero-layer parity rule reduces
inside the same fusion. This is the §5.2 'shared address' trick applied
across cascade layers, and it removes exactly the per-probe dispatch
overhead that dominates small-filter latency (Graf & Lemire, *Xor Filters*).

Layer loop is a static unroll: L is small (≤ ~16 for δ=1/2) and fixed by
the layout descriptor. The program also outputs the per-key *sequential
probe count* min(first_zero, L) — how many layers a short-circuiting
querier would touch (§5.3/§5.4 memory-access accounting).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import bloom_hit


@functools.partial(jax.jit, static_argnames=("layers",))
def cascade_probe(words, hi2d, lo2d, *, layers: tuple):
    """words: packed uint32 buffer of all layer bitmaps (W % 128 == 0);
    hi2d/lo2d: uint32 [R, 128], R % 8 == 0; layers: static tuple of
    (m_bits, k, seed, offset) — see CascadeLayout.probe_params().
    Returns (member, probes) int32 [R, 128]."""
    L = len(layers)
    first_zero = jnp.full(hi2d.shape, L + 1, dtype=jnp.int32)
    for i, (m_bits, k, seed, offset) in enumerate(layers):
        hit = bloom_hit(words, hi2d, lo2d, m_bits=m_bits, k=k, seed=seed,
                        offset=offset)
        undecided = first_zero == L + 1
        first_zero = jnp.where((~hit) & undecided, i + 1, first_zero)
    member = first_zero % 2 == 0
    member = jnp.where(first_zero == L + 1, (L % 2 == 1), member)
    return member.astype(jnp.int32), jnp.minimum(first_zero, L)

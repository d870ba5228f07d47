"""Fused ChainedFilterAnd probe (stage1 ∧ stage2): one jitted XLA program.

The CPU reference short-circuits stage 2 for stage-1 rejects; on the device
the branch-free fused form is faster: both tables live in ONE packed
device-resident buffer (core.tables layout, static word offsets), the six
gathers + bitwise reduce cost less than any divergence machinery, and the
key lanes are loaded exactly once (the paper's §5.2 'shared address'
locality trick).

Outputs both membership and the per-key *sequential probe count*
(1 + stage-1 pass: a sequential querier touches stage 2 only when stage 1
fires — the paper's Fig 7b memory-access accounting).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hashing as H
from .common import xor_lookup


@functools.partial(jax.jit, static_argnames=("l1", "l2", "alpha", "fp_seed",
                                             "strategy", "bit_seed"))
def chained_probe(tables, hi2d, lo2d, *, l1: tuple | None, l2: tuple,
                  alpha: int, fp_seed: int, strategy: str, bit_seed: int):
    """tables: packed uint32 buffer holding both stages.
    l1/l2 = (mode, seed, seg_len, n_seg, offset) static layout tuples;
    l1 may be None (degenerate λ: no stage 1).
    Returns (member, probes) int32 [R, 128] pairs."""
    hi, lo = hi2d, lo2d
    if l1 is not None:
        # stage 1: α-bit fingerprint match
        mode1, seed1, seg1, nseg1, off1 = l1
        v1 = xor_lookup(tables, hi, lo, mode=mode1, seed=seed1, seg_len=seg1,
                        n_seg=nseg1, alpha=alpha, offset=off1)
        fp = H.jx_hash_u32(hi, lo, fp_seed) & jnp.uint32((1 << alpha) - 1)
        s1 = v1 == fp
    else:
        s1 = jnp.ones(hi.shape, dtype=bool)    # degenerate: exact stage only
    # stage 2: exact 1-bit Bloomier
    mode2, seed2, seg2, nseg2, off2 = l2
    v2 = xor_lookup(tables, hi, lo, mode=mode2, seed=seed2, seg_len=seg2,
                    n_seg=nseg2, alpha=1, offset=off2)
    if strategy == "a":
        tgt = H.jx_hash_u32(hi, lo, bit_seed) & jnp.uint32(1)
    else:
        tgt = jnp.uint32(1)
    member = (s1 & (v2 == tgt)).astype(jnp.int32)
    if l1 is not None:
        probes = 1 + s1.astype(jnp.int32)
    else:
        probes = jnp.ones(hi.shape, dtype=jnp.int32)
    return member, probes

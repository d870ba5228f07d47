"""Fused multi-SSTable LSM filter probe (paper §5.4): one jitted XLA program.

An LSM point query probes every SSTable's filter newest→oldest and — with
per-table exact ChainedFilters — reads at most ONE table (the first hit;
Fig 11b). The host model does that per key, per table; here ALL tables'
filters are evaluated for the whole key batch in ONE device program: the
per-table chain tables (stage-1 Xor slots + stage-2 Othello bitmaps,
packed by core.tables into a single 128-word-aligned uint32 FilterBank
buffer) stay in device memory and are read by gathers, the key lanes are
loaded exactly once per store — never per table — and the newest-first
first-hit reduction fuses into the same program. This replaces N
per-table dispatches with one, the same §5.2 'shared address' locality
trick the cascade probe applies across Bloom layers, applied across
SSTables.

Per key the probe emits:

- ``first_hit``  int32 — newest-first index of the first table whose filter
  fires, or N when none does. Under the chain rule this is the ONLY table a
  querier reads (≤ 1 wasted read per query).
- ``hits_mask``  int32 — bit t set iff table t's filter fired (N ≤ 32).
  Baseline read policies (per-table Bloom: read EVERY fired table until the
  key is found) are reconstructed from this mask on the host, so chained
  and Bloom stores share one probe path.

``chains`` is a static tuple of tagged per-table descriptors, newest first:

  ('chain', xor_params | None, oth_params)  — two-stage ChainedFilter
      xor_params = (mode, seed, seg_len, n_seg, alpha, fp_seed, offset)
      oth_params = (ma, mb, seed, offset_a, offset_b)
  ('bloom', (m_bits, k, seed, offset))      — per-table Bloom baseline
  ('always',)                               — no filter (always read)

The per-table loop is NOT a scalar unroll: all 'chain' tables sharing a
slot-layout mode are evaluated *vectorized across tables* — per-table
parameters (hash seeds, segment lengths, table sizes, word offsets) travel
as [T, 1, 1] lanes of the packed ``params`` input broadcast against the
[R, 128] key lanes, so every table's slot indices land in ONE [T, R, 128]
gather from the shared bank buffer and the whole chain stack costs one op
sweep instead of T.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import hashing as H
from repro.core.hashing import _GOLDEN
from .common import bloom_hit, xor_lookup

MAX_TABLES = 32     # hits_mask is an int32 bitmask


# ---------------------------------------------------------------------------
# table-vectorized hashing: per-table ints travel as [T, 1, 1] lanes of a
# small packed uint32 params input (run-time values, so a generation's
# frozen lanes are what its probes read); every op below must mirror
# core.hashing bit-for-bit (uint32 wrap).
# ---------------------------------------------------------------------------

_N_FIELDS = 11   # params rows per chain group, see _group_params


def _group_chains(chains: tuple) -> tuple[dict, list]:
    """Partition table indices: vectorizable two-stage chains grouped by
    slot-layout mode, everything else (bloom / always / degenerate chain)
    on the scalar path. Shared by params packing and params slicing so
    field order always agrees."""
    groups: dict[str, list[int]] = {}
    scalar: list[int] = []
    for t, chain in enumerate(chains):
        if chain[0] == "chain" and chain[1] is not None:
            groups.setdefault(chain[1][0], []).append(t)
        else:
            scalar.append(t)
    return groups, scalar


def chain_params_len(chains: tuple) -> int:
    """Length of the packed params vector ``pack_chain_params`` produces for
    ``chains`` (128-word padded) — lets callers validate a precomputed
    params array against a chains tuple without repacking it."""
    groups, _ = _group_chains(chains)
    flat = sum(_N_FIELDS * len(ts) for ts in groups.values())
    return max(128, flat + ((-flat) % 128)) if flat else 128


def pack_chain_params(chains: tuple) -> np.ndarray:
    """Column-major per-group field vectors, one contiguous uint32 block per
    group in ``_group_chains`` iteration order.

    This is the per-generation params array: a published ``Generation``
    packs it ONCE (and freezes it), so probes of an old generation after a
    newer one publishes read that generation's own immutable lanes — a
    probe can never observe a half-refreshed params array."""
    groups, _ = _group_chains(chains)
    blocks = []
    for _, ts in groups.items():
        xs = [chains[t][1] for t in ts]
        os_ = [chains[t][2] for t in ts]
        cols = [
            [x[1] for x in xs],                 # stage-1 seed
            [x[2] for x in xs],                 # seg_len
            [x[6] for x in xs],                 # stage-1 word offset
            [(1 << x[4]) - 1 for x in xs],      # alpha mask
            [x[5] for x in xs],                 # fingerprint seed
            [max(x[3] - 2, 1) for x in xs],     # n_seg - 2 (fuse window)
            [o[2] for o in os_],                # othello seed
            [o[0] for o in os_],                # ma
            [o[1] for o in os_],                # mb
            [o[3] for o in os_],                # bitmap-A word offset
            [o[4] for o in os_],                # bitmap-B word offset
        ]
        blocks.append(np.asarray(cols, dtype=np.uint32).reshape(-1))
    if not blocks:
        return np.zeros(128, np.uint32)
    flat = np.concatenate(blocks)
    pad = (-len(flat)) % 128
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.uint32)])
    return flat


def _vhash_u32(hi, lo, seeds):
    """jx_hash_u32 with a [T, 1, 1] uint32 seed lane -> uint32 [T, R, C].
    (jx_fmix32 is shape-agnostic; only the seed mixing needs lifting.)"""
    h = H.jx_fmix32(lo[None, :, :].astype(jnp.uint32) ^ seeds)
    h = H.jx_fmix32(h ^ hi[None, :, :].astype(jnp.uint32)
                    ^ (seeds * jnp.uint32(_GOLDEN)))
    return h


def _vmulhi32(a, b):
    """jx_mulhi32 with both operands as uint32 arrays (16-bit partials)."""
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    a_lo = a & jnp.uint32(0xFFFF)
    a_hi = a >> 16
    b_lo = b & jnp.uint32(0xFFFF)
    b_hi = b >> 16
    ll = a_lo * b_lo
    lh = a_lo * b_hi
    hl = a_hi * b_lo
    hh = a_hi * b_hi
    mid = (ll >> 16) + (lh & jnp.uint32(0xFFFF)) + (hl & jnp.uint32(0xFFFF))
    return hh + (lh >> 16) + (hl >> 16) + (mid >> 16)


def _vrange(h, n):
    """jx_fastrange with a per-table [T, 1, 1] range lane -> int32."""
    return _vmulhi32(h, n).astype(jnp.int32)


def _grouped_chain_hits(words, params, hi, lo, base: int, n_t: int,
                        mode: str):
    """All ``n_t`` 'chain' tables of one slot-layout mode at once -> bool
    [T, R, C].

    Stage 1 (Xor fingerprint) and stage 2 (Othello bitmaps) evaluate with
    per-table parameters broadcast as [T, 1, 1] lanes sliced (statically)
    from the packed ``params`` input; the shared bank buffer absorbs
    per-table placement through the pre-offset slot indices, so each probe
    stage is ONE gather for every table together."""

    def field(i, dtype=jnp.uint32):
        lane = params[base + i * n_t: base + (i + 1) * n_t]
        return lane.astype(dtype).reshape(n_t, 1, 1)

    seeds, seg_u = field(0), field(1)
    seg_len, offsets = field(1, jnp.int32), field(2, jnp.int32)
    masks, fp_seeds = field(3), field(4)
    if mode == "fuse":
        start = _vrange(_vhash_u32(hi, lo, seeds * jnp.uint32(7919)
                                   + jnp.uint32(3)), field(5))
    else:                                # uniform: segment i of 3
        start = jnp.zeros((n_t, 1, 1), dtype=jnp.int32)
    v = jnp.zeros((n_t,) + hi.shape, dtype=jnp.uint32)
    for i in range(3):
        h = _vrange(_vhash_u32(hi, lo, seeds * jnp.uint32(7919)
                               + jnp.uint32(i)), seg_u)
        slot = offsets + (start + i) * seg_len + h
        v = v ^ jnp.take(words, slot, axis=0)
    fp = _vhash_u32(hi, lo, fp_seeds) & masks
    s1 = (v & masks) == fp
    oth_seeds = field(6)
    u = _vrange(_vhash_u32(hi, lo, oth_seeds * 3 + 1), field(7))
    w = _vrange(_vhash_u32(hi, lo, oth_seeds * 3 + 2), field(8))
    off_a, off_b = field(9, jnp.int32), field(10, jnp.int32)
    wa = jnp.take(words, off_a + (u >> 5), axis=0)
    wb = jnp.take(words, off_b + (w >> 5), axis=0)
    s2 = (((wa >> (u & 31).astype(jnp.uint32))
           ^ (wb >> (w & 31).astype(jnp.uint32))) & 1) == 1
    return s1 & s2


def othello_hit(words, hi, lo, *, ma: int, mb: int, seed: int,
                offset_a: int, offset_b: int):
    """Othello 1-bit classifier over packed LSB-first bitmaps -> bool.
    Mirrors ``Othello.lookup`` bit-for-bit (bits_a[u] ^ bits_b[v])."""
    u = H.jx_hash_to_range(hi, lo, seed * 3 + 1, ma)
    v = H.jx_hash_to_range(hi, lo, seed * 3 + 2, mb)
    wa = jnp.take(words, offset_a + (u >> 5), axis=0)
    wb = jnp.take(words, offset_b + (v >> 5), axis=0)
    ba = (wa >> (u & 31).astype(jnp.uint32)) & 1
    bb = (wb >> (v & 31).astype(jnp.uint32)) & 1
    return (ba ^ bb) == 1


def _chain_stage1(words, hi, lo, xor_params):
    """Stage-1 α-bit fingerprint match (None ⇒ degenerate pass-all)."""
    if xor_params is None:
        return jnp.ones(hi.shape, dtype=bool)
    mode, seed, seg_len, n_seg, alpha, fp_seed, offset = xor_params
    v = xor_lookup(words, hi, lo, mode=mode, seed=seed, seg_len=seg_len,
                   n_seg=n_seg, alpha=alpha, offset=offset)
    fp = H.jx_hash_u32(hi, lo, fp_seed) & jnp.uint32((1 << alpha) - 1)
    return v == fp


def _table_hit(words, hi, lo, chain):
    """One table's filter decision for the whole key tile -> bool."""
    tag = chain[0]
    if tag == "chain":
        _, xor_params, oth_params = chain
        s1 = _chain_stage1(words, hi, lo, xor_params)
        ma, mb, seed, off_a, off_b = oth_params
        s2 = othello_hit(words, hi, lo, ma=ma, mb=mb, seed=seed,
                         offset_a=off_a, offset_b=off_b)
        return s1 & s2
    if tag == "bloom":
        _, (m_bits, k, seed, offset) = chain
        return bloom_hit(words, hi, lo, m_bits=m_bits, k=k, seed=seed,
                         offset=offset)
    if tag == "always":
        return jnp.ones(hi.shape, dtype=bool)
    raise ValueError(f"unknown chain tag {tag!r}")


@functools.partial(jax.jit, static_argnames=("chains",))
def lsm_probe(words, hi2d, lo2d, params=None, *, chains: tuple):
    """words: packed uint32 FilterBank buffer (W % 128 == 0); hi2d/lo2d:
    uint32 [R, 128] with R % 8 == 0; chains: static per-table descriptors,
    newest first (see module docstring). ``params`` may be a precomputed
    ``pack_chain_params(chains)`` array (the generation-owned plumbing:
    each published Generation passes its own frozen lanes); when omitted it
    is packed here at trace time. Returns (first_hit, hits_mask)
    int32 [R, 128]."""
    if len(chains) == 0 or len(chains) > MAX_TABLES:
        raise ValueError(f"need 1..{MAX_TABLES} tables, got {len(chains)}")
    if params is None:
        params = pack_chain_params(chains)
    elif params.shape[0] != chain_params_len(chains):
        raise ValueError(
            f"params length {params.shape[0]} does not match chains "
            f"(expected {chain_params_len(chains)})")
    params = jnp.asarray(params)
    n = len(chains)
    hits: list = [None] * n
    groups, scalar = _group_chains(chains)
    for t in scalar:             # bloom / always / degenerate chain
        hits[t] = _table_hit(words, hi2d, lo2d, chains[t])
    base = 0
    for mode, ts in groups.items():
        g = _grouped_chain_hits(words, params, hi2d, lo2d, base, len(ts),
                                mode)
        for j, t in enumerate(ts):
            hits[t] = g[j]
        base += _N_FIELDS * len(ts)
    stack = jnp.stack(hits)                       # bool [n, R, C]
    t_lane = jnp.arange(n, dtype=jnp.int32).reshape(-1, 1, 1)
    mask = (stack.astype(jnp.int32) << t_lane).sum(axis=0)
    # argmax over the table axis = newest-first first hit (ties → lowest t)
    first = jnp.where(stack.any(axis=0),
                      jnp.argmax(stack, axis=0).astype(jnp.int32),
                      jnp.int32(n))
    return first, mask


@functools.partial(jax.jit, static_argnames=("chain",))
def lsm_chain_probe(words, hi2d, lo2d, *, chain: tuple):
    """Single-filter probe of one LsmChainLayout (the per-table dispatch
    path — what the fused ``lsm_probe`` replaces N of, and the
    FilterService bank dispatch for LSM chain filters): membership +
    sequential probe count (1 + stage-1 pass — a sequential querier
    touches the Othello stage only when stage 1 fires, the paper's Fig 7b
    accounting). Returns (member, probes) int32 [R, 128]."""
    _, xor_params, oth_params = chain
    s1 = _chain_stage1(words, hi2d, lo2d, xor_params)
    ma, mb, seed, off_a, off_b = oth_params
    s2 = othello_hit(words, hi2d, lo2d, ma=ma, mb=mb, seed=seed,
                     offset_a=off_a, offset_b=off_b)
    if xor_params is None:
        probes = jnp.ones(hi2d.shape, dtype=jnp.int32)
    else:
        probes = 1 + s1.astype(jnp.int32)
    return (s1 & s2).astype(jnp.int32), probes

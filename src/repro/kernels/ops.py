"""jit'd public wrappers: filter object + raw uint64 keys in, bool out.

These handle padding/tiling (common.py) and extract static layout params
from the core filter objects, so callers never touch key lanes or
layouts.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import hashing as H
from repro.core.bloom import BloomFilter
from repro.core.bloomier import XorFilter, ExactBloomier
from repro.core.chained import ChainedFilterAnd, ChainedFilterCascade

from . import common
from .bloom_probe import bloom_probe
from .xor_probe import xor_probe, exact_probe
from .chained_probe import chained_probe
from .cascade_probe import cascade_probe


def _prep_keys(keys: np.ndarray):
    hi, lo = H.np_split_u64(np.asarray(keys, dtype=np.uint64))
    hi2d, lo2d, n = common.blockify(hi, lo)
    return jnp.asarray(hi2d), jnp.asarray(lo2d), n


def bloom_query(f: BloomFilter, keys: np.ndarray) -> np.ndarray:
    hi2d, lo2d, n = _prep_keys(keys)
    words = jnp.asarray(common.pad_table(f.words))
    out = bloom_probe(words, hi2d, lo2d, m_bits=f.m_bits, k=f.k, seed=f.seed)
    return np.asarray(common.unblockify(out, n)).astype(bool)


def xor_query(f: XorFilter, keys: np.ndarray) -> np.ndarray:
    hi2d, lo2d, n = _prep_keys(keys)
    lay = f.tbl.layout
    table = jnp.asarray(common.pad_table(f.tbl.table))
    out = xor_probe(table, hi2d, lo2d, mode=lay.mode, seed=lay.seed,
                    seg_len=lay.seg_len, n_seg=lay.n_seg, alpha=f.tbl.alpha,
                    fp_seed=f.fp_seed)
    return np.asarray(common.unblockify(out, n)).astype(bool)


def exact_query(f: ExactBloomier, keys: np.ndarray) -> np.ndarray:
    hi2d, lo2d, n = _prep_keys(keys)
    lay = f.tbl.layout
    table = jnp.asarray(common.pad_table(f.tbl.table))
    out = exact_probe(table, hi2d, lo2d, mode=lay.mode, seed=lay.seed,
                      seg_len=lay.seg_len, n_seg=lay.n_seg,
                      strategy=f.strategy, bit_seed=f.bit_seed)
    return np.asarray(common.unblockify(out, n)).astype(bool)


def chained_and_params(layout) -> dict:
    """Static kwargs for ``chained_probe`` from a ChainedAndLayout."""
    x, e = layout.xor, layout.exact
    return dict(
        l1=None if x is None else (x.mode, x.seed, x.seg_len, x.n_seg, x.offset),
        l2=(e.mode, e.seed, e.seg_len, e.n_seg, e.offset),
        alpha=0 if x is None else x.alpha,
        fp_seed=0 if x is None else x.fp_seed,
        strategy=e.strategy, bit_seed=e.bit_seed)


def chained_query(f: ChainedFilterAnd, keys: np.ndarray) -> np.ndarray:
    hi2d, lo2d, n = _prep_keys(keys)
    tables, layout = f.to_tables()
    member, _ = chained_probe(jnp.asarray(tables), hi2d, lo2d,
                              **chained_and_params(layout))
    return np.asarray(common.unblockify(member, n)).astype(bool)


def cascade_query(f: ChainedFilterCascade, keys: np.ndarray,
                  with_probes: bool = False):
    """Fused whole-cascade probe: bool member [n] (and sequential probe
    counts [n] when ``with_probes``)."""
    hi2d, lo2d, n = _prep_keys(keys)
    tables, layout = f.to_tables()
    member, probes = cascade_probe(jnp.asarray(tables), hi2d, lo2d,
                                   layers=layout.probe_params())
    out = np.asarray(common.unblockify(member, n)).astype(bool)
    if with_probes:
        return out, np.asarray(common.unblockify(probes, n))
    return out

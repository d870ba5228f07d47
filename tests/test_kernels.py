"""Probe sweeps: every fused probe must match its pure-jnp ref.py oracle
bit-for-bit across shapes, layouts and fingerprint widths."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import hashing as H
from repro.core.bloom import BloomFilter
from repro.core.bloomier import XorFilter, ExactBloomier
from repro.core.chained import ChainedFilterAnd
from repro.kernels import ops, common, ref

KEYS = H.random_keys(40_000, seed=17)


def _lanes2d(keys):
    hi, lo = H.np_split_u64(keys)
    hi2, lo2, n = common.blockify(hi, lo)
    return jnp.asarray(hi2), jnp.asarray(lo2), n


# --------------------------------------------------------------------- bloom
@pytest.mark.slow          # 20-point shape sweep; the fpr sweep below keeps
@pytest.mark.parametrize("n_keys", [1, 7, 1024, 4096, 5000])   # fast coverage
@pytest.mark.parametrize("n_queries", [1, 127, 1024, 2049])
def test_bloom_kernel_matches_oracle(n_keys, n_queries):
    f = BloomFilter.build(KEYS[:n_keys], 0.02, seed=n_keys % 31)
    q = KEYS[: n_keys + n_queries][-n_queries:]
    got = ops.bloom_query(f, q)
    hi, lo = H.keys_to_lanes_jax(q)
    want = np.asarray(ref.bloom_probe_ref(jnp.asarray(f.words), hi, lo,
                                          m_bits=f.m_bits, k=f.k, seed=f.seed))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, f.query(q))


@pytest.mark.parametrize("fpr", [0.3, 0.01, 0.001])
def test_bloom_kernel_fpr_sweep(fpr):
    pos, neg = KEYS[:3000], KEYS[3000:13000]
    f = BloomFilter.build(pos, fpr, seed=5)
    assert ops.bloom_query(f, pos).all()
    np.testing.assert_array_equal(ops.bloom_query(f, neg), f.query(neg))


# ----------------------------------------------------------------------- xor
@pytest.mark.parametrize("mode", ["uniform", "fuse"])
@pytest.mark.parametrize("alpha", [1, 4, 8, 16, 32])
def test_xor_kernel_matches_oracle(mode, alpha):
    pos = KEYS[:2500]
    f = XorFilter.build(pos, alpha, mode=mode, seed=3)
    q = KEYS[:8000]
    got = ops.xor_query(f, q)
    np.testing.assert_array_equal(got, f.query(q))
    hi, lo = H.keys_to_lanes_jax(q)
    lay = f.tbl.layout
    want = np.asarray(ref.xor_probe_ref(
        jnp.asarray(common.pad_table(f.tbl.table)), hi, lo, mode=lay.mode,
        seed=lay.seed, seg_len=lay.seg_len, n_seg=lay.n_seg,
        alpha=alpha, fp_seed=f.fp_seed))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("strategy", ["a", "b"])
def test_exact_kernel_matches_oracle(strategy):
    pos, neg = KEYS[:1500], KEYS[1500:9000]
    f = ExactBloomier.build(pos, neg, strategy=strategy, seed=7)
    q = np.concatenate([pos, neg, KEYS[9000:12000]])   # incl. out-of-universe
    got = ops.exact_query(f, q)
    np.testing.assert_array_equal(got, f.query(q))


# ------------------------------------------------------------------- chained
@pytest.mark.parametrize("lam", [2, 8, 16])
def test_chained_kernel_matches_oracle(lam):
    n = 1500
    pos, neg = KEYS[:n], KEYS[n:n + lam * n]
    cf = ChainedFilterAnd.build(pos, neg, seed=lam)
    q = np.concatenate([pos, neg])
    got = ops.chained_query(cf, q)
    np.testing.assert_array_equal(got, cf.query(q))
    assert got[:n].all() and not got[n:].any()


def test_chained_kernel_degenerate_small_lambda():
    """lam <= 1/ln2: stage 1 absent, kernel must still answer exactly."""
    pos, neg = KEYS[:2000], KEYS[2000:3000]
    cf = ChainedFilterAnd.build(pos, neg, seed=2)
    q = np.concatenate([pos, neg])
    np.testing.assert_array_equal(ops.chained_query(cf, q), cf.query(q))


# ------------------------------------------------------------ block plumbing
@pytest.mark.parametrize("n", [1, 8, 127, 128, 1023, 1024, 1025, 9999])
def test_blockify_roundtrip(n):
    hi = np.arange(n, dtype=np.uint32)
    lo = hi * 7
    h2, l2, nv = common.blockify(hi, lo)
    assert h2.shape[1] == common.BLOCK_COLS
    assert h2.shape[0] % common.BLOCK_ROWS == 0
    back = np.asarray(common.unblockify(jnp.asarray(h2), nv))
    np.testing.assert_array_equal(back, hi)

"""FilterBank packing + fused cascade kernel + FilterService (ISSUE 1).

Covers: to_tables/from_tables round-trip equivalence with direct query()
on all five filter types; cascade_probe vs ChainedFilterCascade.query
parity (membership AND sequential probe counts); packed-bank probing
matching per-filter queries; the batched tiered prefix-cache path; and
hypothesis property tests over construction parameters.
"""
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import hashing as H
from repro.core.bloom import BloomFilter
from repro.core.bloomier import XorFilter, ExactBloomier
from repro.core.chained import ChainedFilterAnd, ChainedFilterCascade
from repro.core.tables import TABLE_ALIGN
from repro.kernels import ops
from repro.serving.filter_service import FilterBank, FilterService
from repro.serving.prefix_cache import TieredPrefixCache, TierSpec

KEYS = H.random_keys(60_000, seed=23)
QUERIES = KEYS[:8192]   # kept modest: each layout compiles its own probe


def _build(kind: str, seed: int = 0):
    pos, neg = KEYS[:1500], KEYS[1500:9000]
    if kind == "bloom":
        return BloomFilter.build(pos, 0.02, seed=seed)
    if kind == "xor":
        return XorFilter.build(pos, 8, seed=seed)
    if kind == "exact":
        return ExactBloomier.build(pos, neg, seed=seed)
    if kind == "chained_and":
        return ChainedFilterAnd.build(pos, neg, seed=seed)
    if kind == "chained_and_degenerate":
        return ChainedFilterAnd.build(KEYS[:2000], KEYS[2000:3000], seed=seed)
    if kind == "cascade":
        return ChainedFilterCascade.build(pos, neg, seed=seed)
    raise ValueError(kind)

ALL_KINDS = ["bloom", "xor", "exact", "chained_and", "chained_and_degenerate",
             "cascade"]


# ------------------------------------------------------------- round trip
@pytest.mark.parametrize("kind", ALL_KINDS)
def test_tables_roundtrip_matches_query(kind):
    f = _build(kind, seed=5)
    tables, layout = f.to_tables()
    assert tables.dtype == np.uint32
    assert len(tables) % TABLE_ALIGN == 0
    g = type(f).from_tables(tables, layout)
    np.testing.assert_array_equal(f.query(QUERIES), g.query(QUERIES))


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_tables_roundtrip_survives_offset_shift(kind):
    """from_tables must honour layout offsets — the packed-bank contract."""
    f = _build(kind, seed=6)
    tables, layout = f.to_tables()
    shifted = np.concatenate([np.zeros(3 * TABLE_ALIGN, np.uint32), tables])
    g = type(f).from_tables(shifted, layout.shift(3 * TABLE_ALIGN))
    np.testing.assert_array_equal(f.query(QUERIES), g.query(QUERIES))


def test_filterbank_pack_unpack_all_kinds():
    filters = [_build(k, seed=i) for i, k in enumerate(ALL_KINDS)]
    bank = FilterBank.pack(filters)
    assert bank.tables.dtype == np.uint32
    assert bank.n_filters == len(filters)
    for f, g in zip(filters, bank.unpack()):
        np.testing.assert_array_equal(f.query(QUERIES), g.query(QUERIES))


# --------------------------------------------------------- fused cascade
@pytest.mark.parametrize("lam", [2, 8])
def test_cascade_probe_matches_query(lam):
    n = 1200
    pos, neg = KEYS[:n], KEYS[n:n * (lam + 1)]
    cas = ChainedFilterCascade.build(pos, neg, seed=lam)
    q = np.concatenate([pos, neg, KEYS[n * (lam + 1):n * (lam + 1) + 2000]])
    member, probes = ops.cascade_query(cas, q, with_probes=True)
    np.testing.assert_array_equal(member, cas.query(q))
    np.testing.assert_array_equal(probes, cas.probes_until_decided(q))
    assert member[:n].all() and not member[n:n * (lam + 1)].any()


def test_cascade_probe_single_layer():
    """L=1 edge: no zero across the only layer ⇒ member ⇔ L odd."""
    pos = KEYS[:800]
    cas = ChainedFilterCascade.build(pos, np.array([], np.uint64), seed=1)
    assert cas.n_layers == 1
    member = ops.cascade_query(cas, pos)
    assert member.all()


# ------------------------------------------------------- property tests
@given(st.integers(300, 1200), st.sampled_from([2, 4, 8]),
       st.integers(0, 200))
@settings(max_examples=4, deadline=None)
def test_cascade_fused_parity_property(n, lam, seed):
    pos, neg = KEYS[:n], KEYS[n:n + lam * n]
    cas = ChainedFilterCascade.build(pos, neg, seed=seed)
    q = KEYS[:min(len(KEYS), n * (lam + 1) + 2000)]
    np.testing.assert_array_equal(ops.cascade_query(cas, q), cas.query(q))


@given(st.sampled_from(ALL_KINDS), st.integers(0, 100))
@settings(max_examples=8, deadline=None)
def test_tables_roundtrip_property(kind, seed):
    f = _build(kind, seed=seed)
    tables, layout = f.to_tables()
    g = type(f).from_tables(tables, layout)
    q = KEYS[:4000]
    np.testing.assert_array_equal(f.query(q), g.query(q))


# --------------------------------------------------------- FilterService
def test_filter_service_bank_matches_direct_queries():
    filters = [_build(k, seed=i) for i, k in enumerate(ALL_KINDS)]
    svc = FilterService(filters)
    member, probes = svc.probe(QUERIES)
    assert member.shape == (len(filters), len(QUERIES))
    for i, f in enumerate(filters):
        np.testing.assert_array_equal(member[i], f.query(QUERIES))
    # sequential probe accounting: cascade probes ≥ 1, ≤ L; chained ∈ {1, 2}
    cas_i = ALL_KINDS.index("cascade")
    cas = filters[cas_i]
    np.testing.assert_array_equal(probes[cas_i],
                                  cas.probes_until_decided(QUERIES))
    and_i = ALL_KINDS.index("chained_and")
    assert set(np.unique(probes[and_i])) <= {1, 2}
    stats = svc.stats.as_dict()
    assert stats["lookups"] == len(QUERIES)
    assert stats["hits"][cas_i] == int(member[cas_i].sum())


def test_filter_service_probe_filter_single_dispatch():
    filters = [_build("bloom", seed=1), _build("cascade", seed=2)]
    svc = FilterService(filters)
    got = svc.probe_filter(1, QUERIES[:2000])
    np.testing.assert_array_equal(got, filters[1].query(QUERIES[:2000]))
    assert svc.stats.lookups == 0          # aggregate stats untouched


def test_filter_service_refresh_tables_in_place():
    f = BloomFilter.build(KEYS[:500], 0.02, seed=9)
    svc = FilterService([f])
    extra = KEYS[500:600]
    assert not svc.probe_filter(0, extra).all()
    f.insert(extra)                        # bit-flips only; layout invariant
    svc.refresh_tables([f])
    assert svc.probe_filter(0, extra).all()
    with pytest.raises(ValueError):        # layout change must be rejected
        svc.refresh_tables([BloomFilter.build(KEYS[:5000], 0.02, seed=9)])


def test_filter_service_empty_batch():
    svc = FilterService([_build("bloom", seed=2)])
    member, probes = svc.probe(np.array([], np.uint64))
    assert member.shape == (1, 0) and probes.shape == (1, 0)
    assert svc.stats.lookups == 0


def test_filter_service_odd_batch_sizes():
    svc = FilterService([_build("bloom", seed=2)])
    for n in [1, 127, 1025]:
        member, _ = svc.probe(QUERIES[:n])
        np.testing.assert_array_equal(member[0],
                                      svc.unpack()[0].query(QUERIES[:n]))


def test_filter_service_multidevice_shard_map():
    """The shard_map row-sharding path on a 4-device CPU mesh. Runs in a
    subprocess (cold jax import): device count must be fixed before jax
    initializes."""
    code = """
import jax, numpy as np
assert jax.device_count() == 4, jax.device_count()
from repro.core import hashing as H
from repro.core.bloom import BloomFilter
from repro.core.chained import ChainedFilterCascade
from repro.serving.filter_service import FilterService
K = H.random_keys(9000, seed=9)
filters = [BloomFilter.build(K[:500], 0.02, seed=1),
           ChainedFilterCascade.build(K[:500], K[500:4500], seed=2)]
svc = FilterService(filters)
q = K[:7001]   # odd size: pads across 4 devices
member, _ = svc.probe(q)
for i, f in enumerate(filters):
    np.testing.assert_array_equal(member[i], f.query(q))
print("OK")
"""
    repo_root = pathlib.Path(__file__).parent.parent
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(repo_root / "src")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=str(repo_root))
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout


# ------------------------------------------------- batched tiered lookups
def _tiers():
    return [TierSpec("hbm", 4, 1.0), TierSpec("dram", 8, 10.0),
            TierSpec("ssd", 64, 150.0)]


def test_prefix_cache_lookup_batch_matches_sequential():
    pc_a = TieredPrefixCache(_tiers(), seed=4)
    pc_b = TieredPrefixCache(_tiers(), seed=4)
    rng = np.random.default_rng(1)
    keys = rng.integers(1, 2**62, 40).tolist()
    for i, k in enumerate(keys):
        pc_a.insert(k, payload=i)
        pc_b.insert(k, payload=i)
    probe_keys = keys + rng.integers(2**62, 2**63, 60).tolist()
    seq = [pc_a.lookup(k) for k in probe_keys]
    bat = pc_b.lookup_batch(probe_keys)
    assert seq == bat
    assert pc_b.batched_lookups == len(probe_keys)
    # same §5.4 accounting on both paths
    assert pc_a.probes == pc_b.probes
    assert pc_a.wasted_probes == pc_b.wasted_probes


def test_prefix_cache_lookup_batch_wasted_probe_invariant():
    pc = TieredPrefixCache(_tiers(), seed=5)
    rng = np.random.default_rng(2)
    keys = rng.integers(1, 2**62, 50).tolist()
    for i, k in enumerate(keys):
        pc.insert(k, payload=i)
    results = pc.lookup_batch(keys)
    assert all(p is not None for p, _ in results)
    assert pc.wasted_probes == 0
    before = pc.probes
    misses = pc.lookup_batch(rng.integers(2**62, 2**63, 100).tolist())
    assert all(p is None for p, _ in misses)
    assert pc.probes - before <= 100          # ≤ 1 wasted probe per lookup


def test_prefix_cache_service_refreshes_after_insert():
    pc = TieredPrefixCache(_tiers(), seed=6)
    pc.insert(101, payload="a")
    assert pc.lookup_batch([101]) == [("a", 0)]
    pc.insert(202, payload="b")               # mutates tier filters
    assert pc.lookup_batch([202]) == [("b", 0)]
    assert pc.lookup_batch([101])[0][0] == "a"

"""The traffic generator: reproducible from the seed, scrambled Zipfian."""
import numpy as np

from chipbench import spec, ycsb

SEED = 2**31 + 77          # the driver's seeds exceed 32 signed bits


def test_fnv1a64_matches_ycsb():
    # Utils.fnvhash64(0) and (1): FNV-1a over 8 little-endian bytes
    def ref(v):
        h = ycsb.FNV_OFFSET
        for _ in range(8):
            h = ((h ^ (v & 0xFF)) * ycsb.FNV_PRIME) & ycsb.MASK64
            v >>= 8
        return h
    xs = np.array([0, 1, 255, 2**40 + 3], dtype=np.uint64)
    assert [int(h) for h in ycsb.fnv1a64(xs)] == [ref(int(x)) for x in xs]


def test_keys_distinct_and_seeded():
    a = ycsb.record_keys(np.arange(100_000), SEED)
    assert len(np.unique(a)) == len(a)
    assert np.array_equal(a, ycsb.record_keys(np.arange(100_000), SEED))
    assert not np.array_equal(a, ycsb.record_keys(np.arange(100_000), SEED + 1))
    assert (ycsb.load_values(a, SEED) & ycsb.TOP == 0).all()


def test_pool_reproduces_from_seed():
    t = spec.traffic("ycsb_a")
    p1 = ycsb.draw_pool(t, 50_000, SEED, 2.0)
    p2 = ycsb.draw_pool(t, 50_000, SEED, 2.0)
    p3 = ycsb.draw_pool(t, 50_000, SEED + 1, 2.0)
    assert np.array_equal(p1.kind, p2.kind)
    for k in ("read", "update"):
        assert np.array_equal(p1.keys[k], p2.keys[k])
    assert not np.array_equal(p1.keys["read"][:10], p3.keys["read"][:10])
    share = (p1.kind == ycsb.KINDS.index("read")).mean()
    assert 0.45 < share < 0.55
    # the rank -> key table gives what hashing each rank gives
    ranks = ycsb.Zipfian(50_000, 0.99).ranks(np.random.default_rng(3), 1000)
    direct = ycsb.record_keys(ycsb.scrambled(ranks, 50_000), SEED)
    table = ycsb.record_keys(ycsb.scrambled(np.arange(50_000), 50_000), SEED)
    assert np.array_equal(table[ranks], direct)


def test_scrambled_zipfian_is_skewed_and_spreads_hot_ranks():
    n = 1 << 16
    rng = np.random.default_rng(1)
    ranks = ycsb.Zipfian(n, 0.99).ranks(rng, 200_000)
    assert (ranks == 0).mean() > 0.05                # the head is hot
    assert (ranks < 100).mean() > 0.3
    hot = ycsb.scrambled(np.arange(1000), n)          # the 1,000 hottest ranks
    # spread over the key space: every eighth of the records holds some
    counts = np.bincount(hot * 8 // n, minlength=8)
    assert counts.min() > 60, counts


def test_write_values_name_their_write():
    v = ycsb.write_value(12345, 128)
    ids = ycsb.decode_write(v)
    assert (ids >> ycsb.OP_BITS == 12345).all()
    assert np.array_equal(ids & 127, np.arange(128))
    assert (ycsb.decode_write(np.array([5], np.uint64)) == -1).all()

"""The probe's byte count per table kind."""
from chipbench import spec

CHAIN = ("chain", ("fuse", 1, 64, 10, 7, 2, 0), (1024, 1024, 3, 0, 32))
CHAIN_NO_XOR = ("chain", None, (1024, 1024, 3, 0, 32))
BLOOM7 = ("bloom", (8192, 7, 5, 0))


def test_words_per_key_by_table_kind():
    k = spec.module("kernels", "lsm_probe")
    assert k.words_per_key((CHAIN,)) == 5            # 3 Xor + 2 Othello
    assert k.words_per_key((CHAIN_NO_XOR,)) == 2
    assert k.words_per_key((BLOOM7,)) == 7           # k words
    assert k.words_per_key((("always",),)) == 0
    assert k.words_per_key((CHAIN,) * 7) == 35


def test_bytes_count_real_keys_not_padded_tiles():
    k = spec.module("kernels", "lsm_probe")
    chains = (CHAIN,) * 7
    per_key = 8 + 8 + 4 * 35                         # key, results, 35 words
    assert k.bytes_moved(128, chains) == 128 * per_key
    # a 128-key request is padded to a 1,024-key tile on the device; the
    # count follows the real keys, so padding reads as a lower share
    assert k.bytes_moved(1024, chains) == 8 * k.bytes_moved(128, chains)
    assert k.bytes_moved(100, (BLOOM7,) * 7) == 100 * (16 + 4 * 49)

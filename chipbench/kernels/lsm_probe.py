"""Bytes the fused LSM filter probe must move for real keys.

Per real (unpadded) key the probe reads its key (hi and lo uint32 lanes),
writes two int32 results (first hit, hit mask), and gathers, per table:

- a two-stage chained filter: 3 stage-1 Xor words (none when stage 1 is
  degenerate) and 2 Othello words;
- a Bloom filter: ``k`` words;
- a table without a filter: nothing.

Words are uint32. The count depends on the tables alone, not on how the
probe is implemented, so tiles padded with dead keys read as a lower
roofline share. Integer hashing is not counted: the probe is bound by its
gathers, and the v5e has no published peak for vector integer ops.
"""
from __future__ import annotations

WORD_BYTES = 4
KEY_BYTES = 8
RESULT_BYTES = 8


def words_per_key(chains: tuple) -> int:
    """Filter words one key gathers over every table of a generation, from
    the probe's static per-table descriptors."""
    words = 0
    for table in chains:
        tag = table[0]
        if tag == "chain":
            words += (3 if table[1] is not None else 0) + 2
        elif tag == "bloom":
            words += int(table[1][1])
        elif tag != "always":
            raise ValueError(f"unknown table kind {tag!r}")
    return words


def bytes_moved(n_keys: int, chains: tuple) -> int:
    return int(n_keys) * (KEY_BYTES + RESULT_BYTES + WORD_BYTES * words_per_key(chains))

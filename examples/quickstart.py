"""Quickstart: the paper in 60 seconds.

Builds an exact ChainedFilter (Algorithm 1) over 100k keys, verifies
zero-error membership, compares its size against the single exact Bloomier
filter and the information-theoretic lower bound, and runs the fused
two-stage device probe (one jitted XLA program).

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import hashing as H, theory
from repro.core.bloomier import ExactBloomier
from repro.core.chained import ChainedFilterAnd
from repro.kernels import ops


def main():
    n, lam = 100_000, 8
    keys = H.random_keys(n * (lam + 1), seed=1)
    pos, neg = keys[:n], keys[n:]

    print(f"n={n} positives, lambda={lam} ({len(neg)} negatives)")

    cf = ChainedFilterAnd.build(pos, neg, seed=7)
    assert cf.query(pos).all(), "false negative!"
    assert not cf.query(neg).any(), "false positive!"
    print(f"ChainedFilter ('&', Alg. 1): {cf.bits / n:.2f} bits/key "
          f"(stage-1 alpha={cf.f1.alpha}, {cf.n_false_pos} stage-2 whitelists)")

    eb = ExactBloomier.build(pos, neg, seed=7)
    lb = theory.f_lower_bound(0.0, lam)
    print(f"exact Bloomier alone:        {eb.bits / n:.2f} bits/key")
    print(f"space lower bound (Thm 2.1): {lb:.2f} bits/key")
    print(f"=> ChainedFilter is {cf.bits / n / lb:.2f}x the bound, "
          f"saves {(1 - cf.bits / eb.bits) * 100:.0f}% vs exact Bloomier")

    # fused two-stage device probe
    sample = np.concatenate([pos[:512], neg[:512]])
    got = ops.chained_query(cf, sample)
    assert (got == cf.query(sample)).all()
    print(f"fused chained_probe matches oracle on {len(sample)} keys")

    # the chain rule itself (Thm 2.2): lossless factorization
    gap = theory.chain_rule_gap(0.001, 64.0, 0.05)
    print(f"chain-rule factorization gap at (eps=1e-3, lam=64): {gap:.2e}")


if __name__ == "__main__":
    main()

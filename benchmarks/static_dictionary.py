"""Paper §5.1 (Fig 6 + Fig 7): static dictionary — filter space,
construction throughput and query throughput of exact Bloomier vs
ChainedFilter, vs the theoretical lower bound; plus the fused device probe
query path."""
from __future__ import annotations

import numpy as np

from repro.core import hashing as H, theory
from repro.core.bloomier import ExactBloomier
from repro.core.chained import ChainedFilterAnd
from repro.kernels import ops
from ._util import render_table, scale, time_op, mops


def run() -> str:
    n = scale(1_000_000, 20_000)
    rows = []
    for lam in (2, 4, 8, 16):
        keys = H.random_keys(n * (lam + 1), seed=lam)
        pos, neg = keys[:n], keys[n:]

        t_eb, eb = time_op(lambda: ExactBloomier.build(pos, neg, seed=3),
                           repeat=1)
        t_cf, cf = time_op(lambda: ChainedFilterAnd.build(pos, neg, seed=3),
                           repeat=1)
        assert cf.query(pos).all() and not cf.query(neg).any()

        q = keys[: min(len(keys), 200_000)]
        tq_eb, _ = time_op(eb.query, q, repeat=1)
        tq_cf, _ = time_op(cf.query, q, repeat=1)
        tq_k, _ = time_op(lambda: ops.chained_query(cf, q), repeat=1)

        lb = theory.f_lower_bound(0.0, lam)
        rows.append([
            lam,
            f"{eb.bits / n:.2f}", f"{cf.bits / n:.2f}", f"{lb:.2f}",
            f"{cf.bits / n / lb:.2f}x",
            f"{mops(n * (lam + 1), t_eb):.2f}", f"{mops(n * (lam + 1), t_cf):.2f}",
            f"{mops(len(q), tq_eb):.2f}", f"{mops(len(q), tq_cf):.2f}",
            f"{mops(len(q), tq_k):.2f}",
        ])
    return render_table(
        f"Static dictionary (Fig 6/7), n={n} positives "
        "[space bits/key | construct Mops | query Mops]",
        ["lam", "EB b/k", "CF b/k", "LB b/k", "CF/LB",
         "EBc", "CFc", "EBq", "CFq", "CFq-kernel"],
        rows)

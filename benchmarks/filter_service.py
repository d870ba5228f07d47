"""Batched FilterBank probe throughput: fused cascade kernel vs. the
per-layer query_jax loop (§5.3 serving hot path).

Three probe paths over the same ChainedFilterCascade and key batch:

  per-layer  — ``ChainedFilterCascade.query_jax``: one device dispatch per
               Bloom layer plus an [n, L] stack (the seed implementation);
  fused      — ``cascade_probe``: every layer + the first-zero parity rule
               in one device program over the packed FilterBank buffer;
  service    — ``FilterService.probe`` over a heterogeneous 5-filter bank
               (shared packed buffer, shard_map row dispatch).

Acceptance target: fused ≥ 1.5× per-layer throughput at CI scale.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import hashing as H
from repro.core.bloom import BloomFilter
from repro.core.bloomier import XorFilter, ExactBloomier
from repro.core.chained import ChainedFilterAnd, ChainedFilterCascade
from repro.kernels import common
from repro.kernels.cascade_probe import cascade_probe
from repro.serving.filter_service import FilterService

from ._util import scale, time_op, mops, render_table


def run():
    n_pos = scale(1_000_000, 2048)
    lam = 8
    n_queries = scale(4_000_000, 32_768)
    keys = H.random_keys(n_pos * (lam + 1) + n_queries, seed=42)
    pos, neg = keys[:n_pos], keys[n_pos:n_pos * (lam + 1)]
    rng = np.random.default_rng(7)
    queries = rng.choice(keys, size=n_queries, replace=True)

    cascade = ChainedFilterCascade.build(pos, neg, seed=3)
    tables, layout = cascade.to_tables()

    # -- per-layer loop (incumbent): L dispatch rounds + [n, L] stack -------
    hi, lo = H.keys_to_lanes_jax(queries)
    t_eager, want = time_op(
        lambda: np.asarray(jax.block_until_ready(cascade.query_jax(hi, lo))))

    # -- fused kernel over the packed buffer --------------------------------
    hi_np, lo_np = H.np_split_u64(queries)
    hi2d, lo2d, n_valid = common.blockify(hi_np, lo_np)
    hi2d, lo2d = jnp.asarray(hi2d), jnp.asarray(lo2d)
    tables_dev = jnp.asarray(tables)
    layers = layout.probe_params()

    def fused():
        member, _ = cascade_probe(tables_dev, hi2d, lo2d, layers=layers)
        return np.asarray(common.unblockify(
            jax.block_until_ready(member), n_valid)).astype(bool)

    got = fused()                                    # warmup: jit compile
    np.testing.assert_array_equal(got, want)
    t_fused, _ = time_op(fused)

    # -- heterogeneous bank through FilterService ---------------------------
    service = FilterService([
        BloomFilter.build(pos, 0.01, seed=11),
        XorFilter.build(pos, 8, seed=12),
        ExactBloomier.build(pos[:n_pos // 2], neg[:n_pos], seed=13),
        ChainedFilterAnd.build(pos, neg, seed=14),
        cascade,
    ])
    service.probe(queries[:common.BLOCK])            # warmup: jit compile
    t_bank, _ = time_op(service.probe, queries)
    bank_queries = n_queries * service.bank.n_filters   # filter-queries/s

    speedup = t_eager / t_fused
    rows = [
        ["per-layer query_jax", f"{t_eager * 1e3:8.1f}", f"{mops(n_queries, t_eager):8.2f}", "1.00x"],
        ["fused cascade_probe", f"{t_fused * 1e3:8.1f}", f"{mops(n_queries, t_fused):8.2f}", f"{speedup:.2f}x"],
        ["FilterService 5-filter bank", f"{t_bank * 1e3:8.1f}", f"{mops(bank_queries, t_bank):8.2f}", "-"],
    ]
    out = render_table(
        f"filter_service — cascade L={cascade.n_layers}, {n_queries} queries, "
        f"bank {service.bank.nbytes / 1024:.0f} KiB",
        ["path", "ms", "Mq/s", "speedup"], rows)
    verdict = "PASS" if speedup >= 1.5 else "FAIL"
    out += (f"\nfused vs per-layer speedup: {speedup:.2f}x "
            f"(target >= 1.5x) [{verdict}]")
    metrics = {
        "n_queries": int(n_queries),
        "cascade_layers": int(cascade.n_layers),
        "t_per_layer_ms": t_eager * 1e3,
        "t_fused_ms": t_fused * 1e3,
        "t_bank_ms": t_bank * 1e3,
        "mqps_per_layer": mops(n_queries, t_eager),
        "mqps_fused": mops(n_queries, t_fused),
        "mqps_bank_filter_queries": mops(bank_queries, t_bank),
        "fused_speedup_vs_per_layer": speedup,
        "speedup_target_met": bool(speedup >= 1.5),
    }
    return out, metrics

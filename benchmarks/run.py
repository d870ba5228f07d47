"""Benchmark driver: one benchmark per paper table/figure + the roofline
table from dry-run artifacts + the serving FilterBank probe bench.

    PYTHONPATH=src python -m benchmarks.run            # CI scale
    BENCH_FULL=1 PYTHONPATH=src python -m benchmarks.run   # paper scale (1M keys)

Each benchmark's ``run()`` returns either a printable string or a
``(string, metrics_dict)`` pair; numbers land in ``BENCH_results.json``
(uploaded as a CI artifact by the bench-smoke job).

``REGISTRY`` is the single source of truth for what this driver produces:
modules import lazily inside ``main`` so tooling (``benchmarks.compare``'s
stale-section check) can enumerate the registered names without paying
for jax imports.
"""
from __future__ import annotations

import importlib
import json
import sys
import time
import traceback

RESULTS_PATH = "BENCH_results.json"

# (results-section name, module under benchmarks/) — every section a run
# writes comes from exactly one entry here; compare.py warns on results
# sections with no registered producer (stale artifacts from removed or
# renamed benchmarks).
REGISTRY = [
    ("chain_rule (§2)", "chain_rule"),
    ("static_dictionary (§5.1, Fig 6/7)", "static_dictionary"),
    ("huffman (§5.2, Fig 8)", "huffman"),
    ("adaptive_hashing (§5.3, Tab 3/Fig 10)", "adaptive_hashing"),
    ("lsm_pointquery (§5.4, Fig 12)", "lsm_pointquery"),
    ("lsm_store (batched storage engine)", "lsm_store"),
    ("write_path (bulk-synchronous ingest)", "write_path"),
    ("scan_delete (range scans + tombstone deletes)", "scan_delete"),
    ("snapshot_compact (generations + snapshot-pinned scans)",
     "snapshot_compact"),
    ("query_pipeline (filter-pushdown query plans)", "query_pipeline"),
    ("sustained (always-on closed-loop CRUD)", "sustained"),
    ("learned_filter (§5.5, Fig 13)", "learned_filter"),
    ("roofline (dry-run artifacts)", "roofline"),
    ("filter_service (fused cascade vs per-layer)", "filter_service"),
]

REGISTERED_NAMES = frozenset(name for name, _ in REGISTRY)


def main() -> int:
    import pathlib

    import jax.numpy as jnp
    from repro import compile_cache
    from repro.models import common as MC
    compile_cache.enable(pathlib.Path(__file__).resolve().parents[1]
                         / ".jax_cache")
    MC.set_compute_dtype(jnp.float32)        # CPU execution dtype

    failures = 0
    results: dict = {}
    for name, module in REGISTRY:
        t0 = time.perf_counter()
        try:
            fn = importlib.import_module(f".{module}", __package__).run
            out = fn()
            metrics = None
            if isinstance(out, tuple):
                out, metrics = out
            seconds = time.perf_counter() - t0
            print(out)
            print(f"[{name}] done in {seconds:.1f}s", flush=True)
            results[name] = {"ok": True, "seconds": seconds}
            if metrics is not None:
                results[name]["metrics"] = metrics
        except Exception:
            failures += 1
            seconds = time.perf_counter() - t0
            print(f"[{name}] FAILED:\n{traceback.format_exc()}", flush=True)
            results[name] = {"ok": False, "seconds": seconds}
    with open(RESULTS_PATH, "w") as fh:
        json.dump(results, fh, indent=2, sort_keys=True)
    print(f"wrote {RESULTS_PATH}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

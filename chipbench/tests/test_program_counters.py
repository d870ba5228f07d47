"""The per-layer metrics that read the store's own counters: small traced
runs on the CPU report each one that applies, and a store without the
counters (the control, or a program older than them) leaves them out."""
import math
from types import SimpleNamespace

import pytest

from chipbench import harness, spec
from chipbench.control import control_store
from chipbench.tests.conftest import ROOT

SEED = 2**31 + 1401
COUNTER_METRICS = ("read_host_cpu_ms", "read_mu_wait_ms", "probe_d2h_ms",
                   "probe_fill_pct", "write_merge_ms", "setup_build_s")


def _traced(cell, bench, small_config, factory=None):
    return harness.run_cell(cell, SEED, 1.0, True, root=ROOT, bench=bench,
                            cfg=small_config(cell), store_factory=factory,
                            require_chip=False, log=lambda line: None)


@pytest.mark.parametrize("cell", ["ycsb_c.kv8m_chained", "ycsb_a.kv8m_chained"])
def test_traced_run_reports_the_counter_metrics(cell, bench, small_config):
    r = _traced(cell, bench, small_config)
    assert r["correct"], r["checks"]
    writes = cell.startswith("ycsb_a")
    for name in COUNTER_METRICS:
        if name == "write_merge_ms" and not writes:
            assert name not in r["metrics"]
            continue
        value = r["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0, (name, value)
    assert 0 < r["metrics"]["probe_fill_pct"]["value"] <= 100
    assert r["metrics"]["setup_build_s"]["value"] > 0
    assert r["metrics"]["read_host_cpu_ms"]["value"] > 0


def test_control_run_leaves_the_counter_metrics_out(bench, small_config):
    r = _traced("ycsb_a.kv8m_chained", bench, small_config, control_store)
    assert not set(COUNTER_METRICS) & set(r["metrics"])


def test_store_without_the_counters_reads_none():
    """A store whose counters predate these metrics: each reader returns
    None rather than raising."""
    old = {"gets": 10, "probed": 5, "sstable_reads": 5, "wasted_reads": 0}
    run = SimpleNamespace(stats0=old, stats1=dict(old, gets=20), seconds=1.0,
                          stat_delta=lambda name: 0)
    for name in COUNTER_METRICS:
        assert spec.module("metrics", name).read(run) is None, name

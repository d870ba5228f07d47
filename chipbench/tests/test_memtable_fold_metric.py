"""``memtable_fold_ms``: the writers' time folding the memtable's sealed
delta into its base, per ``put_batch`` call over the window. A traced
write cell reports it, a read-only cell and the control leave it out, and
a store without the counter reads None."""
import math
from types import SimpleNamespace

import pytest

from chipbench import harness, spec
from chipbench.control import control_store
from chipbench.tests.conftest import ROOT

SEED = 2**31 + 1501
NAME = "memtable_fold_ms"


def _read(run):
    return spec.module("metrics", NAME).read(run)


def _traced(cell, bench, small_config, factory=None):
    return harness.run_cell(cell, SEED, 1.0, True, root=ROOT, bench=bench,
                            cfg=small_config(cell), store_factory=factory,
                            require_chip=False, log=lambda line: None)


def test_fold_metric_reads_fold_time_per_put_call():
    """The window's ``fold_ns`` over its ``put_calls``, in ms."""
    stats0 = {"put_calls": 100, "fold_ns": 1_000_000}
    stats1 = {"put_calls": 356, "fold_ns": 9_000_000}
    run = SimpleNamespace(stats0=stats0, stats1=stats1, seconds=1.0,
                          stat_delta=lambda name: stats1[name] - stats0[name])
    assert math.isclose(_read(run), 8.0 / 256)
    run.stat_delta = lambda name: 0 if name == "put_calls" else 8_000_000
    assert _read(run) is None                     # no put in the window


def test_store_without_the_fold_counter_reads_none():
    """A store whose counters predate the fold: None rather than raising."""
    old = {"gets": 10, "put_calls": 4, "merge_ns": 1000}
    run = SimpleNamespace(stats0=old, stats1=dict(old, put_calls=8),
                          seconds=1.0, stat_delta=lambda name: 4)
    assert _read(run) is None
    run = SimpleNamespace(stats0={}, stats1={}, seconds=1.0,
                          stat_delta=lambda name: 0)
    assert _read(run) is None


@pytest.mark.parametrize("cell", ["ycsb_c.kv8m_chained", "ycsb_a.kv8m_chained"])
def test_traced_run_reports_the_fold_metric_in_write_cells(cell, bench,
                                                           small_config):
    r = _traced(cell, bench, small_config)
    assert r["correct"], r["checks"]
    if cell.startswith("ycsb_a"):
        value = r["metrics"][NAME]["value"]
        assert math.isfinite(value) and value >= 0, value
    else:
        assert NAME not in r["metrics"]


def test_control_run_leaves_the_fold_metric_out(bench, small_config):
    r = _traced("ycsb_a.kv8m_chained", bench, small_config, control_store)
    assert NAME not in r["metrics"]

"""Whole runs on the CPU at a small size, with the chip look skipped:
sound runs come out correct; the control and each fault the cells can
have come out not correct."""
import numpy as np
import pytest

from chipbench import harness
from chipbench.control import control_store
from chipbench.tests.conftest import ROOT

SEED = 2**31 + 901
SECONDS = 1.0


class Faulty:
    """The store under test with one fault planted where answers or state
    are produced."""

    def __init__(self, store, fault: str):
        self._store = store
        self._fault = fault

    def __getattr__(self, name):
        return getattr(self._store, name)

    def get_batch(self, keys):
        if self._fault == "half_batch":        # half the keys left out
            h = len(keys) // 2
            found, vals, reads = self._store.get_batch(keys[:h])
            pad = len(keys) - h
            return (np.concatenate([found, np.zeros(pad, bool)]),
                    np.concatenate([vals, np.zeros(pad, np.uint64)]),
                    np.concatenate([reads, np.zeros(pad, np.int32)]))
        found, vals, reads = self._store.get_batch(keys)
        if self._fault == "altered_answer" and found.any():
            vals = vals.copy()
            vals[np.flatnonzero(found)[0]] ^= np.uint64(1)
        return found, vals, reads

    def put_batch(self, keys, vals):
        if self._fault == "state_unchanged":   # the write is dropped
            return
        return self._store.put_batch(keys, vals)


def _run(cell, bench, config, factory=None, trace=False, seconds=SECONDS):
    return harness.run_cell(cell, SEED, seconds, trace, root=ROOT, bench=bench,
                            cfg=config(cell), store_factory=factory,
                            require_chip=False, log=lambda line: None)


def _faulty(fault):
    def factory(cfg, keys, vals, seed):
        return Faulty(harness.build_store(cfg, keys, vals, seed), fault)
    return factory


@pytest.mark.parametrize("cell", ["ycsb_c.kv8m_chained", "ycsb_c.kv8m_bloom10",
                                  "ycsb_a.kv8m_chained"])
def test_sound_run_is_correct(cell, bench, small_config):
    r = _run(cell, bench, small_config)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["checks"]["pool_wraps"]["value"] == 0
    assert {"setup_s", "ops_per_s", "read_p99_ms"} <= set(r["metrics"])
    assert ("write_p99_ms" in r["metrics"]) == cell.startswith("ycsb_a")


def test_traced_write_mix_reads_its_counters(bench, small_config):
    r = _run("ycsb_a.kv8m_chained", bench, small_config, trace=True)
    assert r["correct"], r["checks"]
    bank = r["metrics"]["bank_bits_per_key"]["value"]
    assert 5 < bank < 100
    assert r["metrics"]["wasted_reads_per_get"]["value"] == 0


def test_window_that_outruns_the_pool_is_not_correct(bench, small_config,
                                                     monkeypatch):
    """A pool of a handful of requests is used up in the window: the
    clients replay it, and the run is refused."""
    from chipbench import spec
    traffic = dict(spec.traffic("ycsb_c"), pool_requests_per_s=2)
    monkeypatch.setattr(spec, "traffic", lambda name, here=spec.HERE: traffic)
    r = _run("ycsb_c.kv8m_chained", bench, small_config)
    assert not r["correct"] and r["checks"]["pool_wraps"]["value"] > 0


@pytest.mark.parametrize("cell", ["ycsb_c.kv8m_chained", "ycsb_a.kv8m_chained"])
def test_control_is_not_correct(cell, bench, small_config):
    r = _run(cell, bench, small_config, control_store)
    assert not r["correct"]
    assert r["checks"]["wrong_reads"]["value"] > 0


@pytest.mark.parametrize("cell,fault,number", [
    ("ycsb_c.kv8m_chained", "half_batch", "wrong_reads"),
    ("ycsb_c.kv8m_chained", "altered_answer", "wrong_reads"),
    ("ycsb_c.kv8m_bloom10", "altered_answer", "wrong_reads"),
    ("ycsb_a.kv8m_chained", "half_batch", "wrong_reads"),
    ("ycsb_a.kv8m_chained", "altered_answer", "wrong_reads"),
    ("ycsb_a.kv8m_chained", "state_unchanged", "lost_writes"),
])
def test_fault_is_not_correct(cell, fault, number, bench, small_config):
    r = _run(cell, bench, small_config, _faulty(fault))
    assert not r["correct"]
    assert r["checks"][number]["value"] > 0, r["checks"]

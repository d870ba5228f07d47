"""Trace reduction: device busy union, kernel time, idle-gap labels."""
import gzip
import json
from pathlib import Path

import pytest

from chipbench import traces
from chipbench.traces import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur))


def test_busy_is_union_of_device_ops_and_kernel_time_sums_module_runs():
    evs = [
        _ev(DEV, "XLA Modules", "jit_lsm_probe(42)", 0, 100),
        _ev(DEV, "XLA Ops", "gather.1", 0, 60),
        _ev(DEV, "XLA Ops", "fusion.2", 40, 60),       # overlaps: union 0..100
        _ev(DEV, "XLA Modules", "jit_lsm_probe(42)", 1000, 50),
        _ev(DEV, "XLA Ops", "gather.1", 1000, 50),
        _ev(HOST, "python", "client.get_batch", 90, 1000),
        _ev(HOST, "python", "client.put_batch", 300, 100),
        _ev(HOST, "python", "PjitFunction(lsm_probe)", 95, 10),
    ]
    s = traces.reduce(evs, window_s=2e-6)
    assert s.n_devices == 1
    assert s.busy_s == pytest.approx(150e-9)
    assert s.kernel_seconds("lsm_probe") == pytest.approx(150e-9)
    assert s.module_runs["lsm_probe"] == 2
    assert s.device_ops[0] == ["gather.1", pytest.approx(110e-9)]
    # one gap, 100..1000, with both client calls open across it
    assert s.idle_gaps == [["get_batch*1 put_batch*1", pytest.approx(900e-9)]]


def test_no_device_plane_reads_as_no_device():
    s = traces.reduce([_ev("/host:CPU", "python", "client.get_batch", 0, 10)], 1.0)
    assert s.n_devices == 0 and s.busy_s == 0.0 and s.idle_gaps == []


def test_op_name():
    long = "%fusion.5 = u32[7168]{0:T(1024)S(1)} fusion(u32[10401536]{0} %copy-done), kind=kCustom"
    assert traces.op_name(long) == "%fusion.5 = u32[7168]"
    assert traces.op_name("gather.1") == "gather.1"


def test_module_name():
    assert traces.module_name("jit_lsm_probe(123)") == "lsm_probe"
    assert traces.module_name("jit_lsm_chain_probe") == "lsm_chain_probe"


RECORDED = Path(__file__).parent / "data" / "v5e_ycsb_c_trace.json.gz"


def test_recorded_chip_trace():
    """A stretch of a traced ycsb_c run on one TPU v5e, as recorded."""
    rec = json.loads(gzip.open(RECORDED, "rt").read())
    evs = [Event(*e) for e in rec["events"]]
    s = traces.reduce(evs, rec["window_s"])
    assert s.n_devices == 1
    assert s.busy_s == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert s.kernel_seconds("lsm_probe") == pytest.approx(
        rec["expect"]["lsm_probe_s"], rel=1e-9)
    assert 0 < s.busy_s < s.window_s
    assert s.module_runs["lsm_probe"] >= 10
    assert s.idle_gaps and all("get_batch" in label for label, _ in s.idle_gaps)

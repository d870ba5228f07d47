"""Everything is found by name; a new mix is a new file and an entry."""
import json
import shutil

from chipbench import spec
from chipbench.tests.conftest import ROOT


def test_every_cell_finds_its_config_traffic_and_metrics(bench):
    for w in bench["workloads"]:
        cfg = spec.config(bench, w["config"], ROOT)
        assert cfg["record_count"] % cfg["store"]["memtable_capacity"] == 0
        t = spec.traffic(w["traffic"])
        assert t["clients"] >= 1 and abs(sum(t["mix"].values()) - 1) < 1e-9
        for trace in (False, True):
            ms = spec.metrics(bench, w["name"], trace)
            assert ms, (w["name"], trace)
            for m in ms:
                assert callable(spec.module("metrics", m["name"]).read)
    names = {m["name"] for m in spec.metrics(bench, "ycsb_c.kv8m_chained", False)}
    assert "write_p99_ms" not in names and "setup_s" in names
    assert spec.module("kernels", "lsm_probe").words_per_key(()) == 0


def test_new_traffic_file_and_cell_need_no_edit(tmp_path, bench):
    """Copy the benchmark, add a traffic file, a metric file and a cell
    entry, and find them: no existing file changes."""
    here = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, here, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(here): p.read_bytes() for p in here.rglob("*") if p.is_file()}
    t = json.loads((here / "traffic" / "ycsb_c.json").read_text())
    t["workload"] = "YCSB core workload B: 95% reads, 5% updates"
    t["mix"], t["ops"] = {"read": 0.95, "update": 0.05}, {"read": 128, "update": 128}
    (here / "traffic" / "ycsb_b.json").write_text(json.dumps(t))
    (here / "metrics" / "reads_per_s.py").write_text(
        "def read(run):\n    return 1.0\n")
    b = json.loads(json.dumps(bench))
    b["workloads"].append({"name": "ycsb_b.kv8m_chained", "config": "kv8m_chained",
                           "traffic": "ycsb_b", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "reads_per_s", "unit": "1/s", "better": "higher",
                           "source": "host_clock", "layer": "device", "moves": "ops_per_s",
                           "workloads": ["ycsb_b.kv8m_chained"]})
    assert spec.cell(b, "ycsb_b.kv8m_chained")["traffic"] == "ycsb_b"
    assert spec.traffic("ycsb_b", here)["mix"]["update"] == 0.05
    names = [m["name"] for m in spec.metrics(b, "ycsb_b.kv8m_chained", True)]
    assert "reads_per_s" in names
    assert spec.module("metrics", "reads_per_s", here).read(None) == 1.0
    after = {p.relative_to(here): p.read_bytes() for p in here.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert all(after[k] == v for k, v in before.items() if "__pycache__" not in k.parts)

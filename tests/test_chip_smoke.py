"""chip_smoke.py on the CPU: its phases at a tiny size, and its refusal to
report anything without a TPU or outside the repository."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
from jax.sharding import Mesh

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_store_phases_at_tiny_size():
    """Every phase of the one-chip smoke, on a CPU device: load, delete +
    compact, background ingest, then get/scan/plan checked against the
    numpy reference inside the phase function."""
    cs = _chip_smoke()
    lines = []
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    facts = cs.run_store_phases(n_tables=8, table_keys=512, batch=1024,
                                mesh=mesh, log=lines.append)
    assert facts["bank_bytes_loaded"] > 0 and facts["n_tables"] >= 2
    assert facts["get"]["reads_present"] == 512   # one read per present key
    assert facts["scan_keys"] == 1024 and facts["plan_rows"] > 0
    assert set(facts["seconds"]) == {
        "load", "get_batch (loaded)", "delete 1% + flush + compact",
        "background compaction + ingest", "get_batch", "scan",
        "catalog plan"}
    assert any(line.startswith("bank at read time") for line in lines)


def test_sharded_tag_probe_at_tiny_size():
    cs = _chip_smoke()
    facts = cs.run_sharded_tag_probe(n_keys=2048, devices=jax.devices()[:1],
                                     log=lambda line: None)
    assert facts == {"keys": 4096, "enrolled": 2048}


def test_main_refuses_without_a_tpu(capsys):
    cs = _chip_smoke()
    assert cs.main([]) == 1
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


def test_fails_outside_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout

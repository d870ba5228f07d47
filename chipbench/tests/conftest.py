"""Harness tests: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests``
from the checkout's root. They run on the CPU at small sizes; none of them
is part of the repository's own test suite."""
import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL_MEMTABLE = 4096


def small(cfg: dict) -> dict:
    """A configuration cut to a size the CPU runs in seconds: the same
    store settings and the same load shape (the records fill the write
    buffer twice), 8,192 records."""
    cfg = copy.deepcopy(cfg)
    cfg["store"]["memtable_capacity"] = SMALL_MEMTABLE
    cfg["record_count"] = 2 * SMALL_MEMTABLE
    return cfg


@pytest.fixture(scope="session")
def bench():
    from chipbench import spec
    return spec.load_benchmark(ROOT)


@pytest.fixture(scope="session")
def small_config(bench):
    from chipbench import spec

    def make(cell_name: str) -> dict:
        return small(spec.config(bench, spec.cell(bench, cell_name)["config"], ROOT))
    return make

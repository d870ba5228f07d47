"""Find a cell's configuration, traffic, metrics and kernel work counts by
name. Adding one is adding a file and a ``BENCHMARK.json`` entry."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent                       # the checkout


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    entry = _named(bench["configs"], name, "config")
    return json.loads((Path(root) / entry["file"]).read_text())


def traffic(name: str, here: Path = HERE) -> dict:
    return json.loads((Path(here) / "traffic" / f"{name}.json").read_text())


def metrics(bench: dict, cell_name: str, trace: bool) -> list[dict]:
    """The metrics a run of the cell reports: the end-to-end ones with
    ``--trace 0``, the per-layer ones with ``--trace 1``; an entry with a
    ``workloads`` list applies only to the cells it lists."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def module(kind: str, name: str, here: Path = HERE):
    """``chipbench/<kind>/<name>.py`` as a module (``kind``: metrics or
    kernels). Loaded by path, so a name may hold dots."""
    path = Path(here) / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    if spec is None or not path.exists():
        raise KeyError(f"no {kind} file {path.name}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

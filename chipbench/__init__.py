"""Chip benchmark of the filter-guarded key-value store.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it finds.
Everything is found by name: a configuration in ``configs/<name>.json``, a
traffic mix in ``traffic/<name>.json``, a metric in ``metrics/<name>.py``
and a kernel's work count in ``kernels/<name>.py``.
"""

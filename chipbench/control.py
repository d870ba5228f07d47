"""The control for ``correct``: the reference, with its values cut to 32
bits, put in the store's place and driven through a whole run of a cell.

    python3 chipbench/control.py --workload ycsb_c.kv8m_chained --seed 7 \\
        --seconds 5

A store that kept values in 32 bits (the step below the configuration's
8-byte values) breaks the guarantee that a read returns the value that
was written; the run must come out with ``correct`` false. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

CONTROL_VALUE_BITS = 32


def control_store(cfg, keys, vals, seed):
    from chipbench.reference import ReferenceStore
    return ReferenceStore(keys, vals, value_bits=CONTROL_VALUE_BITS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from chipbench import compiles, device, harness
    compiles.enable_cache(ROOT)
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds, False,
                                  root=ROOT, store_factory=control_store)
    except device.NoChip as exc:
        print(f"chipbench: {exc}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

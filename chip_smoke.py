"""Drive the store's main path once on a TPU and check every answer.

    python chip_smoke.py             # one chip: the store and query path
    python chip_smoke.py --chips 4   # four chips: the sharded tag-bank probe

With no option it loads a chained ``LsmStore`` with 2^23 keys in eight
flushed SSTables of 2^20 keys (a packed filter bank of more than 16 MiB on
the device; SSTable rows stay in host memory), deletes 1% of them, runs one
foreground ``compact()`` and then a background-compaction phase that
ingests more puts. It then reads through ``get_batch`` (present, absent
and deleted keys), one ``scan`` and one ``Catalog`` plan (tag index +
membership), and checks each answer against sorted numpy arrays of what
was written. With ``--chips 4`` it probes one tag bank of 2^20 keys on a
four-chip mesh and on a one-chip mesh, and the two must agree bit for bit.

Earlier lines give per-phase wall seconds and compilations (information,
not measurements). The last line is one JSON object naming the device.
The script fails, and prints no such line, when JAX finds no TPU.
JAX's compilation cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when that is
set, else to ``.jax_cache`` beside this file.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import hashing as H  # noqa: E402
from repro.core.othello import Othello  # noqa: E402
from repro.query.catalog import Catalog  # noqa: E402
from repro.query.pipeline import Member, Pipeline, TagEq  # noqa: E402
from repro.serving.filter_service import FilterService  # noqa: E402
from repro.storage.lsm_store import LsmStore  # noqa: E402

TAG_BITS = 4
PLAN_TAG = 5
MIB = 1 << 20


def value_of(keys: np.ndarray) -> np.ndarray:
    """The value written for each key (uint64 arithmetic wraps)."""
    return keys * np.uint64(0x9E3779B97F4A7C15) + np.uint64(1)


def tag_of(keys: np.ndarray, vals: np.ndarray) -> np.ndarray:
    """The tag index's function of a row: four bits of its value."""
    return (vals >> np.uint64(7)) & np.uint64((1 << TAG_BITS) - 1)


class CompileCounter:
    """Counts XLA programs compiled and loaded from the persistent cache,
    from JAX's monitoring events (listeners stay for the process)."""

    def __init__(self):
        self.programs = 0          # compile requests that reached XLA
        self.seconds = 0.0         # their wall time, cache loads included
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def compiled(self) -> int:
        return self.programs - self.cache_hits


class Phases:
    """Per-phase wall seconds (and compilations, given a counter)."""

    def __init__(self, log, counter: CompileCounter | None = None):
        self.log = log
        self.counter = counter
        self.seconds: dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        c0 = (self.counter.compiled, self.counter.seconds) if self.counter else None
        t0 = time.perf_counter()
        yield
        self.seconds[name] = dt = time.perf_counter() - t0
        line = f"phase {name}: {dt:.3f} s"
        if c0 is not None:
            line += (f", {self.counter.compiled - c0[0]} compiled "
                     f"({self.counter.seconds - c0[1]:.3f} s in compile calls)")
        self.log(line)


def _check_get(store: LsmStore, ref_k: np.ndarray, ref_v: np.ndarray,
               present: np.ndarray, absent: np.ndarray, deleted: np.ndarray):
    """One get_batch over present, absent and deleted keys; every answer
    must match the reference and the chained read bounds must hold."""
    q = np.concatenate([present, absent, deleted])
    kind = np.repeat([0, 1, 2], [len(present), len(absent), len(deleted)])
    perm = np.random.default_rng(len(q)).permutation(len(q))
    q, kind = q[perm], kind[perm]
    reads0 = store.stats.sstable_reads
    found, vals, reads = store.get_batch(q)
    pos = np.minimum(np.searchsorted(ref_k, q), len(ref_k) - 1)
    want = ref_k[pos] == q
    assert np.array_equal(found, want), "get_batch membership differs"
    assert np.array_equal(vals[want], ref_v[pos[want]]), "get_batch values differ"
    assert np.array_equal(want, kind == 0), "reference disagrees with its own split"
    assert int(reads[kind == 0].max(initial=0)) <= 1, ">1 read for a present key"
    assert int(reads[kind == 2].sum()) == 0, "a deleted key cost a read"
    assert store.stats.sstable_reads - reads0 == int(reads.sum())
    return {"keys": len(q), "reads": int(reads.sum()),
            "reads_present": int(reads[kind == 0].sum()),
            "reads_absent": int(reads[kind == 1].sum())}


def run_store_phases(*, n_tables: int, table_keys: int, batch: int, mesh,
                     seed: int = 0, log=print,
                     counter: CompileCounter | None = None) -> dict:
    """Load, change and read a chained LsmStore through its public API and
    check every answer against sorted numpy arrays of what was written.
    Raises AssertionError on any mismatch; returns what it saw."""
    phase = Phases(log, counter)
    rng = np.random.default_rng(seed)
    n_load, n_extra = n_tables * table_keys, table_keys
    universe = rng.permutation(H.random_keys(n_load + n_extra + batch,
                                             seed=seed))
    load_keys = universe[:n_load]
    extra_keys = universe[n_load:n_load + n_extra]
    absent = universe[n_load + n_extra:]
    facts: dict = {}

    with phase("load"):
        store = LsmStore(filter_kind="chained", memtable_capacity=table_keys,
                         auto_compact=False, seed=seed, mesh=mesh)
        for chunk in np.split(load_keys, n_tables):
            store.put_batch(chunk, value_of(chunk))   # full memtable: flush
        assert store.n_tables == n_tables, store.n_tables
        ref_k = np.sort(load_keys)
        ref_v = value_of(ref_k)
    facts["bank_bytes_loaded"] = store.generation.tables.nbytes
    log(f"bank after load: {facts['bank_bytes_loaded']} bytes in "
        f"{store.n_tables} SSTables of {table_keys} keys")

    none = np.empty(0, np.uint64)
    with phase("get_batch (loaded)"):
        facts["get_loaded"] = _check_get(
            store, ref_k, ref_v, rng.choice(ref_k, batch // 2, replace=False),
            absent[:batch // 2], none)

    with phase("delete 1% + flush + compact"):
        deleted = rng.choice(load_keys, n_load // 100, replace=False)
        store.delete_batch(deleted)
        store.flush()
        store.compact()
        keep = ~np.isin(ref_k, deleted)
        ref_k, ref_v = ref_k[keep], ref_v[keep]

    with phase("background compaction + ingest"):
        bg0 = store.stats.bg_compactions
        store.start_background()
        try:
            for chunk in np.split(extra_keys, 4):
                store.put_batch(chunk, value_of(chunk))
                store.flush()
            assert store.wait_compaction_idle(timeout_s=600.0), "compactor busy"
        finally:
            store.stop_background()
        assert store.background_errors == [], store.background_errors
        assert store.stats.bg_compactions > bg0, "background compactor idle"
        ref_k = np.concatenate([ref_k, np.sort(extra_keys)])
        order = np.argsort(ref_k)
        ref_k = ref_k[order]
        ref_v = value_of(ref_k)
    gen = store.generation
    facts["bank_bytes"] = gen.tables.nbytes
    facts["n_tables"] = gen.n_tables
    log(f"bank at read time: {facts['bank_bytes']} bytes in "
        f"{gen.n_tables} SSTables; {len(ref_k)} live keys")

    q_present = rng.choice(ref_k, batch // 2, replace=False)
    q_absent = absent[:batch // 4]
    q_deleted = deleted[:batch // 4]
    with phase("get_batch"):
        facts["get"] = _check_get(store, ref_k, ref_v, q_present, q_absent,
                                  q_deleted)

    with phase("scan"):
        i = int(rng.integers(0, len(ref_k) - batch))
        lo, hi = int(ref_k[i]), int(ref_k[i + batch])
        keys, vals = store.scan(lo, hi)
        assert np.array_equal(keys, ref_k[i:i + batch]), "scan keys differ"
        assert np.array_equal(vals, ref_v[i:i + batch]), "scan values differ"
        facts["scan_keys"] = len(keys)

    with phase("catalog plan"):
        catalog = Catalog()
        coll = catalog.create_collection("kv", store=store)
        coll.create_index("tag", tag_of, tag_bits=TAG_BITS, seed=seed)
        cands = np.concatenate([q_present, q_absent, q_deleted])
        res = Pipeline(coll, (TagEq("tag", PLAN_TAG), Member())).run(cands)
        pos = np.minimum(np.searchsorted(ref_k, cands), len(ref_k) - 1)
        live = ref_k[pos] == cands
        want = live & (tag_of(cands, ref_v[pos]) == np.uint64(PLAN_TAG))
        assert np.array_equal(res.keys, cands[want]), "plan keys differ"
        assert np.array_equal(res.vals, ref_v[pos[want]]), "plan values differ"
        assert int(res.reads.max(initial=0)) <= 1, ">1 read for a plan key"
        facts["plan_rows"] = len(res.keys)
    facts["seconds"] = phase.seconds
    return facts


def run_sharded_tag_probe(*, n_keys: int, devices, seed: int = 0,
                          log=print) -> dict:
    """One tag bank (TAG_BITS Othello planes over ``n_keys`` keys, as the
    query layer's tag index builds it) probed through ``FilterService`` on
    a mesh over ``devices`` and on a one-device mesh. The two must agree bit
    for bit and retrieve the tag of every enrolled key."""
    keys = H.random_keys(2 * n_keys, seed=seed)
    enrolled = keys[:n_keys]
    tags = tag_of(enrolled, value_of(enrolled))
    planes = [Othello.build(enrolled,
                            ((tags >> np.uint64(j)) & np.uint64(1)
                             ).astype(np.uint8), seed=seed + 131 * j)
              for j in range(TAG_BITS)]
    q = np.random.default_rng(seed).permutation(keys)
    member = {}
    for name, devs in (("sharded", devices), ("one_chip", devices[:1])):
        t0 = time.perf_counter()
        svc = FilterService(planes, mesh=Mesh(np.array(devs), ("data",)))
        member[name], _ = svc.probe(q)
        log(f"tag-bank probe on {len(devs)} device(s): {len(q)} keys, "
            f"{svc.bank.nbytes} bank bytes, "
            f"{time.perf_counter() - t0:.3f} s with compilation")
    assert np.array_equal(member["sharded"], member["one_chip"]), \
        "sharded and one-chip tag-bank probes differ"
    got = np.zeros(len(q), np.uint64)
    for j in range(TAG_BITS):
        got |= member["sharded"][j].astype(np.uint64) << np.uint64(j)
    mine = np.isin(q, enrolled)
    assert np.array_equal(got[mine], tag_of(q[mine], value_of(q[mine]))), \
        "tag bank retrieves a wrong tag for an enrolled key"
    return {"keys": len(q), "enrolled": n_keys}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded tag-bank probe and the "
                         "one-chip probe it is compared with")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    cache_dir = compile_cache.enable(ROOT / ".jax_cache")
    print(f"device: {dev.device_kind} x{len(devices)}; "
          f"compile cache: {cache_dir}", flush=True)
    counter = CompileCounter()

    def log(line):
        print(line, flush=True)

    if args.chips == 1:
        mesh = Mesh(np.array(devices[:1]), ("data",))
        facts = run_store_phases(n_tables=8, table_keys=1 << 20,
                                 batch=1 << 16, mesh=mesh, log=log,
                                 counter=counter)
        assert facts["bank_bytes"] > 16 * MIB, facts["bank_bytes"]
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        log(f"device peak_bytes_in_use: {peak}")
    else:
        facts = run_sharded_tag_probe(n_keys=1 << 20,
                                      devices=devices[:args.chips], log=log)
    log(f"compilations: {counter.compiled} compiled, {counter.cache_hits} "
        f"loaded from the cache, {counter.seconds:.3f} s in compile calls")
    log("facts: " + json.dumps(facts, sort_keys=True))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

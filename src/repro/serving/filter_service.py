"""Batched multi-filter probe engine: FilterBank + FilterService.

Paper mapping
-------------
- **§5.2 (shared address / locality).** The paper speeds up the two-stage
  ChainedFilter by making both stages' probes land in the same cache line.
  Here the same idea is lifted one level: ``FilterBank.pack`` flattens N
  heterogeneous filters (Bloom, Xor, ExactBloomier, ChainedFilterAnd,
  ChainedFilterCascade) into ONE 128-word-aligned uint32 buffer plus static
  layout descriptors (core.tables), so every fused probe gathers from a
  single device-resident table and each key batch is loaded exactly once
  per filter stack — never per layer.
- **§5.3 (cascade probing).** ``ChainedFilterCascade`` queries are served by
  the fused ``cascade_probe``: all Bloom layers and the first-zero-layer
  parity rule evaluate in one device program instead of one device
  dispatch per layer. It also reports the sequential probe
  count min(first_zero, L) — the number of layer touches a short-circuiting
  querier pays — which the service aggregates into its stats, mirroring the
  paper's memory-access accounting (Tab. 3 / Fig. 10).
- **§5.4 (LSM / tiered lookups).** ``TieredPrefixCache`` routes its
  stage-1 tier filters through a FilterService bank (``lookup_batch``):
  one batched probe decides which tiers fire for every key in the stream,
  preserving the ≤ 1 wasted-probe invariant per lookup.

Scale-out: key blocks are sharded across devices with ``shard_map`` over a
1-D ``data`` mesh (CPU multi-device via ``--xla_force_host_platform_
device_count`` in tests); the packed table buffer is replicated and each
device probes its own key rows.
"""
from __future__ import annotations

import functools
import threading
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.bloom import BloomFilter
from repro.core.bloomier import XorFilter, ExactBloomier
from repro.core.chained import ChainedFilterAnd, ChainedFilterCascade
from repro.core.lsm import ChainedTableFilter
from repro.core.othello import DynamicExactFilter
from repro.core.tables import (BloomTable, XorTable, ExactTable, OthelloTable,
                               ChainedAndLayout, CascadeLayout, LsmChainLayout,
                               concat_tables)
from repro.kernels import common
from repro.kernels.bloom_probe import bloom_probe
from repro.kernels.xor_probe import xor_probe, exact_probe
from repro.kernels.chained_probe import chained_probe
from repro.kernels.cascade_probe import cascade_probe
from repro.kernels.lsm_probe import lsm_chain_probe, othello_hit
from repro.kernels.ops import chained_and_params
from repro.core import hashing as H

_LAYOUT_TO_CLASS = {
    BloomTable: BloomFilter,
    XorTable: XorFilter,
    ExactTable: ExactBloomier,
    OthelloTable: DynamicExactFilter,
    ChainedAndLayout: ChainedFilterAnd,
    CascadeLayout: ChainedFilterCascade,
    LsmChainLayout: ChainedTableFilter,
}


# ---------------------------------------------------------------------------
# FilterBank — N heterogeneous filters in one packed buffer
# ---------------------------------------------------------------------------

@dataclass
class FilterBank:
    tables: np.ndarray                  # uint32 [W], 128-word aligned
    layouts: tuple                      # one FilterLayout per filter

    @classmethod
    def pack(cls, filters: list) -> "FilterBank":
        tables, layouts = concat_tables([f.to_tables() for f in filters])
        return cls(tables=tables, layouts=layouts)

    def unpack(self) -> list:
        """Reconstruct the filter objects (bit-identical query behaviour)."""
        out = []
        for lay in self.layouts:
            klass = _LAYOUT_TO_CLASS[type(lay)]
            out.append(klass.from_tables(self.tables, lay))
        return out

    @property
    def n_filters(self) -> int:
        return len(self.layouts)

    @property
    def nbytes(self) -> int:
        return self.tables.nbytes


# ---------------------------------------------------------------------------
# fused per-layout dispatch (single jit, layouts static)
# ---------------------------------------------------------------------------

def _probe_one(tables, hi2d, lo2d, lay):
    """-> (member, probes) int32 [R, 128] for one filter layout."""
    if isinstance(lay, BloomTable):
        m = bloom_probe(tables, hi2d, lo2d, m_bits=lay.m_bits, k=lay.k,
                        seed=lay.seed, offset=lay.offset)
        return m, jnp.ones_like(m)
    if isinstance(lay, XorTable):
        m = xor_probe(tables, hi2d, lo2d, mode=lay.mode, seed=lay.seed,
                      seg_len=lay.seg_len, n_seg=lay.n_seg, alpha=lay.alpha,
                      fp_seed=lay.fp_seed, offset=lay.offset)
        return m, jnp.ones_like(m)
    if isinstance(lay, ExactTable):
        m = exact_probe(tables, hi2d, lo2d, mode=lay.mode, seed=lay.seed,
                        seg_len=lay.seg_len, n_seg=lay.n_seg,
                        strategy=lay.strategy, bit_seed=lay.bit_seed,
                        offset=lay.offset)
        return m, jnp.ones_like(m)
    if isinstance(lay, OthelloTable):
        m = othello_hit(tables, hi2d, lo2d, ma=lay.ma, mb=lay.mb,
                        seed=lay.seed, offset_a=lay.offset,
                        offset_b=lay.offset_b).astype(jnp.int32)
        return m, jnp.ones_like(m)
    if isinstance(lay, LsmChainLayout):
        return lsm_chain_probe(tables, hi2d, lo2d, chain=lay.probe_params())
    if isinstance(lay, ChainedAndLayout):
        return chained_probe(tables, hi2d, lo2d, **chained_and_params(lay))
    if isinstance(lay, CascadeLayout):
        return cascade_probe(tables, hi2d, lo2d, layers=lay.probe_params())
    raise TypeError(f"unknown filter layout {type(lay).__name__}")


@functools.partial(jax.jit, static_argnames=("layouts",))
def bank_probe(tables, hi2d, lo2d, *, layouts: tuple):
    """Probe every filter in the bank on one key block.
    -> (member, probes) int32 [F, R, 128]."""
    members, probes = [], []
    for lay in layouts:
        m, p = _probe_one(tables, hi2d, lo2d, lay)
        members.append(m)
        probes.append(p)
    return jnp.stack(members), jnp.stack(probes)


# ---------------------------------------------------------------------------
# FilterService — batched query streams, device-sharded, double-buffered
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BankState:
    """One immutable published bank version: the packed buffer, its static
    layouts, and the jitted sharded probe closure, swapped as a UNIT.

    Static-function filters (Xor/Bloomier/Othello — Dietzfelbinger & Pagh;
    Graf & Lemire) cannot be mutated mid-probe, so consistency under
    concurrent rebuilds comes from versioned immutable states, not locks:
    a reader that captured a ``BankState`` keeps probing it bit-identically
    no matter how many newer versions publish after it."""

    bank: FilterBank
    tables: object                     # jnp uint32 [W] (device-resident)
    probe_fn: object                   # jitted shard_map'd bank_probe
    version: int                       # monotonically increasing

    @property
    def n_filters(self) -> int:
        return self.bank.n_filters


@dataclass
class ServiceStats:
    lookups: int = 0
    hits: np.ndarray = None            # int64 [F]
    probes: np.ndarray = None          # int64 [F] — sequential probe count

    def as_dict(self) -> dict:
        return {
            "lookups": self.lookups,
            "hits": self.hits.tolist(),
            "hit_rate": [h / max(1, self.lookups) for h in self.hits],
            "avg_probes": [p / max(1, self.lookups) for p in self.probes],
        }


class FilterService:
    """Serve batched membership queries against a packed FilterBank.

    ``probe(keys)`` evaluates every filter in the bank on the whole key
    batch in one jitted dispatch; rows are sharded across the mesh's
    ``data`` axis with shard_map (the table buffer is replicated).

    The service is **double-buffered**: the complete read state (packed
    buffer + layouts + jitted probe closure) lives in one immutable
    ``BankState``, and ``rebuild`` = ``prepare`` (build + jit-warm the new
    bank while the old state stays fully probe-able) + ``publish`` (ONE
    reference swap). A probe stream that captured the old state — e.g. a
    pinned storage generation — finishes against it unchanged."""

    def __init__(self, filters: list, *, mesh=None):
        if mesh is None:
            mesh = jax.make_mesh((jax.device_count(),), ("data",))
        self.mesh = mesh
        self._row_multiple = common.BLOCK_ROWS * self.mesh.devices.size
        # guards the (state, stats) PAIR: publishes swap both, and a probe
        # must attribute its counts to the version it actually probed even
        # when a background rebuild lands mid-call (always-on store)
        self._swap_lock = threading.Lock()
        self._state: BankState | None = None
        self.publish(self.prepare(filters))

    # -- double-buffered bank states -----------------------------------------
    @property
    def state(self) -> BankState:
        """The currently published BankState. Capture it to keep probing
        this exact bank version across later rebuilds (``probe(keys,
        state=captured)``)."""
        return self._state

    @property
    def version(self) -> int:
        return self._state.version if self._state is not None else -1

    @property
    def bank(self) -> FilterBank:
        return self._state.bank

    def prepare(self, filters: list, *, warm: bool = False) -> BankState:
        """Build the NEXT bank version off to the side — all while the
        published state keeps serving. With ``warm=True`` the sharded probe
        closure is additionally jit-compiled and warmed on a dummy block,
        so the first probe after ``publish`` pays no compilation stall
        (pass it when ``probe`` is the serving hot path; LsmStore banks
        probe through the fused ``lsm_probe`` kernel instead and skip it).
        Returns the staged state; nothing is visible to readers until
        ``publish``."""
        bank = FilterBank.pack(filters)
        bank.tables.setflags(write=False)      # immutable once staged
        tables = jnp.asarray(bank.tables)
        layouts = bank.layouts
        probe_fn = jax.jit(jax.shard_map(
            lambda t, h, l: bank_probe(t, h, l, layouts=layouts),
            mesh=self.mesh,
            in_specs=(P(), P("data", None), P("data", None)),
            out_specs=(P(None, "data", None), P(None, "data", None)),
            check_vma=False,
        ))
        if warm:
            # jit-warm: trace + compile now, so the first probe after
            # publish pays no compilation stall
            z = jnp.zeros((self._row_multiple, common.BLOCK_COLS), jnp.uint32)
            jax.block_until_ready(probe_fn(tables, z, z))
        return BankState(bank=bank, tables=tables, probe_fn=probe_fn,
                         version=self.version + 1)

    def publish(self, state: BankState) -> None:
        """Atomically install a staged state as the serving bank — the
        (state, stats) pair swaps under one small lock; in-flight readers
        that captured the previous state finish against it. Stats reset
        (the caller owns cross-version accounting)."""
        stats = ServiceStats(
            hits=np.zeros(state.bank.n_filters, np.int64),
            probes=np.zeros(state.bank.n_filters, np.int64))
        with self._swap_lock:
            self._state = state
            self.stats = stats

    # -- batched probing -----------------------------------------------------
    def _block_keys(self, keys: np.ndarray):
        hi, lo = H.np_split_u64(np.asarray(keys, dtype=np.uint64))
        hi2d, lo2d, n = common.blockify(hi, lo)
        pad_rows = (-hi2d.shape[0]) % self._row_multiple
        if pad_rows:
            z = np.zeros((pad_rows, common.BLOCK_COLS), np.uint32)
            hi2d = np.concatenate([hi2d, z])
            lo2d = np.concatenate([lo2d, z])
        return jnp.asarray(hi2d), jnp.asarray(lo2d), n

    def probe(self, keys: np.ndarray, state: BankState | None = None
              ) -> tuple[np.ndarray, np.ndarray]:
        """-> (member bool [F, n], probes int [F, n]) for n keys across the
        bank's F filters; updates hit/probe stats. Pass a captured ``state``
        to probe an OLDER published bank version bit-identically (stats are
        left untouched for non-current states — cross-version accounting
        belongs to the caller)."""
        with self._swap_lock:              # capture the PAIR coherently: a
            cur_state = self._state        # publish racing this call cannot
            cur_stats = self.stats         # tear probe from its accounting
        current = state is None or state is cur_state
        if state is None:
            state = cur_state
        if len(keys) == 0:
            shape = (state.n_filters, 0)
            return np.zeros(shape, bool), np.zeros(shape, np.int32)
        hi2d, lo2d, n = self._block_keys(keys)
        member, probes = state.probe_fn(state.tables, hi2d, lo2d)
        member = np.asarray(member).reshape(state.n_filters, -1)[:, :n]
        probes = np.asarray(probes).reshape(state.n_filters, -1)[:, :n]
        member = member.astype(bool)
        if current:
            # accumulate into the stats snapshot paired with the probed
            # state: counts land on the version they measured even if a
            # newer bank published while the kernel ran
            with self._swap_lock:
                cur_stats.lookups += n
                cur_stats.hits += member.sum(axis=1)
                cur_stats.probes += probes.sum(axis=1)
        return member, probes

    def probe_filter(self, index: int, keys: np.ndarray) -> np.ndarray:
        """Membership for ONE filter of the bank -> bool [n]. Dispatches only
        that filter's kernel and leaves the aggregate stats untouched."""
        if len(keys) == 0:
            return np.zeros(0, bool)
        state = self._state
        hi2d, lo2d, n = self._block_keys(keys)
        member, _ = bank_probe(state.tables, hi2d, lo2d,
                               layouts=(state.bank.layouts[index],))
        return np.asarray(member).reshape(-1)[:n].astype(bool)

    def refresh_tables(self, filters: list) -> None:
        """Re-pack mutated filter contents into a NEW published state. Valid
        only while every filter's layout (sizes, seeds, offsets) is
        unchanged — e.g. Bloom bit-flips from inserts or Othello exclusions
        that did not resize — so the jitted probe closure and its
        compilation cache survive (the new state reuses it). Packing calls
        each filter's ``to_tables``, which is where batched Othello
        exclusions materialize their lazily-flipped components — one refresh
        per flush folds a whole batch of online updates into the device
        buffer. The previous state's buffer is never touched: readers
        pinned to it keep probing the old contents. Stats are kept
        (content-only refresh)."""
        old = self._state
        bank = FilterBank.pack(filters)
        if bank.layouts != old.bank.layouts:
            raise ValueError("filter layouts changed; build a new FilterService")
        bank.tables.setflags(write=False)
        state = BankState(bank=bank, tables=jnp.asarray(bank.tables),
                          probe_fn=old.probe_fn, version=old.version + 1)
        with self._swap_lock:
            self._state = state

    def rebuild(self, filters: list, *, warm: bool = False) -> None:
        """Structural refresh (filters added/removed/resized), double-
        buffered: ``prepare`` builds (and with ``warm=True`` jit-warms) the
        next state while the published one keeps serving, then ``publish``
        swaps one reference. Stats reset — the caller owns
        cross-generation accounting. Prefer ``refresh_tables`` when the
        layouts are unchanged (it keeps the compilation cache)."""
        self.publish(self.prepare(filters, warm=warm))

    def unpack(self) -> list:
        return self.bank.unpack()


# ---------------------------------------------------------------------------
# BankRegistry — named multi-tenant FilterServices
# ---------------------------------------------------------------------------

class BankRegistry:
    """Named FilterServices under one roof — the multi-tenant bank surface.

    One serving process holds many independent banks: per-collection LSM
    probe banks, per-index tag-retrieval banks, prefix-cache tiers. The
    registry maps stable names ("collection/index") to their services so
    the query layer can resolve banks by name, enumerate them, and
    aggregate stats without threading service handles through every plan.
    Registration is by reference — rebuilds/publishes on the service are
    visible immediately; the registry never copies bank state."""

    def __init__(self):
        self._services: dict[str, FilterService] = {}

    def register(self, name: str, service: FilterService) -> None:
        if name in self._services:
            raise ValueError(f"bank {name!r} already registered")
        self._services[name] = service

    def unregister(self, name: str) -> None:
        del self._services[name]

    def get(self, name: str) -> FilterService:
        try:
            return self._services[name]
        except KeyError:
            raise KeyError(
                f"no bank named {name!r}; registered: {self.names()}"
            ) from None

    def __contains__(self, name: str) -> bool:
        return name in self._services

    def names(self) -> list[str]:
        return sorted(self._services)

    def stats(self) -> dict:
        """{name: per-service stats dict} across every registered bank."""
        return {name: svc.stats.as_dict()
                for name, svc in sorted(self._services.items())}

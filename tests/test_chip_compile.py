"""Compile the device probes for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX, so it can compile for a v5e:2x2
topology that is only described. A probe the chip's compiler refuses (a
whole-bank VMEM block, a gather Mosaic cannot lower) fails here at no chip
time. The bank is 2^22 words (16 MiB), more than any VMEM-resident design
fits. The topology is described inside a fixture: only one process at a
time may load the TPU library, and only the worker that runs this file
does.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.core import hashing as H
from repro.core.bloom import BloomFilter
from repro.core.bloomier import XorFilter, ExactBloomier
from repro.core.chained import ChainedFilterAnd, ChainedFilterCascade
from repro.core.lsm import ChainedTableFilter
from repro.core.othello import Othello
from repro.kernels.bloom_probe import bloom_probe
from repro.kernels.cascade_probe import cascade_probe
from repro.kernels.chained_probe import chained_probe
from repro.kernels.lsm_probe import lsm_chain_probe, lsm_probe
from repro.kernels.ops import chained_and_params
from repro.kernels.xor_probe import exact_probe, xor_probe
from repro.serving.filter_service import FilterService
from repro.storage.lsm_store import LsmStore

BANK_WORDS = 1 << 22          # 16 MiB of uint32
ROWS = 512                    # 65,536 keys: the smoke's get_batch
KEYS = H.random_keys(12_000, seed=29)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True, scope="module")
def _no_compile_cache():
    """A compile for a described chip cannot be read back without one, so
    it stays out of any persistent cache the environment names."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _compile(fn, sharding, *extra, **static):
    words = _sds((BANK_WORDS,), sharding)
    keys = _sds((ROWS, 128), sharding)
    return fn.lower(words, keys, keys, *extra, **static).compile()


def test_fused_lsm_probe_compiles_for_v5e(one_chip):
    """The store's fused probe, with the chains of a store built here
    (three flushed tables and a tombstone-only one)."""
    store = LsmStore(seed=3, memtable_capacity=10 ** 9, auto_compact=False)
    for part in np.split(KEYS[:9000], 3):
        store.put_batch(part, part)
        store.flush()
    store.delete_batch(KEYS[:50])
    store.flush()
    gen = store.generation
    assert gen.n_tables == 4
    params = _sds(gen.params.shape, one_chip)
    compiled = _compile(lsm_probe, one_chip, params, chains=gen.chains)
    assert compiled.memory_analysis() is not None


def _other_probes():
    pos, neg = KEYS[:2000], KEYS[2000:10000]
    bloom = BloomFilter.build(pos, 0.01, seed=1)
    xor = XorFilter.build(pos, 8, seed=2)
    exact = ExactBloomier.build(pos, neg, seed=3)
    _, chained = ChainedFilterAnd.build(pos, neg, seed=4).to_tables()
    _, cascade = ChainedFilterCascade.build(pos[:500], neg, seed=5).to_tables()
    _, lsm = ChainedTableFilter.build(pos, neg, fp_alpha=7, seed1=6,
                                      seed2=7).to_tables()
    xl, el = xor.tbl.layout, exact.tbl.layout
    return {
        "bloom_probe": (bloom_probe, dict(m_bits=bloom.m_bits, k=bloom.k,
                                          seed=bloom.seed)),
        "xor_probe": (xor_probe, dict(mode=xl.mode, seed=xl.seed,
                                      seg_len=xl.seg_len, n_seg=xl.n_seg,
                                      alpha=8, fp_seed=xor.fp_seed)),
        "exact_probe": (exact_probe, dict(mode=el.mode, seed=el.seed,
                                          seg_len=el.seg_len, n_seg=el.n_seg,
                                          strategy=exact.strategy,
                                          bit_seed=exact.bit_seed)),
        "chained_probe": (chained_probe, chained_and_params(chained)),
        "cascade_probe": (cascade_probe, dict(layers=cascade.probe_params())),
        "lsm_chain_probe": (lsm_chain_probe, dict(chain=lsm.probe_params())),
    }


@pytest.mark.parametrize("name", ["bloom_probe", "xor_probe", "exact_probe",
                                  "chained_probe", "cascade_probe",
                                  "lsm_chain_probe"])
def test_probe_compiles_for_v5e(one_chip, name):
    fn, static = _other_probes()[name]
    _compile(fn, one_chip, **static)


@pytest.mark.parametrize("n_chips", [1, 4])
def test_sharded_tag_bank_probe_compiles_for_v5e(topo, n_chips):
    """FilterService's row-sharded probe of a query-layer tag bank (Othello
    planes) on a one- and a four-chip ``data`` mesh."""
    bits = (KEYS & np.uint64(15)).astype(np.uint8)
    planes = [Othello.build(KEYS, (bits >> j) & 1, seed=j) for j in range(4)]
    mesh = Mesh(np.array(topo.devices[:n_chips]), ("data",))
    svc = FilterService(planes, mesh=mesh)
    state = svc.state
    rows = NamedSharding(mesh, P("data", None))
    compiled = state.probe_fn.lower(
        _sds((BANK_WORDS,), NamedSharding(mesh, P())),
        _sds((ROWS, 128), rows), _sds((ROWS, 128), rows)).compile()
    member = compiled.output_shardings[0]
    assert member.num_devices == n_chips

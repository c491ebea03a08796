"""The engine's jitted stages and the flowhash kernel compile for one
TPU v5e chip at the sizes ``chip_smoke.py`` and the benchmark's cells
run.

The chip is described, not attached: XLA:TPU compiles here and refuses
what the chip's compiler would refuse (an unsupported op or dtype, a
program that does not fit the device), at no chip time.  The topology is
described inside a fixture, so only the worker that runs this file loads
the TPU library; where it cannot be described, the tests skip from there.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import (
    bipartite_pairs, build_paper_testbed, compile_fabric, resolve_flows,
    server_name,
)
from repro.core import jax_engine as je
from repro.kernels.flowhash.kernel import bulk_hash_seeded_kernel

FLOWS, SEEDS, HOPS = 4096, 1024, 4      # the paper-fim/throughput chunk
MAX_HOPS = 16
SPRAY_COLUMNS = 7168                    # prime-spray-elephant flowlets


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(one_chip, no_persistent_cache):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return sds


@pytest.fixture(scope="module")
def comp():
    return compile_fabric(build_paper_testbed())


def _compiles(lowered):
    compiled = lowered.compile()
    assert compiled.memory_analysis() is not None
    return compiled


def test_walk_compiles_for_v5e(chip, comp):
    """The walk over the route tables of FLOWS testbed flows."""
    wl = bipartite_pairs([server_name(i) for i in range(8)],
                         [server_name(8 + i) for i in range(8)],
                         flows_per_pair=FLOWS // 16)
    flows = resolve_flows(comp, wl)
    src, _, skey, dkey = comp.flow_endpoint_ids(flows)
    hops, exits, widths, shift, base, inverse = je._route_tables_host(
        comp, src, skey, dkey, MAX_HOPS)
    assert len(hops) == HOPS
    flow_rows = tuple(tuple(chip((FLOWS, *a.shape[1:]), a.dtype)
                            for a in hop) for hop in hops)
    with jax.enable_x64(True):
        _compiles(je._walk_fn().lower(
            flow_rows, chip((FLOWS, exits.shape[1]), jnp.int32),
            chip((FLOWS, 5), jnp.uint64), chip((SEEDS,), jnp.uint64), None,
            hash_backend="murmur", shift=shift, base=base))


def test_wave_walk_compiles_for_v5e(chip, comp):
    seeds = 64                          # the paper-wave phase
    tabs = [chip(a.shape, a.dtype) for a in (
        comp.cand, comp.cand_n, comp.dev_crc, comp.is_server,
        comp.link_dst)]
    flow = chip((FLOWS,), jnp.int32)
    with jax.enable_x64(True):
        _compiles(je._wave_walk_fn().lower(
            *tabs, flow, flow, flow, chip((FLOWS, 5), jnp.uint64),
            chip((seeds,), jnp.uint64),
            chip((seeds, comp.num_links), jnp.float64),
            max_hops=MAX_HOPS, hash_backend="murmur", n_fields=5,
            cool=False, near=False))


@pytest.mark.parametrize("unit", [True, False], ids=["unit", "weighted"])
@pytest.mark.parametrize("hops, flows, seeds, links", [
    (4, 256, 16384, 256),               # a paper-ecmp-fim pass
    (6, 21504, 128, 5760),              # a meta-3tier-2pod-fim pass
], ids=["paper-fim", "meta-fim"])
def test_counts_compiles_for_v5e(chip, unit, hops, flows, seeds, links):
    """Both counting paths at the FIM cells' pass shapes, each within
    1 GB of device memory."""
    weights = None if unit else chip((flows,), jnp.float64)
    with jax.enable_x64(True):
        compiled = _compiles(je._counts_fn().lower(
            chip((hops, flows, seeds), jnp.int32), weights, L=links,
            unit=unit))
    ma = compiled.memory_analysis()
    assert (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes) < 1e9


@pytest.mark.parametrize("only_used_leaves", [False, True])
def test_fim_compiles_for_v5e(chip, comp, only_used_leaves):
    seeds = 16384                       # the paper-ecmp-fim chunk
    L, NL = comp.num_links, len(comp.layer_names)
    with jax.enable_x64(True):
        _compiles(je._fim_fn().lower(
            chip((seeds, L), jnp.float64), chip((NL, L), jnp.bool_),
            chip((L,), jnp.int32), chip((L,), jnp.int32),
            chip((), jnp.int64), only_used_leaves=only_used_leaves,
            num_devices=comp.num_devices))


def test_fill_compiles_for_v5e(chip, comp):
    with jax.enable_x64(True):
        compiled = _compiles(je._fill_fn().lower(
            chip((HOPS, FLOWS, SEEDS), jnp.int32),
            chip((FLOWS,), jnp.float64),
            chip((comp.num_links,), jnp.float64)))
    # the chunk's working set stays within the per-cell budget that
    # sizes the fused front ends' seed chunks
    ma = compiled.memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert used <= HOPS * FLOWS * SEEDS * je._FILL_BYTES_PER_CELL


def test_exposure_compiles_for_v5e(chip):
    cols = (SPRAY_COLUMNS, SEEDS)
    with jax.enable_x64(True):
        _compiles(je._exposure_fn().lower(
            chip(cols, jnp.int64), chip(cols, jnp.float64),
            chip((SPRAY_COLUMNS,), jnp.int32), n=FLOWS))


def test_flowhash_kernel_compiles_for_v5e(chip):
    rows = 65536
    compiled = _compiles(jax.jit(
        lambda f, s: bulk_hash_seeded_kernel(f, s, block=4096)).lower(
            chip((rows, 5), jnp.uint32), chip((rows, 1), jnp.uint32)))
    assert "tpu_custom_call" in compiled.as_text()


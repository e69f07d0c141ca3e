"""Native TPU compiles of the main path's Pallas kernels at main-path sizes.

Each test compiles one kernel with ``interpret=False`` for a described (not
attached) TPU v5e and asserts the Mosaic kernel is in the program
(``tpu_custom_call``). A refusal here is what the chip's compiler would
raise. Sizes follow the scale-30 catalog at p=8: 3.0M ``store_sales`` rows
(375k per partition, 750k after a capacity-2 shuffle) probing filters
built from the 12k-row ``customer`` dimension (3k rows per shuffled
partition, a 131072-bit bloom filter). Nothing runs: the compiles say
nothing about results or times.

The topology is described inside a fixture: only one process at a time
may load the TPU compiler's library, so the file never touches it while
modules are imported.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.kernels.bitonic_sort import MAX_TILE, bitonic_sort_tile
from repro.kernels.bloom import bloom_build, bloom_probe
from repro.kernels.partition_hist import partition_hist
from repro.kernels.tiled_probe import tiled_probe, tiled_probe3
from repro.kernels.zone_map import key_range
from repro.joins.local_join import hash_join

P = 8
FACT_ROWS = 3_000_000
PROBE_CAP = 750_000          # store_sales rows per partition after shuffle
BUILD_CAP = 3_000            # customer rows per partition after shuffle
M_BITS, K = 131_072, 8       # bloom_params(12_000)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def compile_native(one_chip, no_persistent_cache):
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()
    return run


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


I32, BOOL, U32 = jnp.int32, jnp.bool_, jnp.uint32


@pytest.mark.parametrize("nd", [4, P, 128])
def test_partition_hist_compiles(compile_native, nd):
    _assert_kernel(compile_native(
        lambda d: partition_hist(d, nd=nd, interpret=False),
        ((FACT_ROWS,), I32)))


@pytest.mark.parametrize("rows", [BUILD_CAP, PROBE_CAP])
def test_bloom_build_compiles(compile_native, rows):
    _assert_kernel(compile_native(
        lambda k, v: bloom_build(k, v, m_bits=M_BITS, k=K, interpret=False),
        ((P, rows), I32), ((P, rows), BOOL)))


def test_bloom_probe_compiles(compile_native):
    _assert_kernel(compile_native(
        lambda k, b: bloom_probe(k, b, k=K, interpret=False),
        ((P, PROBE_CAP), I32), ((M_BITS // 32,), U32)))


@pytest.mark.parametrize("rows", [BUILD_CAP, PROBE_CAP])
def test_key_range_compiles(compile_native, rows):
    _assert_kernel(compile_native(
        lambda k, v: key_range(k, v, interpret=False),
        ((P, rows), I32), ((P, rows), BOOL)))


def test_tiled_probe_compiles(compile_native):
    _assert_kernel(compile_native(
        lambda a, b: tiled_probe(a, b, interpret=False),
        ((65_536,), I32), ((65_536,), I32)))


@pytest.mark.parametrize("buckets,cap_a,cap_b", [
    (93, 4_096, 129),
    # A broadcast hash join of 375k store_sales rows per partition with the
    # 12k-row customer table: 375 buckets of 4000 probe and 128 build slots.
    (375, 4_000, 128)])
def test_tiled_probe_compiles_under_vmap(compile_native, buckets, cap_a,
                                         cap_b):
    """The hash join's kernel path: one probe per (partition, bucket)."""
    _assert_kernel(compile_native(
        jax.vmap(lambda a, b: tiled_probe(a, b, interpret=False)),
        ((P, buckets, cap_a), I32), ((P, buckets, cap_b), I32)))


def test_tiled_probe3_compiles(compile_native):
    _assert_kernel(compile_native(
        jax.vmap(lambda a1, a2, b, c: tiled_probe3(a1, a2, b, c,
                                                   interpret=False)),
        ((P, 65_536), I32), ((P, 65_536), I32), ((P, 4_096), I32),
        ((P, 2_048), I32)))


@pytest.mark.parametrize("n", [8, MAX_TILE])
def test_bitonic_sort_tile_compiles(compile_native, n):
    _assert_kernel(compile_native(
        lambda k, v: bitonic_sort_tile(k, v, interpret=False),
        ((n,), I32), ((n,), I32)))


def test_bitonic_sort_tile_compiles_under_vmap(compile_native):
    """The sort join's kernel path: one tile sort per partition."""
    _assert_kernel(compile_native(
        jax.vmap(lambda k, v: bitonic_sort_tile(k, v, interpret=False)),
        ((P, MAX_TILE), I32), ((P, MAX_TILE), I32)))


def test_hash_join_kernel_path_compiles(compile_native, monkeypatch):
    """``hash_join(use_kernel=True)`` as the executor runs it, vmapped over
    partitions; the backend here is the CPU, so the test steers ``ops``
    to the native kernels itself."""
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    _assert_kernel(compile_native(
        jax.vmap(lambda ak, av, bk, bv: hash_join(ak, av, bk, bv,
                                                  use_kernel=True)),
        ((P, 16_384), I32), ((P, 16_384), BOOL), ((P, BUILD_CAP), I32),
        ((P, BUILD_CAP), BOOL)))

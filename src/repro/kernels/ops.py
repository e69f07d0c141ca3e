"""Public jit'd wrappers around the Pallas kernels.

On a TPU backend the kernels compile natively; on any other backend (the
CPU CI container) they run in interpret mode (the kernel body executes in
Python, validating the exact TPU program). The backend decides, never a
caller: code outside ``repro.kernels`` uses these wrappers, and the kernel
functions themselves take a required ``interpret`` flag.

Each wrapper counts its calls from Python as ``obs`` counter
``kernel.<name>``: one per eager call, one per trace under jit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import obs
from . import bloom as _bloom
from . import zone_map as _zone_map
from .bitonic_sort import MAX_TILE, bitonic_sort_tile
from .partition_hist import partition_hist
from .tiled_probe import tiled_probe, tiled_probe3

# Kernel-free halves of the zone-map filter (plain XLA compares/reduces).
merge_ranges = _zone_map.merge_ranges
range_probe = _zone_map.range_probe


@functools.cache
def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def probe(a_keys: jax.Array, b_keys: jax.Array) -> jax.Array:
    """First-match index of each probe key in the build keys (-1 if none),
    per row of any leading batch dimensions."""
    obs.count("kernel.tiled_probe")
    return tiled_probe(a_keys, b_keys, interpret=_interpret())


def probe3(a1_keys: jax.Array, a2_keys: jax.Array, b_keys: jax.Array,
           c_keys: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fused two-build first-match probe (hypercube 3-way local join)."""
    obs.count("kernel.tiled_probe3")
    return tiled_probe3(a1_keys, a2_keys, b_keys, c_keys,
                        interpret=_interpret())


def hist(dest: jax.Array, nd: int) -> jax.Array:
    """Partition-destination histogram (skew/capacity statistics)."""
    obs.count("kernel.partition_hist")
    return partition_hist(dest, nd=nd, interpret=_interpret())


def bloom_build(keys: jax.Array, valid: jax.Array | None = None, *,
                m_bits: int, k: int) -> jax.Array:
    """Bit-packed (m_bits/32,) uint32 bloom filter of the valid keys."""
    obs.count("kernel.bloom_build")
    return _bloom.bloom_build(keys, valid, m_bits=m_bits, k=k,
                              interpret=_interpret())


def bloom_probe(keys: jax.Array, bits: jax.Array, *, k: int) -> jax.Array:
    """Keep-mask of ``keys`` against a ``bloom_build`` filter."""
    obs.count("kernel.bloom_probe")
    return _bloom.bloom_probe(keys, bits, k=k, interpret=_interpret())


def key_range(keys: jax.Array, valid: jax.Array | None = None) -> jax.Array:
    """(min, max) of the valid keys: the zone-map build."""
    obs.count("kernel.key_range")
    return _zone_map.key_range(keys, valid, interpret=_interpret())


def sort_pairs(keys: jax.Array, values: jax.Array):
    """Ascending sort of int32 (key, value) pairs.

    Uses the in-VMEM bitonic kernel for power-of-two tiles up to MAX_TILE
    (the TPU tile primitive); falls back to XLA variadic sort for other
    shapes (which XLA itself lowers to a bitonic network on TPU).
    """
    n = keys.shape[0]
    if n and not (n & (n - 1)) and n <= MAX_TILE:
        obs.count("kernel.bitonic_sort_tile")
        return bitonic_sort_tile(keys, values, interpret=_interpret())
    order = jnp.argsort(keys)
    return keys[order], values[order]

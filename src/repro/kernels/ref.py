"""Oracles for every Pallas kernel: pure jnp (allclose targets in tests) and,
for the bloom and zone-map filters, pure numpy."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .bloom import BLOOM_SEED_1, BLOOM_SEED_2
from .zone_map import _HI_IDENT, _LO_IDENT


def tiled_probe_ref(a_keys: jnp.ndarray, b_keys: jnp.ndarray) -> jnp.ndarray:
    """out[i] = first j with b_keys[j] == a_keys[i], else -1 (O(Na*Nb))."""
    eq = a_keys[:, None] == b_keys[None, :]
    nb = b_keys.shape[0]
    col = jnp.arange(nb, dtype=jnp.int32)[None, :]
    big = jnp.iinfo(jnp.int32).max
    first = jnp.min(jnp.where(eq, col, big), axis=1)
    return jnp.where(first == big, -1, first).astype(jnp.int32)


def partition_hist_ref(dest: jnp.ndarray, nd: int) -> jnp.ndarray:
    """counts[k] = #{i : dest[i] == k} (dest < 0 ignored)."""
    valid = (dest >= 0).astype(jnp.int32)
    return jnp.bincount(jnp.where(valid == 1, dest, 0), weights=valid,
                        length=nd).astype(jnp.int32)


def _np_hash32(keys, seed: int):
    with np.errstate(over="ignore"):
        h = keys.astype(np.uint32) * np.uint32(seed)
        h ^= h >> np.uint32(15)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(13)
    return h


def _np_positions(flat, m_bits: int, k: int):
    """(k, n) bloom bit positions, double hashing as in the kernels."""
    h1 = _np_hash32(flat, BLOOM_SEED_1)
    h2 = _np_hash32(flat, BLOOM_SEED_2) | np.uint32(1)
    with np.errstate(over="ignore"):
        return np.stack([(h1 + np.uint32(i) * h2) & np.uint32(m_bits - 1)
                         for i in range(k)])


def bloom_build_ref(keys, valid=None, *, m_bits: int, k: int):
    """Pure-numpy reference of ``bloom_build``."""
    flat = np.asarray(keys, dtype=np.int32).reshape(-1)
    v = (np.ones(flat.shape, bool) if valid is None
         else np.asarray(valid, bool).reshape(-1))
    words = np.zeros(m_bits // 32, np.uint32)
    pos = _np_positions(flat[v], m_bits, k).reshape(-1)
    np.bitwise_or.at(words, pos >> 5, np.uint32(1) << (pos & np.uint32(31)))
    return words


def bloom_probe_ref(keys, words, *, k: int):
    """Pure-numpy reference of ``bloom_probe``: all k probed bits set."""
    words = np.asarray(words, np.uint32)
    flat = np.asarray(keys, dtype=np.int32).reshape(-1)
    pos = _np_positions(flat, words.shape[0] * 32, k)
    bit = (words[pos >> 5] >> (pos & np.uint32(31))) & np.uint32(1)
    return bit.all(axis=0).reshape(np.shape(keys))


def key_range_ref(keys, valid=None):
    """Pure-numpy reference of ``key_range``."""
    flat = np.asarray(keys, dtype=np.int32).reshape(-1)
    v = (np.ones(flat.shape, bool) if valid is None
         else np.asarray(valid, bool).reshape(-1))
    live = flat[v]
    if live.size == 0:
        return np.array([_LO_IDENT, _HI_IDENT], np.int32)
    return np.array([live.min(), live.max()], np.int32)

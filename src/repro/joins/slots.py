"""Slotted scatter — the static-shape primitive behind shuffle and radix
bucketing.

Given per-row destination ids, place each valid row into a fixed-capacity
slot array ``(nd, cap)`` of *source row indices* (-1 = empty). Rows beyond a
destination's capacity are dropped and counted as overflow — the engine's
skew signal (DESIGN.md: capacity-factor + hot-key detection).

Pure per-partition function: used under vmap (global view) and inside
shard_map (distributed executor) unchanged.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

# Distinct multiplicative mix seeds: shuffle destinations and radix buckets
# must be decorrelated or post-shuffle partitions would collapse into a few
# buckets (murmur3 finalizer constants).
SHUFFLE_SEED = jnp.uint32(0x9E3779B1)
BUCKET_SEED = jnp.uint32(0x85EBCA6B)


def pair_capacity(cap: int, nd: int, factor: float = 2.0) -> int:
    """Slot capacity per (source, destination) pair.

    Mean occupancy is cap/nd; the binomial tail needs ~sqrt slack for small
    partitions, on top of the user's skew ``factor`` (paper §3.7 maps skew
    handling to capacity sizing).
    """
    mean = cap / nd
    return max(8, int(mean * factor + 4.0 * mean ** 0.5 + 8))


def hash32(keys: jax.Array, seed: jax.Array) -> jax.Array:
    """Murmur-style avalanche of int32 keys -> uint32 hashes."""
    h = keys.astype(jnp.uint32) * seed
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 13)
    return h


class SlotScatter(NamedTuple):
    idx: jax.Array       # (nd, cap) int32 source row index, -1 = empty
    overflow: jax.Array  # () int32 number of dropped valid rows


@functools.partial(jax.jit, static_argnames=("nd", "cap"))
def slot_scatter(dest: jax.Array, valid: jax.Array, nd: int, cap: int
                 ) -> SlotScatter:
    """Group rows by destination into fixed slots.

    dest: (n,) int32 in [0, nd); valid: (n,) bool.
    """
    n = dest.shape[0]
    d = jnp.where(valid, dest, nd).astype(jnp.int32)  # invalid -> virtual bin
    order = jnp.argsort(d, stable=True)               # rows grouped by dest
    d_sorted = d[order]
    starts = jnp.searchsorted(d_sorted, jnp.arange(nd + 1, dtype=jnp.int32))
    pos = jnp.arange(n, dtype=jnp.int32) - starts[d_sorted]
    keep = (d_sorted < nd) & (pos < cap)
    flat = jnp.where(keep, d_sorted * cap + pos, nd * cap)  # OOB -> dropped
    out = jnp.full((nd * cap,), -1, jnp.int32)
    out = out.at[flat].set(order.astype(jnp.int32), mode="drop")
    overflow = jnp.sum((d_sorted < nd) & (pos >= cap)).astype(jnp.int32)
    return SlotScatter(out.reshape(nd, cap), overflow)


def gather_rows(columns: dict, idx: jax.Array):
    """Gather rows by (possibly -1) source indices; returns (columns, valid)."""
    safe = jnp.maximum(idx, 0)
    cols = {n: jnp.take(c, safe, axis=0) for n, c in columns.items()}
    return cols, idx >= 0

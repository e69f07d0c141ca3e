"""bitonic_sort — TPU Pallas kernel: in-VMEM tile sort for the sort join.

The shuffle-sort join's local phase sorts each partition by key. On TPU the
tile-level primitive is a bitonic network: data-independent compare-exchange
stages that vectorize perfectly on the VPU (no data-dependent control flow).
This kernel sorts one power-of-two tile of (key, payload) pairs entirely in
VMEM; larger arrays are handled by the ops-level wrapper (XLA sort
fallback).

The tile is held lane-dense as an (N/128, 128) matrix: element i sits at
row i // 128, lane i % 128. The compare-exchange partner ``i ^ j`` is then a
rotation instead of a gather: along the lanes by j for j < 128, along the
rows by j/128 otherwise. Element i takes its partner from the rotation by
-j where bit j of i is clear and from the rotation by +j where it is set,
so the wrap-around of either rotation is never read. Stages are unrolled at
trace time (log2(N)^2 / 2 stages, N <= 4096). A tile shorter than one row
of lanes is padded to 128: the stages of an N-sort only exchange within
aligned N-blocks and sort the first block ascending, so the padding is
never compared with it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MAX_TILE = 4096


def _bitonic_kernel(key_ref, val_ref, key_out, val_out, *, n: int):
    keys = key_ref[...]
    vals = val_ref[...]
    shape = keys.shape                      # (max(n, 128) / 128, 128)
    idx = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, shape, 1))

    def partner(x, j):
        axis, step = (1, j) if j < LANES else (0, j // LANES)
        size = shape[axis]
        up = pltpu.roll(x, size - step, axis)    # x[i + j]
        down = pltpu.roll(x, step, axis)         # x[i - j]
        return jnp.where((idx & j) == 0, up, down)

    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            pk, pv = partner(keys, j), partner(vals, j)
            # The lower index of a pair (bit j of i clear) keeps the smaller
            # key iff the pair sorts ascending (bit k of i clear): iff the
            # two bits agree. Equal keys keep their own payloads.
            bits = (idx >> (j.bit_length() - 1)) ^ (idx >> (k.bit_length() - 1))
            new = jnp.where((bits & 1) == 0, jnp.minimum(keys, pk),
                            jnp.maximum(keys, pk))
            vals = jnp.where(new != keys, pv, vals)
            keys = new
            j //= 2
        k *= 2
    key_out[...] = keys
    val_out[...] = vals


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort_tile(keys: jax.Array, values: jax.Array, *,
                      interpret: bool):
    """Sort one power-of-two tile (N <= MAX_TILE) of int32 (key, value)
    pairs ascending by key. Returns (sorted_keys, permuted_values)."""
    n = keys.shape[0]
    if n & (n - 1) or not 1 <= n <= MAX_TILE:
        raise ValueError(f"tile size must be a power of two <= {MAX_TILE}, "
                         f"got {n}")
    if keys.dtype != jnp.int32 or values.dtype != jnp.int32:
        raise TypeError("bitonic_sort_tile expects int32 keys and values")
    width = max(n, LANES)
    shape = (width // LANES, LANES)
    spec = pl.BlockSpec(shape, lambda: (0, 0))
    k, v = pl.pallas_call(
        functools.partial(_bitonic_kernel, n=n),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.int32)] * 2,
        interpret=interpret,
    )(*(jnp.pad(x, (0, width - n)).reshape(shape) for x in (keys, values)))
    return k.reshape(width)[:n], v.reshape(width)[:n]

"""partition_hist — TPU Pallas kernel: histogram of shuffle/radix destinations.

Counts how many rows target each of ``nd`` partitions. Used for (a) sizing
slotted all-to-all capacities, and (b) hot-key / skew detection (DESIGN.md
straggler mitigation). The TPU formulation avoids scatter entirely: each key
tile is compared against the destination iota, producing a (TN, nd) one-hot
matrix that is column-summed on the VPU — a dense, MXU-friendly bincount.

Grid: (N // TN,), accumulating into the full (nd,) output block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TN = 1024


def _hist_kernel(dest_ref, out_ref, *, nd: int):
    it = pl.program_id(0)

    @pl.when(it == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    d = dest_ref[...]  # (TN,) int32; invalid rows carry dest = -1
    onehot = (d[:, None]
              == jax.lax.broadcasted_iota(jnp.int32, (d.shape[0], nd), 1))
    out_ref[...] += jnp.sum(onehot.astype(jnp.int32), axis=0)


@functools.partial(jax.jit, static_argnames=("nd", "tn", "interpret"))
def partition_hist(dest: jax.Array, *, nd: int, interpret: bool,
                   tn: int = DEFAULT_TN) -> jax.Array:
    """counts[k] = #{i : dest[i] == k}; dest < 0 rows are not counted."""
    if dest.dtype != jnp.int32:
        raise TypeError("partition_hist expects int32 destinations")
    n = dest.shape[0]
    tn = min(tn, max(8, n))
    pad = (-n) % tn
    d = jnp.pad(dest, (0, pad), constant_values=-1)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, nd=nd),
        grid=(d.shape[0] // tn,),
        in_specs=[pl.BlockSpec((tn,), lambda i: (i,))],
        out_specs=pl.BlockSpec((nd,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((nd,), jnp.int32),
        interpret=interpret,
    )(d)
    return out

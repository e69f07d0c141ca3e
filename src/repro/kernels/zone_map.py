"""key_range / range_probe — TPU Pallas tiled min/max reduce: zone-map
runtime filters over join keys.

A zone map is the cheapest sideways-information-passing operator the cost
model knows: the build side's surviving join keys are folded into a single
``[min, max]`` interval (8 bytes on the wire, vs a bloom filter's m/8),
and the probe side keeps only rows whose key falls inside it. For
band-shaped key sets — range predicates on the key itself, e.g. TPC-DS
date windows where ``d_date_sk`` is ordered by date — the interval is
*exact*: keep fraction equals the true match fraction with zero false
positives, at a fraction of a bloom filter's broadcast cost.

``key_range`` is the build reduce: a tiled Pallas kernel in the same shape
as ``partition_hist`` — grid over key tiles, accumulating elementwise
min/max into a tiny (1, 2) output block that stays resident across the
grid. The block lives in SMEM, the TPU's scalar memory: each tile reduces
to two scalars, and VMEM takes only vector stores. Invalid rows are masked
to the identity elements (+INT_MAX for min, -INT_MAX-ish for max), so an
empty or all-invalid build yields the empty interval (lo > hi) whose probe
mask rejects every row — the same degenerate-build contract as the zero
bloom filter.

``range_probe`` needs no kernel: the keep mask is two vectorized compares
fused into the caller by XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TN = 1024

#: Identity elements of the (min, max) reduction. An untouched zone map is
#: the empty interval [INT32_MAX, INT32_MIN]: lo > hi, matches nothing.
_LO_IDENT = 2 ** 31 - 1
_HI_IDENT = -(2 ** 31)


def _minmax_kernel(keys_ref, valid_ref, out_ref):
    it = pl.program_id(0)

    @pl.when(it == 0)
    def _init():
        out_ref[0, 0] = jnp.int32(_LO_IDENT)
        out_ref[0, 1] = jnp.int32(_HI_IDENT)

    keys = keys_ref[...]                  # (TN,) int32
    valid = valid_ref[...] != 0           # (TN,)
    lo = jnp.min(jnp.where(valid, keys, jnp.int32(_LO_IDENT)))
    hi = jnp.max(jnp.where(valid, keys, jnp.int32(_HI_IDENT)))
    out_ref[0, 0] = jnp.minimum(out_ref[0, 0], lo)
    out_ref[0, 1] = jnp.maximum(out_ref[0, 1], hi)


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def key_range(keys: jax.Array, valid: jax.Array | None = None, *,
              interpret: bool, tn: int = DEFAULT_TN) -> jax.Array:
    """(min, max) of the valid entries of ``keys`` as an int32 (2,) array.

    Any input shape / integer dtype (viewed as int32, like the bloom pair).
    All-invalid or empty input returns the empty interval (lo > hi).
    """
    flat = keys.reshape(-1).astype(jnp.int32)
    v = (jnp.ones(flat.shape, jnp.int32) if valid is None
         else valid.reshape(-1).astype(jnp.int32))
    n = flat.shape[0]
    # Pow2-quantized tile (compact_partitions convention): padded lengths
    # take few distinct values so XLA reuses compilations across builds.
    tn = min(tn, max(8, 1 << (max(n, 1) - 1).bit_length()))
    pad = (-n) % tn if n else tn
    flat = jnp.pad(flat, (0, pad))
    v = jnp.pad(v, (0, pad))
    out = pl.pallas_call(
        _minmax_kernel,
        grid=(flat.shape[0] // tn,),
        in_specs=[pl.BlockSpec((tn,), lambda i: (i,)),
                  pl.BlockSpec((tn,), lambda i: (i,))],
        out_specs=pl.BlockSpec((1, 2), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 2), jnp.int32),
        interpret=interpret,
    )(flat, v)
    return out[0]


def merge_ranges(parts: jax.Array) -> jax.Array:
    """Merge stacked ``(k, 2)`` partial intervals into one ``(2,)`` zone
    map: elementwise min of the mins, max of the maxes. The min/max merge
    is associative, commutative and has the empty interval as identity, so
    any merge order — a reduce tree, an all_gather + local fold, or this
    single fused reduce — yields the same interval: the distributed-build
    equivalence ``dist_zone_map_build`` rests on."""
    return jnp.stack([jnp.min(parts[:, 0]), jnp.max(parts[:, 1])])


def range_probe(keys: jax.Array, lo_hi: jax.Array) -> jax.Array:
    """Keep-mask of ``keys`` against a ``key_range`` interval: True iff
    lo <= key <= hi. Exact for band-shaped build key sets (no false
    negatives ever: every build key lies inside its own min/max)."""
    k = keys.astype(jnp.int32)
    return (k >= lo_hi[0]) & (k <= lo_hi[1])

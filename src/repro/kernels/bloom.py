"""bloom_build / bloom_probe — TPU Pallas kernel pair: bit-packed bloom
filters over join keys (runtime-filter pushdown / sideways information
passing).

``bloom_build`` folds a table's join-key column into an ``m_bits``-wide
bloom filter packed into a ``(m_bits/32,)`` uint32 array; ``bloom_probe``
produces the keep-mask of a probe-side key column against that filter, to
be fused ahead of ``exchange.shuffle`` so rejected rows never ship.

Inside the kernels the filter is a lane-dense ``(m_bits/128, 128)`` 0/1
bitmap: bit position ``pos`` sits at row ``pos >> 7``, lane ``pos & 127``,
so the bitmap's row-major order is the packed words' bit order and
``bitmap.reshape(m_bits/32, 32)`` is the (word, bit) matrix. The TPU
formulation avoids scatter/gather entirely (same trick as
``partition_hist``): each key tile is expanded into one-hot row and lane
matrices, and

  * build: ``row_onehot^T @ lane_onehot`` is an MXU matmul whose nonzero
    cells are exactly the bitmap cells some key sets; the bitmap tile is
    OR-accumulated (max) across key tiles, and the wrapper packs it into
    uint32 words;
  * probe: each key reads its bitmap row via ``row_onehot @ bitmap`` — a
    dense matmul instead of a data-dependent gather — and its lane by an
    elementwise product + row sum.

Both grids tile the bitmap rows (``TR`` rows per step), so the one-hot
matrices stay ``(TN, TR)``-sized in VMEM whatever the filter size. The
one-hots are exact in bfloat16 and the MXU accumulates in float32.

Hash positions use Kirsch-Mitzenmacher double hashing ``h1 + i*h2`` over
the same murmur-style avalanche as the shuffle (decorrelated seeds), so
the k probes are independent and ``m_bits`` (a power of two) reduces by
mask, never by modulo.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Filter sizing/accuracy math lives with the cost model (it prices the
# filter's broadcast against the exchange savings); re-exported here so
# kernel users need a single import.
from ..core.cost_model import bloom_fpr, bloom_params  # noqa: F401
from ..joins.slots import hash32

#: Keys per grid step (fewer for a short input, see ``_tiles``).
TN = 1024
#: Bitmap rows per grid step (256 rows = 32k bits).
TR = 256
LANES = 128

#: Decorrelated murmur3-style mix seeds for the two base hashes. They must
#: differ from SHUFFLE_SEED/BUCKET_SEED: a bloom position correlated with
#: the shuffle destination would make false positives pile onto single
#: partitions instead of spreading. Plain ints (converted at trace time):
#: module-level jnp constants would be captured by the Pallas kernels.
BLOOM_SEED_1 = 0x165667B1
BLOOM_SEED_2 = 0xD6E8FEB8


def _positions(keys: jax.Array, i, m_bits: int) -> jax.Array:
    """Bit position of hash i for each key (double hashing; h2 forced odd so
    the stride is a unit of the pow2 ring and probes never collapse)."""
    h1 = hash32(keys, jnp.uint32(BLOOM_SEED_1))
    h2 = hash32(keys, jnp.uint32(BLOOM_SEED_2)) | jnp.uint32(1)
    return (h1 + jnp.asarray(i).astype(jnp.uint32) * h2) & jnp.uint32(m_bits - 1)


def _row_lane(keys: jax.Array, i, m_bits: int, r0
              ) -> tuple[jax.Array, jax.Array]:
    """Bitmap (row relative to the tile starting at r0, lane) of hash i."""
    pos = _positions(keys, i, m_bits)
    return ((pos >> 7).astype(jnp.int32) - r0,
            (pos & (LANES - 1)).astype(jnp.int32))


def _build_kernel(keys_ref, valid_ref, out_ref, *, m_bits: int, k: int):
    tr = out_ref.shape[0]
    r0 = pl.program_id(0) * tr

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keys = keys_ref[...]                   # (TN,) int32
    valid = valid_ref[...] != 0            # (TN,) invalid rows contribute 0
    tn = keys.shape[0]

    def one_hash(i, hits):
        row, lane = _row_lane(keys, i, m_bits, r0)
        # (TR, TN) x (TN, 128): cell (r, l) counts this tile's keys setting
        # bit r*128 + l (counts <= TN, exact in f32 accumulation).
        roh_t = (valid[None, :] & (row[None, :] == jax.lax.broadcasted_iota(
            jnp.int32, (tr, tn), 0))).astype(jnp.bfloat16)
        loh = (lane[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (tn, LANES), 1)).astype(jnp.bfloat16)
        return hits + jax.lax.dot(roh_t, loh,
                                  preferred_element_type=jnp.float32)

    hits = jax.lax.fori_loop(0, k, one_hash,
                             jnp.zeros((tr, LANES), jnp.float32))
    out_ref[...] = jnp.maximum(out_ref[...], (hits > 0.5).astype(jnp.int32))


def _probe_kernel(keys_ref, bitmap_ref, out_ref, *, m_bits: int, k: int):
    tr = bitmap_ref.shape[0]
    r0 = pl.program_id(1) * tr

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keys = keys_ref[...]                   # (TN,) int32
    bitmap = bitmap_ref[...]               # (TR, 128) bf16 0/1 bits
    tn = keys.shape[0]

    def one_hash(i, found):
        row, lane = _row_lane(keys, i, m_bits, r0)
        roh = (row[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (tn, tr), 1)).astype(jnp.bfloat16)
        loh = (lane[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (tn, LANES), 1)).astype(jnp.float32)
        # row n of (roh @ bitmap) is n's bitmap row when it lies in this
        # tile (zeros otherwise); the lane one-hot selects n's bit.
        rows = jax.lax.dot(roh, bitmap, preferred_element_type=jnp.float32)
        return found + (jnp.sum(rows * loh, axis=1) > 0.5).astype(jnp.int32)

    found = jax.lax.fori_loop(0, k, one_hash, jnp.zeros((tn,), jnp.int32))
    # Each hash's bit lies in exactly one row tile: summed over the grid's
    # row axis, a key's count reaches k iff all k probed bits are set.
    out_ref[...] += found


def _tiles(n: int) -> tuple[int, int]:
    """(tile, padded length) for n keys. Pow2-quantized tile (like
    compact_partitions' capacities): padded lengths take few distinct
    values, so XLA reuses compilations across build cardinalities."""
    tn = min(TN, max(8, 1 << (max(n, 1) - 1).bit_length()))
    return tn, n + ((-n) % tn if n else tn)


def _bitmap_rows(m_bits: int) -> tuple[int, int]:
    """(rows per tile, bitmap rows) of an m_bits filter."""
    if m_bits < LANES or m_bits & (m_bits - 1):
        raise ValueError(f"m_bits must be a power of two >= {LANES}, "
                         f"got {m_bits}")
    rows = m_bits // LANES
    return min(TR, rows), rows


@functools.partial(jax.jit,
                   static_argnames=("m_bits", "k", "interpret"))
def bloom_build(keys: jax.Array, valid: jax.Array | None = None, *,
                m_bits: int, k: int, interpret: bool) -> jax.Array:
    """Fold ``keys`` (any shape, integer dtype) into a bit-packed bloom
    filter: uint32 array of shape (m_bits/32,). Rows with ``valid`` False
    are excluded; an all-invalid (or empty) input yields the zero filter,
    whose probe mask rejects everything."""
    tr, rows = _bitmap_rows(m_bits)
    flat = keys.reshape(-1).astype(jnp.int32)
    v = (jnp.ones(flat.shape, jnp.int32) if valid is None
         else valid.reshape(-1).astype(jnp.int32))
    tn, n_pad = _tiles(flat.shape[0])
    flat = jnp.pad(flat, (0, n_pad - flat.shape[0]))
    v = jnp.pad(v, (0, n_pad - v.shape[0]))
    bitmap = pl.pallas_call(
        functools.partial(_build_kernel, m_bits=m_bits, k=k),
        grid=(rows // tr, n_pad // tn),
        in_specs=[pl.BlockSpec((tn,), lambda r, i: (i,)),
                  pl.BlockSpec((tn,), lambda r, i: (i,))],
        out_specs=pl.BlockSpec((tr, LANES), lambda r, i: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        interpret=interpret,
    )(flat, v)
    # Pack each 32-bit group into a word (distinct powers of two: the sum
    # is the OR).
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(jnp.where(bitmap.reshape(m_bits // 32, 32) != 0,
                             jnp.uint32(1) << shifts, jnp.uint32(0)),
                   axis=1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def bloom_probe(keys: jax.Array, bits: jax.Array, *, k: int,
                interpret: bool) -> jax.Array:
    """Keep-mask of ``keys`` against a ``bloom_build`` filter: True iff all
    k probed bits are set (never a false negative). Same shape as ``keys``."""
    m_bits = bits.shape[0] * 32
    tr, rows = _bitmap_rows(m_bits)
    shape = keys.shape
    flat = keys.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    tn, n_pad = _tiles(n)
    flat = jnp.pad(flat, (0, n_pad - n))
    # Unpack the words to the kernel's (rows, 128) 0/1 bitmap once.
    bitmap = ((bits[:, None] >> jnp.arange(32, dtype=jnp.uint32)[None, :])
              & jnp.uint32(1)).astype(jnp.bfloat16).reshape(rows, LANES)
    found = pl.pallas_call(
        functools.partial(_probe_kernel, m_bits=m_bits, k=k),
        grid=(n_pad // tn, rows // tr),
        in_specs=[pl.BlockSpec((tn,), lambda i, r: (i,)),
                  pl.BlockSpec((tr, LANES), lambda i, r: (r, 0))],
        out_specs=pl.BlockSpec((tn,), lambda i, r: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        interpret=interpret,
    )(flat, bitmap)
    return (found[:n] == k).reshape(shape)

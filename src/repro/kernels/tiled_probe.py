"""tiled_probe — TPU Pallas kernel for the probe step of the hash-family joins.

TPU adaptation (DESIGN.md §2): a chaining hash map is pointer-chasing and
hostile to the VPU/MXU. The TPU-native probe is a *dense tiled key match*:
stream tiles of probe keys and tiles of build keys through VMEM, compare
every probe key with every build key of the tile on the VPU, and keep each
probe key's first matching build-side index. The radix-bucketed caller
(joins.local_join) bounds the build keys per probe row, giving the hash
join's O(|A| + |B|) workload; this kernel is the inner dense primitive.

Inputs may carry leading batch dimensions (one independent probe per batch
row, e.g. one per hash bucket). The batch is flattened into the rows of a
2-D (rows, keys) array per side and both sides are padded to whole tiles:
rows on the sublanes (ROWS per block), keys on the lanes (multiples of
128). The grid is (rows / ROWS, Na / TA, Nb / TB); the build axis is the
innermost (fastest) grid dimension, so the output tile for a fixed probe
tile stays resident while build tiles stream past (accumulator pattern).
Inside a step one build column at a time is broadcast along the lanes and
compared with the whole (ROWS, TA) probe tile, so every compare fills its
vregs and nothing is reshaped or reduced across lanes.

No-match sentinel inside the kernel is INT32_MAX (monotone under min-
accumulation); the public wrapper converts it to -1.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INT32_MAX = jnp.iinfo(jnp.int32).max

LANES = 128
#: Batch rows per block (the sublane tile).
ROWS = 8
#: Widest probe tile; build tiles are one lane tile wide.
MAX_TA = 512
TB = LANES


def _first_match(a, b_ref, jb):
    """First j in the build tile with b[r, j] == a[r, i], per (r, i), else
    INT32_MAX. Walking j downwards, the last hit written is the first."""
    tb = b_ref.shape[-1]
    out = jnp.full(a.shape, INT32_MAX, jnp.int32)
    for j in reversed(range(tb)):
        out = jnp.where(a == b_ref[:, j:j + 1], jb * tb + j, out)
    return out


def _probe_kernel(a_ref, b_ref, out_ref):
    """One (ROWS, TA) x (ROWS, TB) step: out = min(out, first match)."""
    jb = pl.program_id(2)

    @pl.when(jb == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, INT32_MAX)

    out_ref[...] = jnp.minimum(out_ref[...],
                               _first_match(a_ref[...], b_ref, jb))


def _probe3_kernel(a1_ref, a2_ref, b_ref, c_ref, out1_ref, out2_ref):
    """One step of the fused 3-way probe: both matches share the probe
    tiles' VMEM residency and the same grid walk."""
    jb = pl.program_id(2)

    @pl.when(jb == 0)
    def _init():
        out1_ref[...] = jnp.full_like(out1_ref, INT32_MAX)
        out2_ref[...] = jnp.full_like(out2_ref, INT32_MAX)

    out1_ref[...] = jnp.minimum(out1_ref[...],
                                _first_match(a1_ref[...], b_ref, jb))
    out2_ref[...] = jnp.minimum(out2_ref[...],
                                _first_match(a2_ref[...], c_ref, jb))


def _layout(n_rows: int, na: int, nb: int) -> tuple[int, int, int, int]:
    """(rows per block, padded rows, probe tile, padded probe length)."""
    rows = n_rows if n_rows <= ROWS else ROWS
    ta = min(MAX_TA, -(-max(na, 1) // LANES) * LANES)
    return rows, -(-n_rows // rows) * rows, ta, -(-na // ta) * ta


def _pad2(x: jax.Array, rows: int, n: int, fill: int) -> jax.Array:
    """(batch..., m) -> (rows, n), padded with ``fill``."""
    x = x.reshape(-1, x.shape[-1])
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, n - x.shape[1])),
                   constant_values=fill)


def _resolve(out: jax.Array, batch: tuple, na: int, nb: int) -> jax.Array:
    """Kernel output -> (batch..., na) first-match index, -1 for none.
    Matches landing in the padded tail (a probe key equal to the pad
    sentinel -2) are not real build rows — found by hypothesis."""
    out = out[:math.prod(batch), :na].reshape(batch + (na,))
    return jnp.where((out == INT32_MAX) | (out >= nb), -1, out)


def _check_int32(*keys: jax.Array) -> None:
    for k in keys:
        if k.dtype != jnp.int32:
            raise TypeError("tiled_probe expects int32 keys")


def _specs(rows: int, ta: int):
    a_spec = pl.BlockSpec((rows, ta), lambda r, i, j: (r, i))
    b_spec = pl.BlockSpec((rows, TB), lambda r, i, j: (r, j))
    return a_spec, b_spec


@functools.partial(jax.jit, static_argnames=("interpret",))
def tiled_probe(a_keys: jax.Array, b_keys: jax.Array, *,
                interpret: bool) -> jax.Array:
    """First-match probe: out[i] = min{{j : b_keys[j] == a_keys[i]}} else -1.

    Both inputs are int32 with equal leading (batch) dimensions; the match
    runs independently per batch row. Callers encode invalid rows with
    distinct negative sentinels so they can never match.
    """
    _check_int32(a_keys, b_keys)
    batch, na, nb = a_keys.shape[:-1], a_keys.shape[-1], b_keys.shape[-1]
    rows, n_rows, ta, pa = _layout(math.prod(batch), na, nb)
    pb = -(-max(nb, 1) // TB) * TB
    # Pad with non-matching sentinels (a: -1, b: -2).
    a = _pad2(a_keys, n_rows, pa, -1)
    b = _pad2(b_keys, n_rows, pb, -2)
    a_spec, b_spec = _specs(rows, ta)
    out = pl.pallas_call(
        _probe_kernel,
        grid=(n_rows // rows, pa // ta, pb // TB),
        in_specs=[a_spec, b_spec],
        out_specs=a_spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.int32),
        interpret=interpret,
    )(a, b)
    return _resolve(out, batch, na, nb)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tiled_probe3(a1_keys: jax.Array, a2_keys: jax.Array,
                 b_keys: jax.Array, c_keys: jax.Array, *,
                 interpret: bool) -> tuple[jax.Array, jax.Array]:
    """Fused first-match probe for the hypercube 3-way local join: for each
    probe row i, find the first j with ``b_keys[j] == a1_keys[i]`` and the
    first k with ``c_keys[k] == a2_keys[i]`` in ONE kernel.

    Both build sides are padded to a common tile-multiple length so a single
    grid walk streams them side by side; each grid step min-accumulates two
    output tiles against the resident probe tiles. Sentinel conventions
    match ``tiled_probe`` (invalid probe -1, invalid/pad build -2; INT32_MAX
    no-match converted to -1).
    """
    _check_int32(a1_keys, a2_keys, b_keys, c_keys)
    batch, na = a1_keys.shape[:-1], a1_keys.shape[-1]
    nb, nc = b_keys.shape[-1], c_keys.shape[-1]
    rows, n_rows, ta, pa = _layout(math.prod(batch), na, max(nb, nc))
    pbc = -(-max(nb, nc, 1) // TB) * TB
    a1, a2 = _pad2(a1_keys, n_rows, pa, -1), _pad2(a2_keys, n_rows, pa, -1)
    b, c = _pad2(b_keys, n_rows, pbc, -2), _pad2(c_keys, n_rows, pbc, -2)
    a_spec, b_spec = _specs(rows, ta)
    out1, out2 = pl.pallas_call(
        _probe3_kernel,
        grid=(n_rows // rows, pa // ta, pbc // TB),
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=[a_spec, a_spec],
        out_shape=[jax.ShapeDtypeStruct(a1.shape, jnp.int32)] * 2,
        interpret=interpret,
    )(a1, a2, b, c)
    return _resolve(out1, batch, na, nb), _resolve(out2, batch, na, nc)

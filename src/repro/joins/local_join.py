"""Local join phase (paper §2.1.2) — per-partition pure functions.

Each local join resolves, for every probe row of A, the matching build row
of B (PK build side: unique keys, FK->PK star joins), returning
``(match_idx, found)``. The distributed methods gather B's payload columns
through ``match_idx`` afterwards.

TPU adaptation (DESIGN.md §2): the *hash* join is a radix hash join —
bucket both sides by a multiplicative hash, then run a dense tiled key-match
within each bucket (the ``tiled_probe`` Pallas kernel is the in-VMEM
primitive, taken with ``use_kernel=True``). A jnp path with identical
semantics is the default on every backend, TPU included, until a benchmark
has measured the two paths against each other. The *sort* join sorts the
build side (XLA sort by default, the bitonic tile kernel with
``use_kernel_sort=True``) and merges via binary search. The *nested loop*
compares all pairs with an arbitrary predicate.

Invalid-row sentinels: probe side -1, build side -2 (never equal).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import ops as kops
from .slots import BUCKET_SEED, hash32, slot_scatter

A_SENTINEL = -1
B_SENTINEL = -2

#: Candidate cells (probe rows x bucket slots) the jnp probe gathers at
#: once. Larger probe sides go through in row chunks, so the candidate
#: tiles stay at 64 MiB of int32 per partition however many rows a shuffle
#: lands on it.
PROBE_TILE = 1 << 24


class LocalJoinResult(NamedTuple):
    match_idx: jax.Array  # (na,) int32 row index into the B arrays, -1 = none
    found: jax.Array      # (na,) bool


def _sanitize(keys: jax.Array, valid: jax.Array, sentinel: int) -> jax.Array:
    return jnp.where(valid, keys, sentinel).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Hash join (radix-bucketed tiled match).
# ---------------------------------------------------------------------------

def _bucket_of(keys: jax.Array, nb: int) -> jax.Array:
    return (hash32(keys, BUCKET_SEED) % jnp.uint32(nb)).astype(jnp.int32)


def _probe_buckets(ak: jax.Array, ab: jax.Array, bk_bucketed: jax.Array,
                   b_rows: jax.Array, chunk: int) -> jax.Array:
    """B row of each probe key's first match in its bucket, -1 if none.
    Per ``chunk``-row slice of the probe side: gather the bucket's keys and
    row ids, compare, take the first hit."""
    n = ak.shape[0]
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    ak = jnp.pad(ak, (0, pad), constant_values=A_SENTINEL)
    ab = jnp.pad(ab, (0, pad))

    def one(x):
        keys, buckets = x
        cand_keys = jnp.take(bk_bucketed, buckets, axis=0)  # (chunk, cap_b)
        cand_rows = jnp.take(b_rows, buckets, axis=0)       # (chunk, cap_b)
        hit = cand_keys == keys[:, None]
        slot = jnp.argmax(hit, axis=1)
        idx = jnp.take_along_axis(cand_rows, slot[:, None], axis=1)[:, 0]
        return jnp.where(jnp.any(hit, axis=1), idx, -1)

    idx = jax.lax.map(one, (ak.reshape(n_chunks, chunk),
                            ab.reshape(n_chunks, chunk)))
    return idx.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("n_buckets", "bucket_cap_factor",
                                             "use_kernel"))
def hash_join(a_keys: jax.Array, a_valid: jax.Array,
              b_keys: jax.Array, b_valid: jax.Array,
              *, n_buckets: int | None = None,
              bucket_cap_factor: float = 4.0,
              use_kernel: bool = False) -> LocalJoinResult:
    """Radix hash join of one partition. Build side keys must be unique.

    Build: scatter B rows into ``nb`` hash buckets of static capacity
    (C'_build ~ |B|). Probe: each A row inspects only its bucket's keys
    (C_probe ~ |A| + fanout*|B|). With ``use_kernel`` both sides are
    bucketed and each bucket pair runs the dense ``tiled_probe`` Pallas
    match; the default jnp path, on every backend, gathers each probe row's
    bucket tile and compares — identical semantics.
    """
    na, b_cap = a_keys.shape[0], b_keys.shape[0]
    ak = _sanitize(a_keys, a_valid, A_SENTINEL)
    bk = _sanitize(b_keys, b_valid, B_SENTINEL)

    nb = n_buckets or max(1, min(1 << (max(b_cap, 1) - 1).bit_length(),
                                 max(8, b_cap // 32)))
    b_slot_cap = max(8, int(-(-b_cap * bucket_cap_factor) // nb))

    # Build: bucket B (the "hash map" is the slotted (nb, cap) layout).
    bb = _bucket_of(bk, nb)
    scat_b = slot_scatter(bb, b_valid, nb, b_slot_cap)
    bk_bucketed = jnp.where(scat_b.idx >= 0,
                            jnp.take(bk, jnp.maximum(scat_b.idx, 0)),
                            B_SENTINEL)  # (nb, cap_b)
    ab = _bucket_of(ak, nb)

    if not use_kernel:
        # Probe: gather each A row's bucket tile and match within it.
        chunk = min(max(8, PROBE_TILE // b_slot_cap), max(na, 1))
        idx = _probe_buckets(ak, ab, bk_bucketed, scat_b.idx, chunk)
        found = (idx >= 0) & a_valid
        return LocalJoinResult(jnp.where(found, idx, -1).astype(jnp.int32),
                               found)

    # Kernel path: bucket A as well, run one dense tile match per bucket.
    a_slot_cap = max(8, int(-(-na * bucket_cap_factor) // nb))
    scat_a = slot_scatter(ab, a_valid, nb, a_slot_cap)
    ak_bucketed = jnp.where(scat_a.idx >= 0,
                            jnp.take(ak, jnp.maximum(scat_a.idx, 0)),
                            A_SENTINEL)  # (nb, cap_a)
    slot_in_bucket = kops.probe(ak_bucketed, bk_bucketed)  # one per bucket
    # Resolve to B row ids and scatter back to A's original row order.
    b_rows = jnp.take_along_axis(
        scat_b.idx, jnp.maximum(slot_in_bucket, 0), axis=1)
    b_rows = jnp.where(slot_in_bucket >= 0, b_rows, -1)  # (nb, cap_a)
    out = jnp.full((na,), -1, jnp.int32)
    out = out.at[jnp.where(scat_a.idx >= 0, scat_a.idx, na).reshape(-1)
                 ].set(b_rows.reshape(-1), mode="drop")
    found = (out >= 0) & a_valid
    return LocalJoinResult(jnp.where(found, out, -1), found)


# ---------------------------------------------------------------------------
# Sort join (sort both sides, merge by binary search).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("use_kernel_sort",))
def sort_join(a_keys: jax.Array, a_valid: jax.Array,
              b_keys: jax.Array, b_valid: jax.Array,
              *, use_kernel_sort: bool = False) -> LocalJoinResult:
    """Sort-merge join of one partition. Build side keys must be unique.

    Both sides are sorted by key (C_sort ~ |A|log a/p + |B|log b/p); the
    merge walks A in key order probing the sorted B run (C_merge ~ |A|+|B|).
    Output rows remain addressed in A's original order (match_idx aligns
    with the unsorted probe side; the sort is internal to the method).
    """
    ak = _sanitize(a_keys, a_valid, jnp.iinfo(jnp.int32).max)  # invalid last
    bk = _sanitize(b_keys, b_valid, jnp.iinfo(jnp.int32).max)
    nb = bk.shape[0]

    rows_b = jnp.arange(nb, dtype=jnp.int32)
    if use_kernel_sort:
        bk_sorted, b_perm = kops.sort_pairs(bk, rows_b)
    else:
        b_perm = jnp.argsort(bk, stable=True).astype(jnp.int32)
        bk_sorted = bk[b_perm]

    # Sort A as the method prescribes (workload accounting); the merge below
    # is order-insensitive so correctness is unaffected.
    pos = jnp.searchsorted(bk_sorted, ak).astype(jnp.int32)
    pos = jnp.minimum(pos, nb - 1)
    found = (jnp.take(bk_sorted, pos) == ak) & a_valid
    idx = jnp.take(b_perm, pos)
    b_ok = jnp.take(b_valid, jnp.maximum(idx, 0))
    found = found & b_ok
    return LocalJoinResult(jnp.where(found, idx, -1).astype(jnp.int32), found)


# ---------------------------------------------------------------------------
# Nested loop (arbitrary predicate; O(na * nb)).
# ---------------------------------------------------------------------------

def nested_loop_join(a_cols: dict, a_valid: jax.Array,
                     b_cols: dict, b_valid: jax.Array,
                     predicate: Callable[[dict, dict], jax.Array]
                     ) -> LocalJoinResult:
    """First-match nested loop with an arbitrary row predicate.

    ``predicate`` receives A columns shaped (na, 1) and B columns shaped
    (1, nb) and returns an (na, nb) boolean matrix.
    """
    a_b = {n: c[:, None] for n, c in a_cols.items()}
    b_b = {n: c[None, :] for n, c in b_cols.items()}
    hit = predicate(a_b, b_b) & a_valid[:, None] & b_valid[None, :]
    found = jnp.any(hit, axis=1)
    idx = jnp.argmax(hit, axis=1).astype(jnp.int32)
    return LocalJoinResult(jnp.where(found, idx, -1), found)

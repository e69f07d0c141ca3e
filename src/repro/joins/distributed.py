"""Distributed execution of the join methods under ``jax.shard_map``.

The global-view functions in ``methods.py`` are the semantic spec; here the
partition axis is a real mesh axis ``"p"`` and the exchanges are actual
collectives:

    broadcast  ->  jax.lax.all_gather   (paper's broadcast, Eq. 1)
    shuffle    ->  jax.lax.all_to_all   (paper's shuffle,   Eq. 5)

The per-partition compute (slot packing, radix hash join, sort join) is the
*same code* as the global view — only the exchange primitive differs. On the
CPU CI container this runs on ``--xla_force_host_platform_device_count``
placeholder devices (see tests/test_distributed_join.py); on a real cluster
the identical program spans pods.

Every runtime-filter kind also gets its **distributed build** here, one
per reducer:

    dist_bloom_build      partial bloom arrays, OR-merged      (bloom)
    dist_zone_map_build   per-device (min, max), min/max merge (zone_map)
    dist_key_set_build    per-device distinct keys, all_gather
                          + merge-dedupe                       (semi_join)

All three share one **distributed-equivalence contract**: the distributed
build's result is bit-/value-identical to the corresponding global-view
build (``kernels.bloom.bloom_build``, ``kernels.zone_map.key_range``,
``core.psts.key_set``) over the concatenated column, at *any* device
count — because each merge operator (bitwise OR, elementwise min/max,
sorted set-union) is associative, commutative, and neutral on empty
partitions, the result cannot depend on how rows land on devices.
``tests/test_distributed_filters.py`` pins the contract at device counts
{1, 8}. The cost model charges each build its actual merge shape
(``filter_reduce_cost(kind=...)``): a ceil(log2 p) reduce tree for the
constant-size bloom/zone-map payloads, the (p-1)·m/8 all_gather volume
for the semi-join key lists, whose disjoint partials cannot be compressed
mid-tree.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..core.psts import key_set
from ..kernels import ops as kops
from .local_join import hash_join, sort_join
from .methods import HypercubeSpec
from .slots import (SHUFFLE_SEED, gather_rows, hash32, pair_capacity,
                    slot_scatter)
from .table import Table

AXIS = "p"

def make_join_mesh(p: int) -> Mesh:
    """1-D mesh over the join parallelism p."""
    return jax.make_mesh((p,), (AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def cube_axis_names(n_axes: int) -> tuple[str, ...]:
    """Axis names of the hypercube mesh (one axis per join variable)."""
    return tuple(f"hc{i}" for i in range(n_axes))


def make_cube_mesh(dims: tuple[int, ...]) -> Mesh:
    """Multi-axis mesh for the hypercube multi-way shuffle: the p devices
    arranged as a cube of shape ``dims`` (C-order, matching the global-view
    ``hypercube_shuffle``'s flat cell index). A flat mesh is the degenerate
    cube ``(p, 1, ..., 1)`` — same devices, same program, share-1 axes make
    their collectives identities."""
    return jax.make_mesh(tuple(dims), cube_axis_names(len(dims)),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(dims))


def place_cube(table: Table, mesh: Mesh) -> Table:
    """Place a stacked table with its partition axis sharded jointly over
    all cube axes (partition i on cube cell i in C-order)."""
    sh = NamedSharding(mesh, P(mesh.axis_names))
    cols = {n: jax.device_put(c, sh) for n, c in table.columns.items()}
    return Table(cols, jax.device_put(table.valid, sh))


def place(table: Table, mesh: Mesh) -> Table:
    """Place a stacked table so partition i lives on device i."""
    sh = NamedSharding(mesh, P(AXIS))
    cols = {n: jax.device_put(c, sh) for n, c in table.columns.items()}
    return Table(cols, jax.device_put(table.valid, sh))


# -- per-shard exchange primitives (run inside shard_map; local leading axis
#    is 1 because each device owns exactly one partition) -------------------

def _local_shuffle(cols: Dict[str, jax.Array], valid: jax.Array, key: str,
                   p: int, pair_cap: int):
    """Pack rows into per-destination slots and all_to_all them."""
    dest = (hash32(cols[key], SHUFFLE_SEED) % jnp.uint32(p)).astype(jnp.int32)
    scat = slot_scatter(dest, valid, p, pair_cap)      # idx: (p, pair_cap)
    send_cols, send_valid = gather_rows(cols, scat.idx)
    recv_cols = {
        n: jax.lax.all_to_all(c, AXIS, split_axis=0, concat_axis=0
                              ).reshape(p * pair_cap)
        for n, c in send_cols.items()}
    recv_valid = jax.lax.all_to_all(send_valid, AXIS, split_axis=0,
                                    concat_axis=0).reshape(p * pair_cap)
    return recv_cols, recv_valid


def _local_broadcast(cols: Dict[str, jax.Array], valid: jax.Array, p: int):
    """all_gather a full replica of the table onto every device."""
    full_cols = {n: jax.lax.all_gather(c, AXIS).reshape(-1)
                 for n, c in cols.items()}
    full_valid = jax.lax.all_gather(valid, AXIS).reshape(-1)
    return full_cols, full_valid


# -- distributed join methods ------------------------------------------------

def _attach(a_cols, a_valid, b_cols, res):
    out = dict(a_cols)
    gathered, _ = gather_rows(b_cols, res.match_idx)
    for n, c in gathered.items():
        out[n if n not in out else f"{n}_r"] = c
    return out, a_valid & res.found


@functools.partial(jax.jit, static_argnames=("a_key", "b_key", "mesh",
                                              "capacity_factor"))
def dist_shuffle_hash_join(a: Table, b: Table, a_key: str, b_key: str,
                           mesh: Mesh, capacity_factor: float = 2.0) -> Table:
    p = mesh.shape[AXIS]
    cap_a = pair_capacity(a.capacity, p, capacity_factor)
    cap_b = pair_capacity(b.capacity, p, capacity_factor)

    def f(a_cols, a_valid, b_cols, b_valid):
        a_cols = {n: c[0] for n, c in a_cols.items()}
        b_cols = {n: c[0] for n, c in b_cols.items()}
        ra_cols, ra_valid = _local_shuffle(a_cols, a_valid[0], a_key, p, cap_a)
        rb_cols, rb_valid = _local_shuffle(b_cols, b_valid[0], b_key, p, cap_b)
        res = hash_join(ra_cols[a_key], ra_valid, rb_cols[b_key], rb_valid)
        out_cols, out_valid = _attach(ra_cols, ra_valid, rb_cols, res)
        return ({n: c[None] for n, c in out_cols.items()}, out_valid[None])

    cols, valid = jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS)),
    )(a.columns, a.valid, b.columns, b.valid)
    return Table(cols, valid)


@functools.partial(jax.jit, static_argnames=("a_key", "b_key", "mesh",
                                              "capacity_factor"))
def dist_shuffle_sort_join(a: Table, b: Table, a_key: str, b_key: str,
                           mesh: Mesh, capacity_factor: float = 2.0) -> Table:
    p = mesh.shape[AXIS]
    cap_a = pair_capacity(a.capacity, p, capacity_factor)
    cap_b = pair_capacity(b.capacity, p, capacity_factor)

    def f(a_cols, a_valid, b_cols, b_valid):
        a_cols = {n: c[0] for n, c in a_cols.items()}
        b_cols = {n: c[0] for n, c in b_cols.items()}
        ra_cols, ra_valid = _local_shuffle(a_cols, a_valid[0], a_key, p, cap_a)
        rb_cols, rb_valid = _local_shuffle(b_cols, b_valid[0], b_key, p, cap_b)
        res = sort_join(ra_cols[a_key], ra_valid, rb_cols[b_key], rb_valid)
        out_cols, out_valid = _attach(ra_cols, ra_valid, rb_cols, res)
        return ({n: c[None] for n, c in out_cols.items()}, out_valid[None])

    cols, valid = jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS)),
    )(a.columns, a.valid, b.columns, b.valid)
    return Table(cols, valid)


@functools.partial(jax.jit, static_argnames=("spec", "mesh",
                                             "capacity_factor"))
def dist_hypercube_join(tables: tuple, spec: HypercubeSpec, mesh: Mesh,
                        capacity_factor: float = 2.0) -> Table:
    """Hypercube multi-way join under ``shard_map`` over the multi-axis
    cube mesh — the distributed twin of ``methods.hypercube_multiway_join``.

    Per relation the cube exchange is compositional in the mesh axes:
    one ``all_to_all`` along each *owned* axis routes rows to their
    hash coordinate, then one ``all_gather`` along each *free* axis
    replicates the shard across the slice the relation does not own.
    After the exchange every cube cell holds exactly the global view's
    cell content, so the same local probe chain + closing checks run
    unchanged. Tables must be placed with ``place_cube``.
    """
    names = mesh.axis_names
    dims = tuple(mesh.shape[n] for n in names)

    def cube_exchange(cols, valid, axis_keys):
        owned = {ax for ax, _ in axis_keys}
        for ax, col in axis_keys:
            d = dims[ax]
            cap = pair_capacity(valid.shape[0], d, capacity_factor)
            dest = (hash32(cols[col], SHUFFLE_SEED)
                    % jnp.uint32(d)).astype(jnp.int32)
            scat = slot_scatter(dest, valid, d, cap)
            send_cols, send_valid = gather_rows(cols, scat.idx)
            cols = {n: jax.lax.all_to_all(c, names[ax], split_axis=0,
                                          concat_axis=0).reshape(d * cap)
                    for n, c in send_cols.items()}
            valid = jax.lax.all_to_all(send_valid, names[ax], split_axis=0,
                                       concat_axis=0).reshape(d * cap)
        for ax in range(len(dims)):
            if ax in owned:
                continue
            cols = {n: jax.lax.all_gather(c, names[ax]).reshape(-1)
                    for n, c in cols.items()}
            valid = jax.lax.all_gather(valid, names[ax]).reshape(-1)
        return cols, valid

    def f(cols_list, valid_list):
        shards = []
        for cols, valid, ak in zip(cols_list, valid_list, spec.axis_keys):
            cols = {n: c[0] for n, c in cols.items()}
            shards.append(cube_exchange(cols, valid[0], tuple(ak)))
        cols, valid = dict(shards[0][0]), shards[0][1]
        for lk in spec.links:
            b_cols, b_valid = shards[lk.build]
            res = hash_join(cols[lk.probe_col], valid, b_cols[lk.build_col],
                            b_valid)
            gathered, _ = gather_rows(b_cols, res.match_idx)
            for n, c in gathered.items():
                if n in cols:
                    raise ValueError(f"duplicate column {n!r} in "
                                     "multi-way join")
                cols[n] = c
            valid = valid & res.found
        for c1, c2 in spec.checks:
            valid = valid & (cols[c1] == cols[c2])
        return ({n: c[None] for n, c in cols.items()}, valid[None])

    spec_all = P(names)
    cols, valid = jax.shard_map(
        f, mesh=mesh,
        in_specs=(spec_all, spec_all),
        out_specs=(spec_all, spec_all),
    )(tuple(t.columns for t in tables), tuple(t.valid for t in tables))
    return Table(cols, valid)


# -- distributed runtime-filter build ----------------------------------------

@functools.partial(jax.jit, static_argnames=("key", "mesh", "m_bits", "k"))
def dist_bloom_build(table: Table, key: str, mesh: Mesh, *, m_bits: int,
                     k: int) -> jax.Array:
    """Distributed bloom build: per-device partial filters (the
    ``bloom_build`` kernel over each device's partition) OR-merged across
    the mesh, then held replicated on every device.

    Returns the merged (m_bits/32,) uint32 array — bit-identical to the
    global-view ``bloom_build`` over the concatenated column, because OR
    accumulation is order- and partition-invariant. The all_gather +
    local OR here is the semantic spec of a bitwise-or all-reduce (XLA
    has no uint32 OR all-reduce primitive); the cost model prices the
    operation as the reduce tree a real all-reduce executes —
    ceil(log2 p) rounds of m/8 bytes (``filter_reduce_cost``) — not the
    gather's (p-1)·m/8.
    """
    p = mesh.shape[AXIS]

    def f(col, valid):
        part = kops.bloom_build(col[0], valid[0], m_bits=m_bits, k=k)
        parts = jax.lax.all_gather(part, AXIS)        # (p, m_words)
        merged = parts[0]
        for i in range(1, p):
            merged = merged | parts[i]
        return merged[None]

    # check_vma=False: a Pallas kernel's outputs carry no varying-axes type.
    words = jax.shard_map(
        f, mesh=mesh, in_specs=(P(AXIS), P(AXIS)), out_specs=P(AXIS),
        check_vma=False,
    )(table.column(key), table.valid)
    # Every device holds the identical merged filter; take one replica.
    return words[0]


@functools.partial(jax.jit, static_argnames=("key", "mesh"))
def dist_zone_map_build(table: Table, key: str, mesh: Mesh) -> jax.Array:
    """Distributed zone-map build: per-device (min, max) partial intervals
    (the ``key_range`` kernel over each device's partition) merged across
    the mesh with an elementwise min/max reduce.

    Returns the merged int32 ``(2,)`` interval — value-identical to the
    global-view ``kernels.zone_map.key_range`` over the concatenated
    column at any device count: min/max is associative and commutative,
    and an empty (all-invalid) partition contributes the empty-interval
    identity ``[INT32_MAX, INT32_MIN]``, which is neutral under the
    merge. As with ``dist_bloom_build``, the all_gather + local fold is
    the semantic spec of the min/max all-reduce tree the cost model
    charges — ceil(log2 p) rounds of the 8-byte payload
    (``filter_reduce_cost(ZONE_MAP_BITS, kind="zone_map")``).
    """

    def f(col, valid):
        part = kops.key_range(col[0], valid[0])
        parts = jax.lax.all_gather(part, AXIS)        # (p, 2)
        return kops.merge_ranges(parts)[None]

    out = jax.shard_map(
        f, mesh=mesh, in_specs=(P(AXIS), P(AXIS)), out_specs=P(AXIS),
        check_vma=False,
    )(table.column(key), table.valid)
    # Every device holds the identical merged interval; take one replica.
    return out[0]


@functools.partial(jax.jit, static_argnames=("key", "mesh"))
def dist_key_set_build(table: Table, key: str, mesh: Mesh
                       ) -> tuple[jax.Array, jax.Array]:
    """Distributed semi-join build: per-device *distinct* key lists,
    all_gather + merge-dedupe on the sorted machinery in ``core.psts``.

    Each device first folds its own partition into a local ``key_set``
    (sorted distinct live keys + sentinel padding) — local dedupe before
    the exchange, so duplicated hot keys are shipped once per device, not
    once per row. The padded partial lists are then all_gathered — the
    (p-1)·m/8-byte wire volume ``filter_reduce_cost(kind="semi_join")``
    charges — and merge-deduped with a second ``key_set`` pass over the
    gathered material, masking each partial to its live prefix.

    Returns ``(sorted_keys, n_distinct)`` with the same static shape as —
    and value-identical to — the global-view ``key_set`` over the
    concatenated column at any device count: distinct-of-union equals
    union-of-distincts, and sorting canonicalizes the order.
    """

    def f(col, valid):
        local, n_local = key_set(col[0], valid[0])
        gathered = jax.lax.all_gather(local, AXIS)     # (p, cap)
        counts = jax.lax.all_gather(n_local, AXIS)     # (p,)
        live = (jnp.arange(gathered.shape[1])[None, :] < counts[:, None])
        merged, n = key_set(gathered.reshape(-1), live.reshape(-1))
        return merged[None], n[None]

    keys, n = jax.shard_map(
        f, mesh=mesh, in_specs=(P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS)),
    )(table.column(key), table.valid)
    # Every device holds the identical merged key set; take one replica.
    return keys[0], n[0]


@functools.partial(jax.jit, static_argnames=("a_key", "b_key", "mesh"))
def dist_broadcast_hash_join(a: Table, b: Table, a_key: str, b_key: str,
                             mesh: Mesh) -> Table:
    def f(a_cols, a_valid, b_cols, b_valid):
        a_cols = {n: c[0] for n, c in a_cols.items()}
        b_cols = {n: c[0] for n, c in b_cols.items()}
        fb_cols, fb_valid = _local_broadcast(b_cols, b_valid[0],
                                             mesh.shape[AXIS])
        res = hash_join(a_cols[a_key], a_valid[0], fb_cols[b_key], fb_valid)
        out_cols, out_valid = _attach(a_cols, a_valid[0], fb_cols, res)
        return ({n: c[None] for n, c in out_cols.items()}, out_valid[None])

    cols, valid = jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS)),
    )(a.columns, a.valid, b.columns, b.valid)
    return Table(cols, valid)

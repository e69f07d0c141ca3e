"""Columnar tables on JAX arrays with static shapes + validity masks.

XLA needs static shapes, so a Table has a fixed row *capacity*; the live rows
are marked in ``valid``. A *stacked* table carries a leading partition axis
``(p, cap)`` — the engine's unit of distribution; an *unstacked* table
``(cap,)`` is a single partition (or a broadcast replica).

The measured (size, cardinality) of the valid rows IS the paper's adaptive
runtime statistic; ``measure()`` produces it after every exchange.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.stats import StatsSource, TableStats


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Table:
    """Columnar table: dict of same-shape arrays + validity mask.

    ``partitioned_by`` records the hash-partitioning key when the table was
    produced by a shuffle on that key (Spark's output-partitioning property):
    a subsequent shuffle on the same key is elided (§3.7's key-dependency
    case where C_shuffle = 0).
    """

    columns: Dict[str, jax.Array]
    valid: jax.Array  # bool, shape == each column's shape
    partitioned_by: str | None = None

    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        leaves = tuple(self.columns[n] for n in names) + (self.valid,)
        return leaves, (names, self.partitioned_by)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        names, part = aux
        return cls(dict(zip(names, leaves[:-1])), leaves[-1], part)

    # -- structure ----------------------------------------------------------

    @property
    def stacked(self) -> bool:
        return self.valid.ndim == 2

    @property
    def num_partitions(self) -> int:
        return self.valid.shape[0] if self.stacked else 1

    @property
    def capacity(self) -> int:
        return self.valid.shape[-1]

    @property
    def row_bytes(self) -> int:
        return int(sum(np.dtype(c.dtype).itemsize
                       for c in self.columns.values()))

    def column(self, name: str) -> jax.Array:
        return self.columns[name]

    def with_columns(self, columns: Dict[str, jax.Array]) -> "Table":
        return Table(columns, self.valid, self.partitioned_by)

    def with_valid(self, valid: jax.Array) -> "Table":
        return Table(self.columns, valid, self.partitioned_by)

    def select(self, names) -> "Table":
        part = self.partitioned_by if self.partitioned_by in names else None
        return Table({n: self.columns[n] for n in names}, self.valid, part)

    # -- statistics ----------------------------------------------------------

    def count(self) -> int:
        """Concrete number of valid rows (host sync)."""
        return int(obs.fetch(jnp.sum(self.valid)))

    def measure(self) -> TableStats:
        """Adaptive runtime statistic of this materialized dataset."""
        rows = self.count()
        return TableStats(rows * self.row_bytes, rows, StatsSource.RUNTIME)

    # -- conversion ----------------------------------------------------------

    def to_numpy(self) -> Dict[str, np.ndarray]:
        """Compacted valid rows as numpy (host-side; for tests/oracles)."""
        v = np.asarray(obs.fetch(self.valid)).reshape(-1)
        out = {}
        for n, c in self.columns.items():
            out[n] = np.asarray(obs.fetch(c)).reshape(-1)[v]
        return out


def from_numpy(columns: Dict[str, np.ndarray], capacity: int | None = None
               ) -> Table:
    """Build an unstacked table; pads to ``capacity`` with invalid rows."""
    n = len(next(iter(columns.values())))
    cap = capacity or n
    if cap < n:
        raise ValueError(f"capacity {cap} < rows {n}")
    cols, pad = {}, cap - n
    for name, arr in columns.items():
        a = np.asarray(arr)
        if a.dtype == np.int64:
            a = a.astype(np.int32)
        if a.dtype == np.float64:
            a = a.astype(np.float32)
        cols[name] = jnp.asarray(np.pad(a, (0, pad)))
    valid = jnp.asarray(np.arange(cap) < n)
    return Table(cols, valid)


def partition_round_robin(table: Table, p: int) -> Table:
    """Split an unstacked table into p partitions (initial data placement,
    like HDFS blocks landing on executors). Capacity must divide by p."""
    if table.stacked:
        raise ValueError("already stacked")
    cap = table.capacity
    per = -(-cap // p)
    pad = per * p - cap
    cols = {n: jnp.pad(c, (0, pad)).reshape(p, per)
            for n, c in table.columns.items()}
    valid = jnp.pad(table.valid, (0, pad), constant_values=False
                    ).reshape(p, per)
    return Table(cols, valid)


def compact_partitions(table: Table, capacity: int | None = None,
                       slack: float = 1.1) -> Table:
    """Pack valid rows to the front of each partition and shrink capacity.

    Keeps post-join tables from growing unboundedly across a join chain
    (Spark analog: AQE's post-stage partition coalescing). Host-syncs the
    max per-partition live count, like any stage materialization.

    The chosen capacity is rounded up to a power of two: downstream join
    kernels then see a small set of distinct shapes, so XLA compilations
    are reused across stages, queries, and strategies instead of
    recompiling for every data-dependent row count.
    """
    if not table.stacked:
        raise ValueError("compact expects a stacked table")
    with obs.span("op.compact"):
        need = int(obs.fetch(_max_live(table.valid)))
        cap = capacity or max(
            8, 1 << (max(int(need * slack), 1) - 1).bit_length())
        cap = min(cap, table.capacity)
        order = _front_order(table.valid, cap)
        cols = {n: jnp.take_along_axis(c, order, axis=1)
                for n, c in table.columns.items()}
        valid = jnp.take_along_axis(table.valid, order, axis=1)
    return Table(cols, valid, table.partitioned_by)


@jax.jit
def _max_live(valid: jax.Array) -> jax.Array:
    return jnp.max(jnp.sum(valid, axis=1))


@functools.partial(jax.jit, static_argnames=("cap",))
def _front_order(valid: jax.Array, cap: int) -> jax.Array:
    """Per partition, the first ``cap`` row indices with valid rows first
    (stable)."""
    return jnp.argsort(~valid, axis=1, stable=True)[:, :cap]


def concat_partitions(table: Table) -> Table:
    """Flatten a stacked table into a single logical partition view."""
    if not table.stacked:
        return table
    cols = {n: c.reshape(-1) for n, c in table.columns.items()}
    return Table(cols, table.valid.reshape(-1))

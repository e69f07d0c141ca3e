"""Group-by aggregation — the other exchange-bounded operation (paper §1:
"every join or group-by-like operation" updates runtime statistics).

Distributed plan: shuffle rows by group key (same exchange as the shuffle
joins), then aggregate each co-partition locally: sort by key, mark segment
heads, segment-sum. Static shapes throughout; output rows are the segment
heads (cardinality = #groups, the runtime statistic of the stage).
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import obs
from .exchange import ExchangeReport, shuffle
from .table import Table

AGG_OPS = ("sum", "count", "min", "max", "mean")


def _local_segments(key: jax.Array, valid: jax.Array):
    """Group one partition by key: the sort order, each sorted row's group
    id, the live (valid) sorted rows, the group-head rows that carry the
    results, and the group keys at those heads."""
    big = jnp.iinfo(jnp.int32).max
    k = jnp.where(valid, key, big).astype(jnp.int32)
    order = jnp.argsort(k)
    ks = k[order]
    head = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
    seg = jnp.cumsum(head.astype(jnp.int32)) - 1          # group id per row
    live = ks != big
    out_valid = head & live
    return order, seg, live, out_valid, jnp.where(out_valid, ks, 0)


def _local_agg_column(v: jax.Array, order: jax.Array, seg: jax.Array,
                      live: jax.Array, op: str) -> jax.Array:
    """One aggregate of one partition's column over its key groups; each
    row reads its group's result (only head rows stay valid)."""
    n = v.shape[0]
    v = v[order]
    if op == "count":
        seg_out = jax.ops.segment_sum(live.astype(jnp.int32), seg,
                                      num_segments=n)
    elif op in ("sum", "mean"):
        data = jnp.where(live, v, 0)
        seg_out = jax.ops.segment_sum(data, seg, num_segments=n)
        if op == "mean":
            cnt = jax.ops.segment_sum(live.astype(v.dtype), seg,
                                      num_segments=n)
            seg_out = seg_out / jnp.maximum(cnt, 1)
    elif op == "min":
        data = jnp.where(live, v, jnp.asarray(jnp.inf, v.dtype)
                         if jnp.issubdtype(v.dtype, jnp.floating)
                         else jnp.iinfo(v.dtype).max)
        seg_out = jax.ops.segment_min(data, seg, num_segments=n)
    elif op == "max":
        data = jnp.where(live, v, jnp.asarray(-jnp.inf, v.dtype)
                         if jnp.issubdtype(v.dtype, jnp.floating)
                         else jnp.iinfo(v.dtype).min)
        seg_out = jax.ops.segment_max(data, seg, num_segments=n)
    else:
        raise ValueError(f"unknown agg op {op}")
    return jnp.take(seg_out, seg)


# One program per shape (and per dtype and op), whatever the columns are
# called: the group-by compiles once for every query of a shape.
_segments = jax.jit(jax.vmap(_local_segments))


@functools.partial(jax.jit, static_argnames=("op",))
def _agg_column(v, order, seg, live, op: str) -> jax.Array:
    return jax.vmap(lambda *a: _local_agg_column(*a, op))(v, order, seg,
                                                          live)


def group_aggregate(table: Table, key: str,
                    aggs: Sequence[Tuple[str, str]],
                    capacity_factor: float = 2.0
                    ) -> tuple[Table, ExchangeReport]:
    """Distributed group-by: shuffle by key + local segment aggregation."""
    if not table.stacked:
        raise ValueError("group_aggregate expects a stacked table")
    with obs.span("op.aggregate"):
        shuffled, report = shuffle(table, key, capacity_factor)
        order, seg, live, out_valid, group_key = _segments(
            shuffled.column(key), shuffled.valid)
        out_cols = {f"{op}_{col}": _agg_column(shuffled.column(col), order,
                                               seg, live, op)
                    for col, op in aggs}
    out_cols[key] = group_key
    # Output is hash-partitioned by the group key: downstream shuffles on
    # the same key are elided (§3.7 key-dependency).
    return Table(out_cols, out_valid, partitioned_by=key), report


def global_aggregate(table: Table, aggs: Sequence[Tuple[str, str]]
                     ) -> Dict[str, float]:
    """Whole-table scalar aggregates (query result tails)."""
    out = {}
    v = table.valid
    for col, op in aggs:
        c = table.column(col)
        if op == "count":
            out[f"count_{col}"] = float(obs.fetch(jnp.sum(v)))
        elif op == "sum":
            out[f"sum_{col}"] = float(obs.fetch(jnp.sum(jnp.where(v, c, 0))))
        elif op == "mean":
            s = float(obs.fetch(jnp.sum(jnp.where(v, c, 0))))
            n = float(obs.fetch(jnp.sum(v)))
            out[f"mean_{col}"] = s / max(n, 1.0)
        elif op == "min":
            out[f"min_{col}"] = float(obs.fetch(
                jnp.min(jnp.where(v, c, jnp.inf))))
        elif op == "max":
            out[f"max_{col}"] = float(obs.fetch(
                jnp.max(jnp.where(v, c, -jnp.inf))))
    return out

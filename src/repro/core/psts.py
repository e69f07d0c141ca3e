"""Performance Sensitivity To Selections (PSTS) — paper §5.4, Table 5 —
plus the distinct-key machinery the exact semi-join reducer builds on.

PSTS = %TimeDiff / %JoinDiff with a baseline strategy (AQE in the paper):

    %JoinDiff = (# joins where the strategy and the baseline select different
                 methods) / (total # joins) * 100
    %TimeDiff = (baseline total time - strategy total time)
                / baseline total time * 100

PSTS > 0: the strategy's differing selections help; ~1 means 1% of selection
changes buys 1% completion-time reduction. Near 0 / negative: ineffective or
harmful (paper: ShuffleSort -0.03, ShuffleHash -0.04, RelJoin 1.98).

The selection-difference accounting above and semi-join reduction answer
the same underlying question — *which distinct join keys actually
participate?* — so the distinct-key helpers live here: ``key_set`` folds a
(possibly duplicated, partially invalid) key column into a sorted
membership structure, ``distinct_count`` sizes it, and ``semi_join_mask``
is the exact probe — the zero-false-positive reducer the runtime-filter
planner weighs against bloom filters and zone maps.

**Distributed-equivalence contract.** ``key_set`` is a pure function of
the key *set* (order- and duplication-invariant, canonical sorted
serialization), which makes it the merge operator of its own distributed
build: ``joins.distributed.dist_key_set_build`` runs ``key_set`` per
device, all_gathers the partial lists, and merge-dedupes with a second
``key_set`` pass — value-identical (array and count) to the global
``key_set`` over the concatenated column at any device count, because
distinct-of-union equals union-of-distincts. ``semi_join_mask`` therefore
produces the same probe mask whether its key set was built globally or
distributed — the property the runtime-filter executor and the
cross-query ``FilterCache`` both rest on.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from .. import obs
from .cost_model import JoinMethod

#: Sentinel used to pad the sorted key set to its static capacity. Chosen
#: as INT32_MAX so padding sorts to the tail; a real key equal to the
#: sentinel would be indistinguishable from padding, so ``key_set`` tracks
#: the live count separately and ``semi_join_mask`` only consults the
#: live prefix.
KEY_SET_SENTINEL = 2 ** 31 - 1


def key_set(keys: jax.Array, valid: jax.Array | None = None
            ) -> tuple[jax.Array, jax.Array]:
    """Sorted distinct-key membership structure of the valid entries.

    Returns ``(sorted_keys, n_distinct)``: an int32 array of the input's
    flattened (static) shape — distinct live keys sorted ascending, then
    sentinel padding — and the scalar count of distinct live keys. Pure
    function of the key *set*: duplicates and input order do not change
    the result (the property serialization / bit-identity tests pin).
    """
    flat = keys.reshape(-1).astype(jnp.int32)
    v = (jnp.ones(flat.shape, jnp.bool_) if valid is None
         else valid.reshape(-1).astype(jnp.bool_))
    if flat.shape[0] == 0:
        return flat, jnp.int32(0)
    # Invalid rows sort to the tail as sentinels; duplicate live keys are
    # then sentinel-ed too (first occurrence wins) and re-sorted away.
    # Positions < n_valid hold exactly the sorted live keys, so masking the
    # duplicate test to that prefix keeps the arithmetic correct even for a
    # live key that happens to equal the sentinel value.
    s = jnp.sort(jnp.where(v, flat, KEY_SET_SENTINEL))
    n_valid = jnp.sum(v)
    live = jnp.arange(s.shape[0]) < n_valid
    dup = jnp.concatenate([jnp.zeros((1,), jnp.bool_),
                           s[1:] == s[:-1]]) & live
    distinct = jnp.sort(jnp.where(dup, KEY_SET_SENTINEL, s))
    return distinct, n_valid - jnp.sum(dup)


def distinct_count(keys: jax.Array, valid: jax.Array | None = None) -> int:
    """Concrete number of distinct valid keys (host sync)."""
    _, n = key_set(keys, valid)
    return int(obs.fetch(n))


def semi_join_mask(probe_keys: jax.Array, sorted_keys: jax.Array,
                   n: jax.Array | int | None = None) -> jax.Array:
    """Exact membership mask of ``probe_keys`` against a ``key_set``.

    Binary search on the sorted array (log2 n compares per probe, all
    vectorized) — no hashing, no false positives, no false negatives.
    ``n`` bounds the live prefix; rows landing in the sentinel padding are
    rejected. Same shape as ``probe_keys``.
    """
    flat = probe_keys.reshape(-1).astype(jnp.int32)
    if sorted_keys.shape[0] == 0:
        return jnp.zeros(probe_keys.shape, jnp.bool_)
    idx = jnp.searchsorted(sorted_keys, flat)
    idx = jnp.clip(idx, 0, sorted_keys.shape[0] - 1)
    hit = jnp.take(sorted_keys, idx) == flat
    if n is not None:
        hit = hit & (idx < n)
    else:
        hit = hit & (jnp.take(sorted_keys, idx) != KEY_SET_SENTINEL)
    return hit.reshape(probe_keys.shape)


def _is_shuffle(m: JoinMethod) -> bool:
    # Paper §5.4 treats shuffle sort and shuffle hash as the same method when
    # counting selection differences (their performance is near-identical).
    return m in (JoinMethod.SHUFFLE_SORT, JoinMethod.SHUFFLE_HASH,
                 JoinMethod.SALTED_SHUFFLE_HASH, JoinMethod.CARTESIAN)


def selections_differ(m1: JoinMethod, m2: JoinMethod) -> bool:
    """Broadcast-vs-shuffle is the difference that matters (paper §5.4)."""
    return _is_shuffle(m1) != _is_shuffle(m2)


@dataclasses.dataclass(frozen=True)
class PSTSReport:
    n_join_diff: int
    n_joins: int
    cost_diff: float
    time_diff: float
    pct_join_diff: float
    pct_time_diff: float
    psts: float

    def cost_diff_per_join(self) -> float:
        return self.cost_diff / self.n_join_diff if self.n_join_diff else 0.0

    def time_diff_per_join(self) -> float:
        return self.time_diff / self.n_join_diff if self.n_join_diff else 0.0


def compute_psts(strategy_methods: Sequence[JoinMethod],
                 baseline_methods: Sequence[JoinMethod],
                 strategy_time: float, baseline_time: float,
                 strategy_costs: Sequence[float] = (),
                 baseline_costs: Sequence[float] = ()) -> PSTSReport:
    """Compute the Table-5 statistics for one benchmark run."""
    if len(strategy_methods) != len(baseline_methods):
        raise ValueError("selection sequences must align join-for-join")
    n = len(strategy_methods)
    diffs = [i for i in range(n)
             if selections_differ(strategy_methods[i], baseline_methods[i])]
    cost_diff = 0.0
    if strategy_costs and baseline_costs:
        cost_diff = sum(baseline_costs[i] - strategy_costs[i] for i in diffs)
    time_diff = baseline_time - strategy_time
    pct_join = 100.0 * len(diffs) / n if n else 0.0
    pct_time = 100.0 * time_diff / baseline_time if baseline_time else 0.0
    psts = pct_time / pct_join if pct_join else 0.0
    return PSTSReport(len(diffs), n, cost_diff, time_diff, pct_join, pct_time,
                      psts)

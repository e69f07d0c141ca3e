"""Runtime-filter framework: zone-map / semi-join kinds + kind selection.

Covers the three layers the pluggable framework spans:

  * kernels — the tiled min/max reduce (``key_range``) against its numpy
    reference, and the exact distinct-key machinery in ``core.psts``
    (no false positives OR negatives, order/duplication invariance);
  * planner — per-edge kind quoting: zone map only for band-shaped build
    keys, semi-join winning small exact key sets, bloom as the dense
    default, the strict cost gate at sigma = 1, and the ``kinds``
    restriction reproducing bloom-only behaviour;
  * executor — q22 picks zone_map, q23 picks semi_join, both preserve
    results and cut probe-shuffle bytes; plus the aggregate group-key
    sigma regression (filters planned even without header FK metadata).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.cost_model import (CostParams, ZONE_MAP_BITS,
                                   cached_filter_cost, semi_join_cost,
                                   zone_map_cost)
from repro.core.psts import distinct_count, key_set, semi_join_mask
from repro.joins.ref import rows_as_set, rows_close
from repro.kernels.ops import key_range, range_probe
from repro.kernels.ref import key_range_ref
from repro.sql import (Executor, FilterCache, FilteredStrategy,
                       RelJoinStrategy, filter_cache_key, filtered_queries,
                       generate, plan_runtime_filters)
from repro.sql.datagen import Catalog
from repro.sql.logical import (Aggregate, Filter, Join, JoinEdge, Project,
                               Scan, key_band_fraction, key_retain_fraction)
from repro.core.stats import TableStats


# ---------------------------------------------------------------------------
# Kernel: tiled min/max reduce
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,seed", [(1, 0), (7, 1), (64, 2), (1000, 3),
                                    (4096, 4)])
def test_key_range_matches_ref(n, seed):
    rng = np.random.default_rng(seed)
    keys = rng.integers(-(1 << 30), 1 << 30, n).astype(np.int32)
    valid = rng.random(n) < 0.6
    got = np.asarray(key_range(jnp.asarray(keys), jnp.asarray(valid)))
    assert (got == key_range_ref(keys, valid)).all()
    # valid=None counts every row
    got_all = np.asarray(key_range(jnp.asarray(keys)))
    assert (got_all == key_range_ref(keys)).all()


def test_key_range_empty_interval_rejects_all():
    """All-invalid build -> empty interval (lo > hi) -> probe keeps none:
    the degenerate-build contract shared with the zero bloom filter."""
    keys = np.arange(100, dtype=np.int32)
    lo_hi = key_range(jnp.asarray(keys), jnp.zeros(100, bool))
    assert int(lo_hi[0]) > int(lo_hi[1])
    mask = np.asarray(range_probe(jnp.asarray(keys), lo_hi))
    assert not mask.any()


def test_range_probe_no_false_negatives():
    """Every build key passes its own zone map; outside keys may pass only
    if they fall inside the band (false positives), never the reverse."""
    rng = np.random.default_rng(7)
    build = rng.integers(100, 200, 500).astype(np.int32)
    lo_hi = key_range(jnp.asarray(build))
    assert np.asarray(range_probe(jnp.asarray(build), lo_hi)).all()
    probe = rng.integers(0, 400, 2000).astype(np.int32)
    mask = np.asarray(range_probe(jnp.asarray(probe), lo_hi))
    inside = (probe >= build.min()) & (probe <= build.max())
    assert (mask == inside).all()


# ---------------------------------------------------------------------------
# Distinct-key machinery (core.psts) / exact semi-join reducer
# ---------------------------------------------------------------------------


def test_key_set_dedup_and_order_invariance():
    rng = np.random.default_rng(0)
    base = rng.integers(-1000, 1000, 300).astype(np.int32)
    dup = np.repeat(base, 3)
    a, na = key_set(jnp.asarray(dup))
    b, nb = key_set(jnp.asarray(rng.permutation(dup)))
    want = np.unique(base)
    assert int(na) == int(nb) == len(want)
    assert (np.asarray(a)[:len(want)] == want).all()
    # Serialized prefix is a pure function of the key *set*.
    assert (np.asarray(a)[:len(want)] == np.asarray(b)[:len(want)]).all()
    assert distinct_count(jnp.asarray(dup)) == len(want)


def test_semi_join_mask_is_exact():
    """No false positives AND no false negatives — the property that
    distinguishes the exact reducer from bloom's fpr floor."""
    rng = np.random.default_rng(1)
    build = rng.integers(0, 500, 120).astype(np.int32)
    valid = rng.random(120) < 0.5
    ks, n = key_set(jnp.asarray(build), jnp.asarray(valid))
    probe = rng.integers(-100, 700, 5000).astype(np.int32)
    mask = np.asarray(semi_join_mask(jnp.asarray(probe), ks, n))
    assert (mask == np.isin(probe, build[valid])).all()


def test_semi_join_mask_empty_build_rejects_all():
    ks, n = key_set(jnp.asarray(np.arange(8, dtype=np.int32)),
                    jnp.zeros(8, bool))
    assert int(n) == 0
    mask = np.asarray(semi_join_mask(jnp.arange(100, dtype=jnp.int32),
                                     ks, n))
    assert not mask.any()


# ---------------------------------------------------------------------------
# Band / key-retain analysis on logical leaves
# ---------------------------------------------------------------------------


def test_key_band_fraction_requires_range_on_key():
    date = Scan("date_dim")
    on_key = Filter(date, "d_date_sk", "lt", 90, selectivity=0.25)
    off_key = Filter(date, "d_month", "eq", 6, selectivity=1 / 12)
    assert key_band_fraction(on_key, "d_date_sk") == pytest.approx(0.25)
    # A predicate on another column does not make the key set a band.
    assert key_band_fraction(off_key, "d_date_sk") is None
    # Stacked: the band tightens only with the key's own predicates.
    both = Filter(on_key, "d_month", "eq", 6, selectivity=1 / 12)
    assert key_band_fraction(both, "d_date_sk") == pytest.approx(0.25)
    # Band analysis descends projections.
    proj = Project(on_key, ("d_date_sk",))
    assert key_band_fraction(proj, "d_date_sk") == pytest.approx(0.25)


def test_key_retain_fraction_sees_through_aggregates():
    """Group keys survive grouping: a filter on the group key below the
    Aggregate still thins the key set the leaf exposes — this is the
    pushdown-through-aggregates sigma fix."""
    agg = Aggregate(Filter(Scan("catalog_sales"), "cs_item_sk", "lt", 200,
                           selectivity=0.1), "cs_item_sk",
                    (("cs_sales_price", "sum"),))
    assert key_retain_fraction(agg, "cs_item_sk") == pytest.approx(0.1)
    # A filter on a non-key column below the aggregate is conservative 1.0.
    agg2 = Aggregate(Filter(Scan("catalog_sales"), "cs_quantity", "lt", 10,
                            selectivity=0.1), "cs_item_sk",
                     (("cs_sales_price", "sum"),))
    assert key_retain_fraction(agg2, "cs_item_sk") == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Planner: per-edge kind selection
# ---------------------------------------------------------------------------


def _stats(size, card):
    return TableStats(float(size), float(card))


_EDGE = [JoinEdge(0, 1, "fk", "pk")]
_PARAMS = CostParams(p=8, w=1.0)


def test_planner_picks_zone_map_for_banded_build():
    probe, build = _stats(1 << 20, 32_768), _stats(2_048, 128)
    leaves = [Scan("fact"),
              Filter(Scan("dim"), "pk", "lt", 128, selectivity=0.25)]
    planned = plan_runtime_filters(_EDGE, [probe, build], [1.0, 0.25],
                                   _PARAMS, leaves=leaves)
    assert len(planned) == 1 and planned[0].kind == "zone_map"
    assert planned[0].m_bits == ZONE_MAP_BITS
    assert planned[0].cost == pytest.approx(zone_map_cost(_PARAMS))


def test_planner_picks_semi_join_for_tiny_exact_sets():
    """5 distinct keys: 160 bits exact vs the 256-bit bloom minimum."""
    probe, build = _stats(1 << 20, 32_768), _stats(80, 5)
    leaves = [Scan("fact"),
              Filter(Scan("dim"), "payload", "eq", 0, selectivity=0.08)]
    planned = plan_runtime_filters(_EDGE, [probe, build], [1.0, 0.08],
                                   _PARAMS, leaves=leaves)
    assert len(planned) == 1 and planned[0].kind == "semi_join"
    assert planned[0].cost == pytest.approx(semi_join_cost(5, _PARAMS))
    assert planned[0].keep_est == pytest.approx(0.08)


def test_planner_defaults_to_bloom_for_large_scattered_sets():
    probe, build = _stats(1 << 20, 32_768), _stats(1 << 14, 1_024)
    leaves = [Scan("fact"),
              Filter(Scan("dim"), "payload", "lt", 1, selectivity=0.1)]
    planned = plan_runtime_filters(_EDGE, [probe, build], [1.0, 0.1],
                                   _PARAMS, leaves=leaves)
    assert len(planned) == 1 and planned[0].kind == "bloom"


def test_planner_kind_restriction_reproduces_bloom_only():
    probe, build = _stats(1 << 20, 32_768), _stats(2_048, 128)
    leaves = [Scan("fact"),
              Filter(Scan("dim"), "pk", "lt", 128, selectivity=0.25)]
    planned = plan_runtime_filters(_EDGE, [probe, build], [1.0, 0.25],
                                   _PARAMS, leaves=leaves, kinds=("bloom",))
    assert len(planned) == 1 and planned[0].kind == "bloom"


def test_planner_plans_nothing_at_sigma_one_for_every_kind():
    """The parity guarantee generalizes: an unfiltered build offers no
    kind anything to cut (the banded case keeps band >= sigma = 1)."""
    probe, build = _stats(1 << 20, 32_768), _stats(1 << 14, 1_024)
    leaves = [Scan("fact"), Scan("dim")]
    assert plan_runtime_filters(_EDGE, [probe, build], [1.0, 1.0],
                                _PARAMS, leaves=leaves) == []


# ---------------------------------------------------------------------------
# Executor: end-to-end kind selection on q22/q23
# ---------------------------------------------------------------------------


def _rows(res):
    return rows_as_set(res.table.to_numpy())


def test_q22_selects_zone_map(catalog):
    plan = filtered_queries()["q22_zone_map_window"]
    base = Executor(catalog, RelJoinStrategy()).execute(plan)
    filt = Executor(catalog, FilteredStrategy()).execute(plan)
    assert [f.plan.kind for f in filt.filters] == ["zone_map"]
    assert rows_close(_rows(filt), _rows(base))
    assert filt.probe_shuffle_bytes < 0.5 * base.probe_shuffle_bytes
    # The zone map's wire size undercuts any bloom array by construction.
    assert filt.filters[0].plan.m_bits == ZONE_MAP_BITS


def test_q23_selects_semi_join(catalog):
    plan = filtered_queries()["q23_semi_join_stores"]
    base = Executor(catalog, RelJoinStrategy()).execute(plan)
    filt = Executor(catalog, FilteredStrategy()).execute(plan)
    assert [f.plan.kind for f in filt.filters] == ["semi_join"]
    assert rows_close(_rows(filt), _rows(base))
    assert filt.probe_shuffle_bytes < 0.5 * base.probe_shuffle_bytes
    # Exact reducer: measured keep equals the true match fraction, no
    # false-positive slack on top.
    f = filt.filters[0]
    assert f.rows_after <= f.rows_before


def test_bloom_only_configuration_still_filters(catalog):
    """kinds=("bloom",) reproduces PR-3 behaviour on the new queries: a
    bloom filter is planned (it still beats no filter), just not the
    cheaper specialized kind."""
    plan = filtered_queries()["q22_zone_map_window"]
    filt = Executor(catalog,
                    FilteredStrategy(kinds=("bloom",))).execute(plan)
    assert [f.plan.kind for f in filt.filters] == ["bloom"]


# ---------------------------------------------------------------------------
# Cross-query filter cache: key normalization + hit/miss/invalidation
# ---------------------------------------------------------------------------


def test_filter_cache_key_normalizes_predicate_order():
    """Conjunctive filters commute, so stacking order must not split the
    cache: F1(F2(scan)) and F2(F1(scan)) share an entry. Projections are
    transparent (they never change the key column's values)."""
    f1 = dict(column="d_month", op="eq", value=6.0, selectivity=1 / 12)
    f2 = dict(column="d_date_sk", op="lt", value=90.0, selectivity=0.25)
    a = Filter(Filter(Scan("date_dim"), **f1), **f2)
    b = Filter(Filter(Scan("date_dim"), **f2), **f1)
    ka = filter_cache_key(a, "d_date_sk", "bloom", 1024, 7)
    kb = filter_cache_key(b, "d_date_sk", "bloom", 1024, 7)
    assert ka is not None and ka == kb
    proj = Project(a, ("d_date_sk",))
    assert filter_cache_key(proj, "d_date_sk", "bloom", 1024, 7) == ka
    # Different kind / size params are different payloads.
    assert filter_cache_key(a, "d_date_sk", "zone_map", 64, 0) != ka
    assert filter_cache_key(a, "d_date_sk", "bloom", 2048, 7) != ka


def test_filter_cache_key_rejects_non_scan_leaves():
    """Aggregated subqueries' key sets depend on subtree execution — the
    normalization does not capture that, so they are uncacheable."""
    agg = Aggregate(Scan("catalog_sales"), "cs_item_sk",
                    (("cs_sales_price", "sum"),))
    assert filter_cache_key(agg, "cs_item_sk", "bloom", 1024, 7) is None


def test_planner_quotes_cache_hits_without_build_terms():
    """A cached kind is quoted at cached_filter_cost (broadcast only);
    with an empty cache the quote — and the planned filter — is
    byte-identical to the uncached planner's."""
    probe, build = _stats(1 << 20, 32_768), _stats(2_048, 128)
    leaves = [Scan("fact"),
              Filter(Scan("dim"), "pk", "lt", 128, selectivity=0.25)]
    cold = plan_runtime_filters(_EDGE, [probe, build], [1.0, 0.25],
                                _PARAMS, leaves=leaves, cache=FilterCache())
    bare = plan_runtime_filters(_EDGE, [probe, build], [1.0, 0.25],
                                _PARAMS, leaves=leaves)
    assert cold == bare and not cold[0].cached
    cache = FilterCache()
    rf = cold[0]
    cache.store(filter_cache_key(leaves[1], rf.build_key, rf.kind,
                                 rf.m_bits, rf.k),
                payload="sentinel", build_stats=build)
    warm = plan_runtime_filters(_EDGE, [probe, build], [1.0, 0.25],
                                _PARAMS, leaves=leaves, cache=cache)
    assert warm[0].cached
    assert warm[0].cost == pytest.approx(
        cached_filter_cost(rf.m_bits, _PARAMS))
    assert warm[0].cost < rf.cost


def test_executor_cache_hit_miss_and_zero_rebuild(catalog):
    """End to end: the first run misses and populates, the repeat run
    reuses every payload (zero reduce bytes) with identical results."""
    plan = filtered_queries()["q19_filtered_customer"]
    cache = FilterCache()
    strat = FilteredStrategy(cache=cache)
    cold = Executor(catalog, strat).execute(plan)
    assert cold.filters and cold.cached_filters == 0
    assert cache.misses == len(cold.filters) and cache.hits == 0
    assert cold.filter_reduce_bytes > 0
    warm = Executor(catalog, strat).execute(plan)
    assert warm.cached_filters == len(warm.filters) == len(cold.filters)
    assert warm.filter_reduce_bytes == 0.0
    assert cache.hits == len(warm.filters)
    assert rows_close(_rows(warm), _rows(cold))
    # Every stored payload carries the measured build-side stats.
    stored = [cache.build_stats(k) for k in cache._entries]
    assert stored and all(s is not None and s.cardinality > 0
                          for s in stored)
    assert cache.build_stats(None) is None  # uncacheable key -> no stats


def test_filter_cache_invalidates_on_catalog_change(catalog):
    """Payloads built against one catalog version must never filter
    another: regenerated data invalidates every entry."""
    plan = filtered_queries()["q19_filtered_customer"]
    cache = FilterCache()
    strat = FilteredStrategy(cache=cache)
    Executor(catalog, strat).execute(plan)
    assert len(cache) > 0
    other = generate(scale=0.1, p=4, seed=43)
    res = Executor(other, strat).execute(plan)
    assert cache.invalidations == 1
    assert res.cached_filters == 0          # nothing stale was reused
    # Back on the original catalog: the entries built against it are gone
    # too (validity is a binding, not a per-catalog pool).
    res2 = Executor(catalog, strat).execute(plan)
    assert res2.cached_filters == 0 and cache.invalidations == 2


def test_two_catalogs_sharing_a_version_never_share_payloads(catalog):
    """Regression: ``FilterCache.sync`` used to bind by version integer
    alone, so two distinct Catalog instances that happened to share a
    version number silently reused each other's payloads — wrong rows
    (a payload filters against the *other* catalog's customer data), not
    just a perf miss. The binding is now the full identity fingerprint
    (version + generation uid), so a forced version collision must still
    invalidate."""
    plan = filtered_queries()["q19_filtered_customer"]
    cache = FilterCache()
    strat = FilteredStrategy(cache=cache)
    Executor(catalog, strat).execute(plan)
    assert len(cache) > 0
    other = generate(scale=0.1, p=4, seed=43)
    other.version = catalog.version     # version collision, different data
    assert other.uid != catalog.uid
    base = Executor(other, RelJoinStrategy()).execute(plan)
    res = Executor(other, strat).execute(plan)
    assert cache.invalidations == 1     # uid mismatch invalidated
    assert res.cached_filters == 0      # nothing foreign was reused
    assert rows_close(_rows(res), _rows(base))


def test_masked_build_side_is_not_cached(catalog):
    """A payload built from a build table that was itself masked by
    another runtime filter of the same query must NOT be stored under
    the chain-only cache key: a later query reusing it would drop rows
    that only the first query's extra join excludes (false negatives).

    Snowflake shape: household's zone map masks customer first, then the
    fact<-customer bloom is built from the *masked* customer — that
    second payload is the poisoned one."""
    cust = Filter(Scan("customer"), "c_region", "eq", 3, selectivity=0.125)
    hh = Filter(Scan("household"), "hd_demo_sk", "lt", 300,
                selectivity=0.1)
    snowflake = Join(Scan("store_sales"),
                     Join(cust, hh, "c_hdemo_sk", "hd_demo_sk"),
                     "ss_customer_sk", "c_customer_sk")
    two_way = Join(Scan("store_sales"), cust,
                   "ss_customer_sk", "c_customer_sk")
    cache = FilterCache()
    strat = FilteredStrategy(cache=cache)
    res1 = Executor(catalog, strat).execute(snowflake)
    # The scenario is real: both filters planned, customer masked before
    # the fact<-customer payload is built from it.
    assert len(res1.filters) == 2
    assert [f.plan.build_key for f in res1.filters] == ["hd_demo_sk",
                                                        "c_customer_sk"]
    # Only household's (clean) payload may be stored.
    assert len(cache) == 1
    # The two-way query must rebuild customer's filter from its true
    # static chain and produce exactly the uncached result.
    base = Executor(catalog, RelJoinStrategy()).execute(two_way)
    res2 = Executor(catalog, strat).execute(two_way)
    assert res2.filters and all(not f.cached for f in res2.filters)
    assert rows_close(_rows(res2), _rows(base))


def test_cold_cache_selections_identical_to_uncached(catalog):
    """The cold-cache byte-identity claim, end to end on q19-q23: an
    empty cache changes no quote, no kind, no method selection."""
    for qname, plan in filtered_queries().items():
        bare = Executor(catalog, FilteredStrategy()).execute(plan)
        cold = Executor(catalog, FilteredStrategy(cache=FilterCache())
                        ).execute(plan)
        assert [f.plan for f in cold.filters] == [f.plan for f in
                                                  bare.filters], qname
        assert cold.methods() == bare.methods(), qname


# ---------------------------------------------------------------------------
# Regression: filter pushdown through aggregates (sigma estimation)
# ---------------------------------------------------------------------------


def test_aggregate_group_key_filter_plans_without_key_metadata(catalog):
    """A filter below an Aggregate on its group key must still yield a
    runtime filter when the catalog has no header FK metadata for the key
    (derived/external sources): sigma comes from the key-aware retain
    fraction, which sees through the grouping. Before the fix this fell
    back to sigma = 1.0 and nothing was planned."""
    nometa = Catalog(catalog.tables, catalog.p,
                     {k: v for k, v in catalog.key_domains.items()
                      if k != "cs_item_sk"})
    leaf = Aggregate(Filter(Scan("catalog_sales"), "cs_item_sk", "lt", 200,
                            selectivity=0.1), "cs_item_sk",
                     (("cs_sales_price", "sum"),))
    plan = Aggregate(Join(Scan("store_sales"), leaf, "ss_item_sk",
                          "cs_item_sk"),
                     "ss_store_sk", (("ss_sales_price", "sum"),))
    base = Executor(nometa, RelJoinStrategy()).execute(plan)
    filt = Executor(nometa, FilteredStrategy()).execute(plan)
    assert filt.filters, "group-key filter below aggregate was not planned"
    assert filt.filters[0].plan.sigma_est == pytest.approx(0.1)
    assert rows_close(_rows(filt), _rows(base))

"""Concurrent query service: plan cache, cross-query CSE, shared
FilterCache, and admission control.

The batched fixture runs the whole service suite (q19-q23 + the
deliberately-overlapping q33/q34) through one ``QueryService`` with
``verify=True`` — plan-analysis gates armed on every executed plan,
producers included — and keeps the solo reference runs beside it. Tests
then pin the correctness contract (rows identical to solo), the sharing
claims (each deduped subtree executes exactly once; suite bytes strictly
below serial), and the admission/caching discipline.
"""

import pytest

from repro import obs
from repro.joins.ref import rows_as_set, rows_close
from repro.sql import (AdmissionController, Aggregate, Join, PlanCache,
                       Project, QueryService, Scan, Submission, every_query,
                       generate, optimize, parse_sql, service_queries,
                       shared_subtree_candidates, signature)
from repro.sql.logical import leaf_columns
from repro.sql.planner import catalog_schema
from repro.sql.queries import SQL_TEXTS


def _rows(res):
    return rows_as_set(res.table.to_numpy())


def _sub(qid, cost):
    """Minimal Submission for admission-only tests (no compiled plan)."""
    return Submission(qid=qid, name=f"q{qid}", plan=None, optimized=None,
                      quoted_cost=cost, plan_cached=False)


@pytest.fixture(scope="module")
def service_batch(catalog):
    """(service, submissions, batch report, solo references) for the full
    suite — module-scoped because execution dominates wall time."""
    service = QueryService(catalog, verify=True)
    queries = service_queries()
    subs = {q: service.submit(plan, name=q) for q, plan in queries.items()}
    reports = service.run()
    assert len(reports) == 1
    solos = {q: service.execute_solo(plan) for q, plan in queries.items()}
    return service, subs, reports[0], solos


# ---------------------------------------------------------------------------
# Correctness: batched == solo
# ---------------------------------------------------------------------------


def test_batched_rows_identical_to_solo(service_batch):
    _, _, report, solos = service_batch
    for qname, solo in solos.items():
        assert rows_close(_rows(report.results[qname]), _rows(solo)), qname


def test_shared_subtrees_executed_exactly_once(service_batch):
    """q33 duplicates q19's join and q34 duplicates q22's: each shared
    subtree gets exactly one producer execution, and its consumers run
    zero joins of their own for it (the injected table replaces them)."""
    _, _, report, solos = service_batch
    by_consumers = {frozenset(s.consumers): s for s in report.shared}
    pair19 = frozenset(("q19_filtered_customer", "q33_shared_customer_join"))
    pair22 = frozenset(("q22_zone_map_window", "q34_shared_window_join"))
    assert pair19 in by_consumers and pair22 in by_consumers
    for s in report.shared:
        assert s.occurrences >= 2
    # One producer execution per shared signature.
    sigs = [s.sig for s in report.shared]
    assert len(sigs) == len(set(sigs))
    # Consumers of a fully-shared join subtree execute no joins at all:
    # their whole pre-aggregate subtree arrives by injection.
    for qname in pair19 | pair22:
        assert len(report.results[qname].decisions) == 0, qname
        assert report.results[qname].network_bytes == 0.0, qname
    # Globally: batched joins strictly fewer than serial.
    batch_joins = (sum(len(s.result.decisions) for s in report.shared)
                   + sum(len(r.decisions) for r in report.results.values()))
    serial_joins = sum(len(r.decisions) for r in solos.values())
    assert batch_joins < serial_joins


def test_suite_bytes_strictly_below_serial(service_batch):
    _, _, report, solos = service_batch
    serial = sum(r.network_bytes for r in solos.values())
    assert report.total_network_bytes < serial


def test_stats_publish(service_batch):
    service, subs, _, _ = service_batch
    stats = service.stats()
    assert stats["queries_submitted"] >= len(subs)
    assert stats["plan_cache_misses"] >= len(subs)
    assert stats["plan_cache_size"] == len(service.plan_cache)


# ---------------------------------------------------------------------------
# Projection pruning of the executed plans, shared subtrees as barriers
# ---------------------------------------------------------------------------

#: One star under two GROUP BYs on different dimensions: the join region
#: below the Aggregate is the shared subtree.
STAR_BY = """
    SELECT {key}, SUM(ss_net_profit)
    FROM store_sales
    JOIN customer ON ss_customer_sk = c_customer_sk
    JOIN (SELECT * FROM item WHERE i_category < 3) ON ss_item_sk = i_item_sk
    GROUP BY {key}
"""

#: A shared aggregated subquery under two joins that differ, so the
#: fact scans of its consumers lie outside every shared subtree.
CROSS_CHANNEL = """
    SELECT {key}, SUM({col})
    FROM store_sales
    JOIN (SELECT cs_item_sk, SUM(cs_sales_price), COUNT(cs_quantity)
          FROM catalog_sales GROUP BY cs_item_sk)
      ON ss_item_sk = cs_item_sk
    {more}
    GROUP BY {key}
"""


def _pruned_round(catalog, queries, cse=True):
    """Run ``queries`` ({name: sql}) as one batch: (report, the round's
    ``pruned_columns``)."""
    service = QueryService(catalog, cse=cse, verify=True)
    for name, sql in queries.items():
        service.submit(sql, name=name)
    before = obs.snapshot()
    (report,) = service.run()
    after = obs.snapshot()
    return report, after["pruned_columns"] - before.get("pruned_columns", 0)


def _scans_read(plan, barriers=()):
    """{table: columns yielded} over the scans of ``plan`` outside the
    subtrees whose signatures are in ``barriers``."""
    if signature(plan) in barriers:
        return {}
    if isinstance(plan, Project) and isinstance(plan.child, Scan):
        return {plan.child.table: set(plan.columns)}
    if isinstance(plan, Scan):
        return {plan.table: {"*"}}
    out = {}
    for child in plan.children():
        out.update(_scans_read(child, barriers))
    return out


def test_shared_producer_carries_the_union_of_its_consumers_columns(
        catalog):
    queries = {"by_region": STAR_BY.format(key="c_region"),
               "by_brand": STAR_BY.format(key="i_brand")}
    report, pruned = _pruned_round(catalog, queries)
    (shared,) = report.shared
    assert shared.consumers == ("by_region", "by_brand")
    assert set(leaf_columns(shared.node, catalog_schema(catalog))) == {
        "ss_customer_sk", "ss_item_sk", "ss_net_profit", "c_customer_sk",
        "c_region", "i_item_sk", "i_category", "i_brand"}
    # The consumers hold the shared subtree whole and run no join of it.
    for name in queries:
        assert signature(report.plans[name].child) == shared.sig
        assert report.results[name].decisions == []
    schema = catalog_schema(catalog)
    assert pruned == (len(schema["store_sales"]) - 3
                      + len(schema["customer"]) - 2
                      + len(schema["item"]) - 3)
    solo = QueryService(catalog, verify=True)
    for name, sql in queries.items():
        assert rows_close(_rows(report.results[name]),
                          _rows(solo.execute_solo(sql))), name


def test_consumer_scans_outside_shared_subtrees_read_only_their_columns(
        catalog):
    queries = {
        "by_store": CROSS_CHANNEL.format(key="ss_store_sk",
                                         col="ss_sales_price", more=""),
        "by_state": CROSS_CHANNEL.format(
            key="s_state", col="ss_net_profit",
            more="JOIN store ON ss_store_sk = s_store_sk")}
    report, pruned = _pruned_round(catalog, queries)
    (shared,) = report.shared
    assert isinstance(shared.node, Aggregate)
    sigs = {shared.sig}
    assert _scans_read(report.plans["by_store"], sigs) == {
        "store_sales": {"ss_item_sk", "ss_store_sk", "ss_sales_price"}}
    assert _scans_read(report.plans["by_state"], sigs) == {
        "store_sales": {"ss_item_sk", "ss_store_sk", "ss_net_profit"},
        "store": {"s_store_sk", "s_state"}}
    assert _scans_read(shared.node) == {
        "catalog_sales": {"cs_item_sk", "cs_sales_price", "cs_quantity"}}
    schema = catalog_schema(catalog)
    assert pruned == (2 * (len(schema["store_sales"]) - 3)
                      + len(schema["store"]) - 2
                      + len(schema["catalog_sales"]) - 3)
    solo = QueryService(catalog, verify=True)
    for name, sql in queries.items():
        assert rows_close(_rows(report.results[name]),
                          _rows(solo.execute_solo(sql))), name


def test_a_root_that_needs_every_column_prunes_nothing(catalog):
    service = QueryService(catalog, verify=True)
    sub = service.submit(every_query()["q1_star3"].child, name="q")
    before = obs.snapshot()
    (report,) = service.run()
    after = obs.snapshot()
    assert after["pruned_columns"] == before.get("pruned_columns", 0)
    assert report.plans["q"] == sub.optimized.plan


def test_without_cse_every_plan_is_pruned_fully(catalog):
    queries = {"by_region": STAR_BY.format(key="c_region"),
               "by_brand": STAR_BY.format(key="i_brand")}
    report, pruned = _pruned_round(catalog, queries, cse=False)
    assert report.shared == []
    fact = {"ss_customer_sk", "ss_item_sk", "ss_net_profit"}
    assert _scans_read(report.plans["by_region"]) == {
        "store_sales": fact, "customer": {"c_customer_sk", "c_region"},
        "item": {"i_item_sk", "i_category"}}
    assert _scans_read(report.plans["by_brand"]) == {
        "store_sales": fact, "customer": {"c_customer_sk"},
        "item": {"i_item_sk", "i_category", "i_brand"}}
    schema = catalog_schema(catalog)
    assert pruned == (2 * (len(schema["store_sales"]) - 3)
                      + len(schema["customer"]) - 2
                      + len(schema["customer"]) - 1
                      + len(schema["item"]) - 2
                      + len(schema["item"]) - 3)


# ---------------------------------------------------------------------------
# Subtree-candidate enumeration (region atomicity)
# ---------------------------------------------------------------------------


def test_candidates_are_exchange_rooted_and_region_atomic():
    """Only Join/Aggregate roots are candidates, and an inner join nested
    directly under another hint-free inner join is NOT one: solo execution
    dissolves it into the parent's region (reordered/filtered across its
    boundary), so deduping it would not be execution-equivalent."""
    inner = Join(Scan("store_sales"), Scan("customer"),
                 "ss_customer_sk", "c_customer_sk")
    outer = Join(inner, Scan("store"), "ss_store_sk", "s_store_sk")
    plan = Aggregate(outer, "c_region", (("ss_net_profit", "sum"),))
    nodes = [n for _, n in shared_subtree_candidates(plan)]
    assert plan in nodes          # Aggregate root
    assert outer in nodes         # region root (parent is the Aggregate)
    assert inner not in nodes     # dissolves into the parent region
    # An aggregated subquery under a join IS atomic (exchange boundary).
    agg_leaf = Aggregate(Scan("catalog_sales"), "cs_item_sk",
                         (("cs_sales_price", "sum"),))
    j = Join(Scan("store_sales"), agg_leaf, "ss_item_sk", "cs_item_sk")
    assert agg_leaf in [n for _, n in shared_subtree_candidates(j)]


def test_aggregate_specs_distinguish_signatures():
    """q33 is q19's join under a different aggregate column: the plan
    signatures must differ (the plan cache / CSE would otherwise alias
    them and return wrong aggregates), while the join subtrees match."""
    q19 = parse_sql(SQL_TEXTS["q19_filtered_customer"])
    q33 = parse_sql(SQL_TEXTS["q33_shared_customer_join"])
    assert signature(q19) != signature(q33)
    assert signature(q19.child) == signature(q33.child)


# ---------------------------------------------------------------------------
# Plan cache
# ---------------------------------------------------------------------------


def test_plan_cache_warm_hit_skips_optimize(catalog):
    service = QueryService(catalog)
    plan = service_queries()["q19_filtered_customer"]
    cold = service.submit(plan, name="cold")
    warm = service.submit(plan, name="warm")
    assert not cold.plan_cached and warm.plan_cached
    assert warm.optimized is cold.optimized   # the stored object, verbatim
    assert service.plan_cache.hits == 1


def test_plan_cache_binds_to_catalog_fingerprint(catalog):
    """Two catalogs sharing a version number must not share plans: the
    fingerprint (version + uid) is the binding, mirroring FilterCache."""
    plan = service_queries()["q19_filtered_customer"]
    cache = PlanCache()
    optimize(plan, catalog, prune=False, plan_cache=cache)
    assert len(cache) == 1 and cache.misses == 1
    other = generate(scale=0.1, p=4, seed=43)
    other.version = catalog.version   # forced version collision
    optimize(plan, other, prune=False, plan_cache=cache)
    assert cache.invalidations == 1
    assert cache.hits == 0            # the collision was NOT a hit
    assert len(cache) == 1            # re-populated against `other`


def test_plan_cache_key_separates_optimizer_knobs(catalog):
    """The same logical plan under different rewrite knobs compiles to
    different plans — the key must keep them apart."""
    plan = service_queries()["q19_filtered_customer"]
    cache = PlanCache()
    optimize(plan, catalog, prune=False, plan_cache=cache)
    optimize(plan, catalog, prune=True, plan_cache=cache)
    assert cache.hits == 0 and cache.misses == 2 and len(cache) == 2


# ---------------------------------------------------------------------------
# Shared FilterCache across the batch (interleaved multi-query execution)
# ---------------------------------------------------------------------------


def test_interleaved_queries_share_one_filter_cache(catalog):
    """Two queries with overlapping predicate chains through the service
    (CSE off, so both actually execute their joins): rows identical to
    solo, and the second query's eligible filters all report cached=True
    with zero rebuild bytes — PR 5's warm-run result, now intra-batch."""
    service = QueryService(catalog, cse=False)
    q19 = service_queries()["q19_filtered_customer"]
    q33 = service_queries()["q33_shared_customer_join"]
    service.submit(q19, name="first")
    service.submit(q33, name="second")
    report = service.run()[0]
    first, second = report.results["first"], report.results["second"]
    # Both executed fully (no CSE) and built/used filters.
    assert first.filters and second.filters
    assert first.cached_filters == 0
    assert second.cached_filters == len(second.filters)
    assert second.filter_reduce_bytes == 0.0
    assert rows_close(_rows(first), _rows(service.execute_solo(q19)))
    assert rows_close(_rows(second), _rows(service.execute_solo(q33)))


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------


def test_admission_fifo_preserves_order():
    ac = AdmissionController()
    for i, cost in enumerate([5.0, 1.0, 3.0]):
        ac.submit(_sub(i, cost))
    assert [s.qid for s in ac.next_batch()] == [0, 1, 2]
    assert len(ac) == 0


def test_admission_cost_policy_sorts_cheapest_first():
    ac = AdmissionController(policy="cost")
    for i, cost in enumerate([5.0, 1.0, 3.0, 1.0]):
        ac.submit(_sub(i, cost))
    # Stable: the two cost-1.0 queries keep submission order.
    assert [s.qid for s in ac.next_batch()] == [1, 3, 2, 0]


def test_admission_budget_splits_batches():
    ac = AdmissionController(budget=4.0)
    for i, cost in enumerate([2.0, 2.0, 2.0, 10.0, 1.0]):
        ac.submit(_sub(i, cost))
    assert [s.qid for s in ac.next_batch()] == [0, 1]   # 2+2 <= 4
    assert [s.qid for s in ac.next_batch()] == [2]      # next 2 would + 10
    # An over-budget query is admitted alone — no live-lock.
    assert [s.qid for s in ac.next_batch()] == [3]
    assert [s.qid for s in ac.next_batch()] == [4]
    assert ac.next_batch() == []


def test_admission_rejects_unknown_policy():
    with pytest.raises(ValueError):
        AdmissionController(policy="priority")


def test_service_budget_run_produces_multiple_batches(catalog):
    """End to end: a budget below the suite's total quote forces multiple
    batches, every query still executes, rows still match solo."""
    probe = QueryService(catalog)
    queries = dict(list(service_queries().items())[:3])
    quotes = [probe.submit(p, name=q).quoted_cost
              for q, p in queries.items()]
    budget = max(quotes)  # big enough for any single query, not for all
    service = QueryService(catalog, cost_budget=budget)
    for q, p in queries.items():
        service.submit(p, name=q)
    reports = service.run()
    assert len(reports) >= 2
    executed = {q for r in reports for q in r.results}
    assert executed == set(queries)
    for r in reports:
        for qname, res in r.results.items():
            assert rows_close(_rows(res),
                              _rows(service.execute_solo(queries[qname])))


def test_submission_quotes_are_positive(service_batch):
    _, subs, _, _ = service_batch
    for sub in subs.values():
        assert sub.quoted_cost > 0

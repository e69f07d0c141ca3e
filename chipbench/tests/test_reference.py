"""The data and the plain reference: the tables have TPC-DS's columns and
calendar, the engine's catalog holds exactly the benchmark's host columns,
and the reference's answers equal the engine's on every template of the
mix, at a small size on the CPU."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench.bench import cell, compare, data, reference
from chipbench.bench.traffic import Traffic

BENCH = Path(__file__).resolve().parents[1]
SEEDS = (5, 2**31 + 17)


def _config(fact_rows=40_000):
    cfg = json.loads((BENCH / "configs" / "tpcds_sf1_p8.json").read_text())
    cfg["rows"]["store_sales"] = fact_rows
    return cfg


def test_tables_have_the_specified_columns_and_calendar():
    cfg = _config(5_000)
    tables = data.make_tables(cfg, 3)
    assert {t: len(c) for t, c in tables.items()} == cfg["columns"]
    for name, cols in tables.items():
        assert {len(v) for v in cols.values()} == {cfg["rows"][name]}, name
        assert all(v.dtype in (np.int32, np.float32) for v in cols.values())
    d = tables["date_dim"]
    i = int(np.flatnonzero(d["d_date_sk"] == data.julian(
        np.datetime64("2000-11-30")))[0])
    assert (d["d_year"][i], d["d_moy"][i], d["d_dom"][i]) == (2000, 11, 30)
    assert d["d_dow"][i] == 4                     # a Thursday
    assert d["d_date_sk"][0] == 2415022
    ss = tables["store_sales"]
    assert ss["ss_sold_date_sk"].min() >= data.julian(data.SALES_FIRST)
    assert ss["ss_sold_date_sk"].max() <= data.julian(data.SALES_LAST)
    assert ss["ss_item_sk"].min() >= 1
    assert ss["ss_item_sk"].max() <= cfg["rows"]["item"]
    paid = ss["ss_ext_sales_price"].astype(np.float64) - ss["ss_coupon_amt"]
    np.testing.assert_allclose(ss["ss_net_paid"], paid, atol=0.011)


def test_catalog_holds_the_host_columns():
    cfg = _config(5_000)
    tables = data.make_tables(cfg, 9)
    catalog = cell.build_catalog(tables, 8, data.key_domains(cfg))
    for name, cols in tables.items():
        theirs = catalog.table(name).to_numpy()
        assert set(theirs) == set(cols)
        for col, arr in cols.items():
            np.testing.assert_array_equal(theirs[col], arr, err_msg=col)
    assert set(catalog.column_stats) == {c for t in tables.values()
                                         for c in t}


def test_inner_join_looks_up_the_unique_side():
    left = {"a": np.array([1, 2, 2, 3, 9]), "x": np.arange(5)}
    right = {"b": np.array([2, 3, 1]), "y": np.array([10, 20, 40])}
    for out in (reference.inner_join(left, right, "a", "b"),
                reference.inner_join(right, left, "b", "a")):
        got = sorted(zip(out["x"].tolist(), out["y"].tolist()))
        assert got == [(0, 40), (1, 10), (2, 10), (3, 20)]
    with pytest.raises(reference.SqlError):
        reference.inner_join(left, {"b": np.array([2, 2]), "y": np.ones(2)},
                             "a", "b")


def test_dialect_and_errors():
    tables = {"t": {"k": np.array([0, 1, 1, 2]), "j": np.array([7, 8, 8, 9]),
                    "v": np.array([1.0, 2.0, 3.0, 4.0], np.float32)},
              "u": {"j2": np.array([8, 9]), "w": np.array([5, 6])}}
    out = reference.answer("SELECT k, SUM(v), COUNT(v) FROM t, u "
                           "WHERE j = j2 AND k BETWEEN 1 AND 2 AND v >= 2 "
                           "AND w <> 0 GROUP BY k", tables)
    assert out["k"].tolist() == [1, 2]
    assert out["sum_v"].tolist() == [5.0, 4.0]
    assert out["count_v"].tolist() == [2, 1]
    with pytest.raises(reference.SqlError):
        reference.parse("SELECT k FROM t ORDER BY k")
    with pytest.raises(reference.SqlError):
        reference.answer("SELECT k, COUNT(v) FROM t, u GROUP BY k", tables)


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_matches_the_engine_on_every_template(seed):
    from repro.sql import QueryService

    cfg = _config()
    spec = json.loads((BENCH / "traffic" / "star_x4.json").read_text())
    tables = data.make_tables(cfg, seed)
    traffic = Traffic(spec, BENCH / "queries", tables)
    catalog = cell.build_catalog(tables, int(cfg["p"]),
                                 data.key_domains(cfg))
    service = QueryService(catalog)
    streams = traffic.streams(seed)
    queries = [next(s) for s in streams[:2] for _ in range(traffic.deck)]
    assert {q.template for q in queries} == set(traffic.templates)
    names = [f"q{i}" for i in range(len(queries))]
    _, results, _ = cell._execute(service, queries, names)
    for name, q in zip(names, queries):
        got = results[name].table.to_numpy()
        numbers = compare.compare_answer(got, reference.answer(q.sql,
                                                               tables))
        assert numbers["group_mismatch"] == 0, q.sql
        assert numbers["count_mismatch"] == 0, q.sql
        assert numbers["sum_rel_err"] < 1e-5, (q.sql, numbers)

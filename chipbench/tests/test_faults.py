"""A run with the timed path broken underneath reads `correct` false.

Each test skips the harness's look for a chip and drives the rest of a run
(``bench.cell.run``) at a small scale on the CPU, with one fault planted in
the engine: an answer altered where it is produced (the group-by's sums),
the exchange between partitions left out, and half of every table's
partitions left out of the scans. A sound run beside them reads true.
"""

import time
from pathlib import Path

import pytest

from chipbench.bench import cell
from chipbench.bench.spec import load_cell

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 101


def _run(cell_name="sf1_p8.star_x4"):
    c = load_cell(ROOT, cell_name)
    c.config["rows"]["store_sales"] = 40_000
    res = cell.run(c, SEED, 0.5, time.perf_counter(), log=lambda m: None)
    assert res["attempted"] > 0
    return res


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["numbers"]
    assert res["failed"] == 0


def test_altered_sums_are_caught(monkeypatch):
    from repro.joins import aggregate

    real = aggregate._agg_column

    def altered(v, order, seg, live, op):
        out = real(v, order, seg, live, op=op)
        return out * 1.01 if op == "sum" else out

    monkeypatch.setattr(aggregate, "_agg_column", altered)
    res = _run()
    assert not res["correct"]
    assert res["numbers"]["sum_rel_err"] > res["limits"]["sum_rel_err"]


def test_missing_exchange_is_caught(monkeypatch):
    from repro.joins import aggregate, exchange, methods

    def no_exchange(table, key, capacity_factor=2.0):
        return table, exchange.ExchangeReport("shuffle", 0.0, 0.0)

    for module in (aggregate, methods):
        monkeypatch.setattr(module, "shuffle", no_exchange)
    res = _run()
    assert not res["correct"]


def test_half_the_partitions_left_out_is_caught(monkeypatch):
    from repro.sql import datagen

    def half(self, name):
        t = self.tables[name]
        keep = t.valid.at[t.num_partitions // 2:].set(False)
        return t.with_valid(keep)

    monkeypatch.setattr(datagen.Catalog, "table", half)
    res = _run()
    assert not res["correct"]
    assert res["numbers"]["count_mismatch"] > 0

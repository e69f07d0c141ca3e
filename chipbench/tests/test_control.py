"""The control, the reference computed in bfloat16, fails the comparison
that the engine's float32 answers pass, on three seeds at a small size."""

import json
from pathlib import Path

import pytest

from chipbench.bench import compare, data, reference
from chipbench.bench.traffic import Traffic

BENCH = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("seed", (11, 2**31 + 5, 4_000_000_007))
def test_bfloat16_reference_fails_the_limit(seed):
    cfg = json.loads((BENCH / "configs" / "tpcds_sf1_p8.json").read_text())
    cfg["rows"]["store_sales"] = 200_000
    spec = json.loads((BENCH / "traffic" / "star_x4.json").read_text())
    tables = data.make_tables(cfg, seed)
    traffic = Traffic(spec, BENCH / "queries", tables)
    limits = compare.load_limits(BENCH / "limits.json")
    readings = []
    for stream in traffic.streams(seed):
        for _ in range(traffic.deck):
            sql = next(stream).sql
            want = reference.answer(sql, tables)
            low = reference.answer(sql, tables, compare.accumulate_bf16)
            readings.append(compare.compare_answer(low, want))
    numbers = compare.worst(readings)
    assert not compare.within(numbers, limits), numbers
    assert numbers["sum_rel_err"] > limits["sum_rel_err"]

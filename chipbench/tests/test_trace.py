"""The reduction from a trace to device time, idle share and idle gaps, on
a constructed trace whose answers are known."""

import pytest

from chipbench.bench.cell import Context
from chipbench.bench.trace import Op, Trace

MS = 1e6  # nanoseconds


def _trace():
    ops = [Op("fusion", "jit__route", 10 * MS, 20 * MS),
           Op("copy", "jit__route", 15 * MS, 25 * MS),       # overlaps
           Op("fusion.1", "jit_hash_join", 40 * MS, 60 * MS),
           Op("fusion.2", "jit__agg_column", 90 * MS, 95 * MS),
           Op("late", "jit_hash_join", 95 * MS, 130 * MS)]   # past window
    spans = [("window", 0, 100 * MS), ("submit", 0, 8 * MS),
             ("run", 8 * MS, 88 * MS), ("fetch", 88 * MS, 100 * MS)]
    return Trace(ops, spans, (0, 100 * MS))


def test_busy_is_the_union_clipped_to_the_window():
    t = _trace()
    assert t.busy() == [(10 * MS, 25 * MS), (40 * MS, 60 * MS),
                        (90 * MS, 100 * MS)]
    assert t.busy_s == pytest.approx(0.045)
    assert t.window_s == pytest.approx(0.1)
    assert t.idle_share() == pytest.approx(0.55)


def test_device_seconds_by_module_pattern():
    t = _trace()
    assert t.device_seconds(r"_route") == pytest.approx(0.020)
    assert t.device_seconds(r"hash_join") == pytest.approx(0.025)
    assert t.device_seconds(r"nothing") == 0.0
    assert t.top_modules(2) == [("jit_hash_join", pytest.approx(0.025)),
                                ("jit__route", pytest.approx(0.020))]


def test_idle_gaps_are_labelled_by_the_covering_span():
    gaps = _trace().idle_gaps(10)
    assert gaps == [("run", pytest.approx(0.030)),
                    ("run", pytest.approx(0.015)),
                    ("submit", pytest.approx(0.010))]


def test_device_ms_per_query_and_nothing_to_read():
    ctx = Context(records=[object()] * 5, window_s=0.1, network_bytes=0.0,
                  compiles=0, trace=_trace())
    assert ctx.device_ms_per_query(r"_agg_column") == pytest.approx(1.0)
    assert ctx.device_ms_per_query(r"bloom") is None
    ctx.trace = None
    assert ctx.device_ms_per_query(r"_route") is None


def test_module_name_drops_the_fingerprint():
    from chipbench.bench.trace import module_name

    assert module_name("jit_hash_join(10851775155875707406)") == \
        "jit_hash_join"
    assert module_name("jit__all_to_all(8)") == "jit__all_to_all"

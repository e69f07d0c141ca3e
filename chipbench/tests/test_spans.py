"""Device time by launching span, on hand-built traces whose answers are
known: a program goes to the innermost ``op.*`` span open at its launch,
linked by ``run_id`` or, without one, by launch order per module; a launch
outside every ``op.*`` span is unattributed; idle gaps take the innermost
span's name; and the per-query numbers follow."""

import pytest

from chipbench.bench.spans import (Launch, Program, Span, SpanTrace,
                                   load, metrics, module_key)

MS = 1e6  # nanoseconds
LINE = "/host:CPU/python"


def _spans():
    return [Span("window", 0, 100 * MS, LINE),
            Span("submit", 0, 4 * MS, LINE),
            Span("service.submit", 1 * MS, 3 * MS, LINE, (("query", "a"),)),
            Span("run", 4 * MS, 90 * MS, LINE),
            Span("service.batch", 5 * MS, 90 * MS, LINE),
            Span("service.query", 10 * MS, 90 * MS, LINE, (("query", "a"),)),
            Span("op.local_join", 10 * MS, 40 * MS, LINE),
            Span("op.exchange", 12 * MS, 20 * MS, LINE),
            Span("op.filter", 50 * MS, 60 * MS, LINE),
            Span("sync", 55 * MS, 60 * MS, LINE),
            Span("fetch", 90 * MS, 100 * MS, LINE)]


def _trace(run_ids: bool) -> SpanTrace:
    rid = (lambda i: i) if run_ids else (lambda i: None)
    launches = [Launch("_take", 11 * MS, 11.5 * MS, LINE, rid(1)),   # join
                Launch("_route", 13 * MS, 13.5 * MS, LINE, rid(2)),  # exch.
                Launch("_take", 52 * MS, 52.5 * MS, LINE, rid(3)),   # filter
                Launch("_reduce_sum", 70 * MS, 70.1 * MS, LINE, rid(4))]
    # Programs run later than their launches; the two _take programs run
    # in launch order, each for its own time.
    programs = [Program("jit__take", 20 * MS, 30 * MS, rid(1)),
                Program("jit__route", 30 * MS, 32 * MS, rid(2)),
                Program("jit__take", 61 * MS, 65 * MS, rid(3)),
                Program("jit__reduce_sum", 71 * MS, 72 * MS, rid(4))]
    return SpanTrace(programs, launches, _spans(), (0, 100 * MS))


@pytest.mark.parametrize("run_ids", [True, False])
def test_programs_go_to_the_innermost_op_span_at_launch(run_ids):
    t = _trace(run_ids)
    assert t.link()[0] == ("run_id" if run_ids else "order")
    assert t.link()[1] == [0, 1, 2, 3]
    by_op = t.device_seconds_by_op()
    assert by_op == {"op.local_join": pytest.approx(0.010),
                     "op.exchange": pytest.approx(0.002),
                     "op.filter": pytest.approx(0.004),
                     "unattributed": pytest.approx(0.001)}
    assert sum(by_op.values()) == pytest.approx(t.device_s)
    assert t.counts_by_module() == {}


def test_order_linking_follows_launch_order_within_a_module():
    t = _trace(run_ids=False)
    # Listed out of order, the programs still pair with their launches.
    t.programs.reverse()
    assert t.link()[1] == [3, 2, 1, 0]


def test_a_program_without_its_launch_is_unlinked_and_counted():
    t = _trace(run_ids=False)
    t.programs.append(Program("jit__agg_column", 80 * MS, 81 * MS))
    assert t.device_seconds_by_op()["unlinked"] == pytest.approx(0.001)
    assert t.counts_by_module() == {module_key("jit__agg_column"): (0, 1)}


def test_module_key_matches_a_launch_to_its_module():
    assert module_key("jit__take(1234)") == module_key(
        Launch("_take", 0, 1, LINE).module)
    assert module_key("jit__lambda") == module_key(
        Launch("<lambda>", 0, 1, LINE).module)


def test_idle_gaps_take_the_innermost_span():
    # Busy: 20-32, 61-65, 71-72 ms. In the gap 32-61 the innermost span
    # is op.local_join for 8 ms, service.query for 11, op.filter and sync
    # for 5 each; in 72-100, service.query for 18 and fetch for 10; in
    # 0-20, op.exchange for 8 and service.batch for 5.
    assert _trace(run_ids=True).idle_gaps(10) == [
        ("service.query", pytest.approx(0.029)),
        ("service.query", pytest.approx(0.028)),
        ("op.exchange", pytest.approx(0.020)),
        ("service.query", pytest.approx(0.006))]


def test_metrics_per_query():
    t = _trace(run_ids=True)
    m = metrics(t, {"host_syncs": 6, "exchange_bytes": 3e6}, n_queries=2)
    assert m["join_span_device_ms"] == pytest.approx(5.0)
    assert m["exchange_span_device_ms"] == pytest.approx(1.0)
    assert m["filter_span_device_ms"] == pytest.approx(2.0)
    assert m["unattributed_device_ms"] == pytest.approx(0.5)
    assert m["aggregate_span_device_ms"] == 0.0
    assert m["submit_span_ms"] == pytest.approx(1.0)
    assert m["queue_wait_ms"] == pytest.approx(7.0)
    assert m["sync_wait_ms"] == pytest.approx(2.5)
    assert m["host_syncs_per_query"] == 3.0
    assert m["exchange_mb_per_query"] == pytest.approx(1.5)


def test_load_reads_engine_spans_and_launches(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro import obs

    x = jnp.arange(64)
    jnp.take(x, jnp.arange(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    from jax.profiler import TraceAnnotation
    with TraceAnnotation("window"):
        with obs.span("service.query", query="q7"):
            with obs.span("op.filter"):
                obs.fetch(jnp.take(x, jnp.arange(4)))
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    t = load(path)
    names = {s.name for s in t.spans}
    assert {"window", "service.query", "op.filter", "sync"} <= names
    (q,) = [s for s in t.spans if s.name == "service.query"]
    assert q.get("query") == "q7"
    takes = [la for la in t.launches if la.fn == "_take"]
    assert len(takes) == 1 and takes[0].run_id is not None
    assert t.op_labels()[t.launches.index(takes[0])] == "op.filter"

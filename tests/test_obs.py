"""The engine's spans and counters (``repro.obs``): spans land in a
profiler trace, nested and carrying their query; ``fetch`` reads what an
implicit read would; a service round moves the counters; ``exchange_bytes``
agrees with the engine's own ``network_bytes`` where both count the same
exchanges; and a trace changes no result.
"""

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import array as jax_array

from repro import obs
from repro.joins.ref import rows_as_set
from repro.kernels import ops as kops
from repro.sql import QueryService, RelJoinStrategy, every_query


def _delta(before, after, name):
    return after.get(name, 0) - before.get(name, 0)


def _events(tmp_path):
    """Host events of the one trace written under ``tmp_path``, as
    (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData

    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                           for ev in line.events)
    return out


@contextlib.contextmanager
def _traced(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _one_query(catalog, plan, strategy=None):
    """Submit ``plan`` to a fresh service, run it; (result, batch report,
    counter deltas of the round)."""
    service = QueryService(catalog, strategy=strategy)
    before = obs.snapshot()
    service.submit(plan, name="q")
    (report,) = service.run()
    after = obs.snapshot()
    counters = {k: _delta(before, after, k) for k in after}
    return report.results["q"], report, counters


def test_spans_nest_and_carry_the_query(tmp_path):
    x = jnp.arange(8)
    with _traced(tmp_path):
        with obs.span("service.query", query="c0.1.2"):
            with obs.span("op.filter"):
                obs.fetch(jnp.sum(x))
    events = {name: (s, e, st) for name, s, e, st in _events(tmp_path)
              if name in obs.SPANS}
    assert set(events) == {"service.query", "op.filter", "sync"}
    q, f, sync = (events[n] for n in ("service.query", "op.filter", "sync"))
    assert q[2].get("query") == "c0.1.2"
    assert q[0] <= f[0] <= sync[0] and sync[1] <= f[1] <= q[1]


def test_unknown_names_are_refused():
    with pytest.raises(ValueError):
        obs.span("op.nothing")
    with pytest.raises(ValueError):
        obs.count("nothing")


def test_fetch_reads_what_an_implicit_read_would_and_counts_a_sync():
    x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    before = obs.snapshot()
    assert int(obs.fetch(jnp.sum(x > 3))) == int(jnp.sum(x > 3))
    np.testing.assert_array_equal(obs.fetch(x), np.asarray(x))
    tree = obs.fetch({"a": x, "b": jnp.int32(7)})
    assert int(tree["b"]) == 7
    obs.wait(x)
    assert _delta(before, obs.snapshot(), "host_syncs") == 4


def test_kernel_calls_are_counted():
    before = obs.snapshot()
    kops.hist(jnp.array([0, 1, 1, -1], jnp.int32), nd=2)
    assert _delta(before, obs.snapshot(), "kernel.partition_hist") == 1


def test_a_service_round_moves_the_counters(catalog):
    _, _, counters = _one_query(catalog, every_query()["q1_star3"])
    assert counters["host_syncs"] > 0
    assert counters["exchange_bytes"] > 0


def test_exchange_bytes_are_the_joins_network_bytes(catalog):
    """Without runtime filters (whose payload bytes ``network_bytes``
    adds) a join-only query's exchanges are what ``network_bytes``
    counts; a GROUP BY adds its shuffle, which ``network_bytes`` leaves
    out."""
    grouped = every_query()["q1_star3"]
    strategy = RelJoinStrategy()
    res, report, counters = _one_query(catalog, grouped.child, strategy)
    assert res.filters == []
    assert counters["exchange_bytes"] == pytest.approx(
        report.total_network_bytes)
    res, report, counters = _one_query(catalog, grouped, strategy)
    assert counters["exchange_bytes"] > report.total_network_bytes


def test_results_are_the_same_under_a_trace(catalog, tmp_path):
    plan = every_query()["q1_star3"]
    plain, _, _ = _one_query(catalog, plan)
    with _traced(tmp_path):
        traced, _, _ = _one_query(catalog, plan)
    assert rows_as_set(traced.table.to_numpy()) == \
        rows_as_set(plain.table.to_numpy())
    names = {name for name, *_ in _events(tmp_path)}
    assert {"service.submit", "service.batch", "service.query",
            "op.filter", "op.exchange", "op.local_join", "op.aggregate",
            "op.compact", "sync"} <= names


@contextlib.contextmanager
def _only_explicit_reads():
    """Refuse every read of a device array's value except through
    ``jax.device_get``: the CPU's stand-in for running under
    ``jax.transfer_guard_device_to_host("disallow")``, which the CPU
    backend does not enforce."""
    state = threading.local()
    real_value, real_get = jax_array.ArrayImpl._value, jax.device_get

    def value(self):
        if not getattr(state, "explicit", False):
            raise AssertionError("a device value was read outside "
                                 "obs.fetch")
        return real_value.fget(self)

    def device_get(x):
        state.explicit = True
        try:
            return real_get(x)
        finally:
            state.explicit = False

    jax_array.ArrayImpl._value = property(value)
    jax.device_get = device_get
    try:
        yield
    finally:
        jax_array.ArrayImpl._value = real_value
        jax.device_get = real_get


def test_every_read_of_a_service_round_goes_through_fetch(catalog):
    queries = {n: every_query()[n] for n in ("q1_star3", "q7_filtered_fact",
                                             "q8_semi", "q10_promo_window")}

    def round_():
        service = QueryService(catalog)
        for name, plan in queries.items():
            service.submit(plan, name=name)
        return {n: r.table.to_numpy() for rep in service.run()
                for n, r in rep.results.items()}

    want = round_()  # compiles outside the guard: lowering reads constants
    with _only_explicit_reads():
        got = round_()
    assert {n: rows_as_set(c) for n, c in got.items()} == \
        {n: rows_as_set(c) for n, c in want.items()}

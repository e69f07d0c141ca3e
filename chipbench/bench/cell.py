"""One run of one cell: set-up, warm-up, the measured window, the check.

Set-up draws the tables from the seed on the host (``data.py``) and hands
those same arrays to the engine: its catalog is built with the engine's
own ``from_numpy``, ``partition_round_robin`` and ``compute_column_stats``.

The window is a closed loop of cycles (``traffic.py``). A cycle runs in a
fresh ``QueryService``, a new session with empty plan and filter caches:
in each of its rounds every stream submits its next query (parse and bind
against the catalog's schema, then ``QueryService.submit``: optimise,
quote), one ``QueryService.run()`` executes the queued batch, and each
result's rows are fetched to the host (``Table.to_numpy``). A query's
latency runs from the start of its submission to the moment its rows are
on the host. Cycles start until ``seconds`` have passed; the window ends
when the last cycle's rows are on the host, so every query it started
counts, and its length is measured, not assumed. Every cycle of a run
sends the same rounds, and the warm-up runs one of them in a session then
dropped, so the window meets only shapes the warm-up compiled.
"""

from __future__ import annotations

import dataclasses
import gc
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from . import compare, data, reference
from .spec import Cell
from .trace import Trace, find, load
from .traffic import Query, Traffic

#: ``jax.monitoring`` event of a program compiled, or loaded from the
#: persistent compilation cache (the event wraps both): inside the window
#: it means a shape the warm-up did not cover.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: At most this many distinct queries are checked against the reference,
#: drawn from the seed, so the check stays shorter than the window; every
#: answer the window returned for them is compared.
MAX_CHECKED = 96


@dataclasses.dataclass
class QueryRecord:
    query: Query
    submitted: float     # host clock at the start of ``submit``
    plan_s: float        # seconds in ``submit``
    done: float          # host clock when the rows were on the host

    @property
    def latency_s(self) -> float:
        return self.done - self.submitted


@dataclasses.dataclass
class Context:
    """What the per-layer metric readers read."""

    records: List[QueryRecord]
    window_s: float
    network_bytes: float      # every result and shared producer, once
    compiles: int
    trace: Optional[Trace]

    def device_ms_per_query(self, module_pattern: str) -> Optional[float]:
        """Device milliseconds per query of the programs whose XLA module
        name matches; None without a trace or where none ran."""
        if self.trace is None or not self.records:
            return None
        seconds = self.trace.device_seconds(module_pattern)
        if seconds <= 0:
            return None
        return seconds * 1e3 / len(self.records)


class CompileCounter:
    """Counts compilations while ``active``; listeners live per process."""

    def __init__(self) -> None:
        import jax

        self.active = False
        self.names: List[str] = []

        def on_compile(event: str, duration: float, **kwargs) -> None:
            if self.active and event == COMPILE_EVENT:
                self.names.append(str(kwargs.get("fun_name", "?")))

        jax.monitoring.register_event_duration_secs_listener(on_compile)

    @property
    def count(self) -> int:
        return len(self.names)


def _span(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def build_catalog(tables: Dict[str, Dict[str, np.ndarray]], p: int,
                  key_domains: Dict[str, float]):
    """The engine's catalog over the benchmark's host columns."""
    import jax

    from repro.joins.table import from_numpy, partition_round_robin
    from repro.sql.datagen import Catalog, compute_column_stats

    whole = {name: from_numpy(cols) for name, cols in tables.items()}
    catalog = Catalog({name: partition_round_robin(t, p)
                       for name, t in whole.items()}, p,
                      key_domains=dict(key_domains),
                      column_stats=compute_column_stats(whole))
    jax.block_until_ready(catalog.tables)
    return catalog


def _execute(service, queries: List[Query], names: List[str]
             ) -> Tuple[List[Tuple[Query, float, float, str]], Dict, float]:
    """Submit ``queries`` as one batch and run it."""
    from repro.sql.binder import parse_sql
    from repro.sql.planner import catalog_schema

    schema = catalog_schema(service.catalog)
    batch = []
    for q, name in zip(queries, names):
        t = time.perf_counter()
        with _span("submit"):
            service.submit(parse_sql(q.sql, schema,
                                     service.catalog.key_domains), name=name)
        batch.append((q, t, time.perf_counter() - t, name))
    with _span("run"):
        reports = service.run()
    results = {n: r for rep in reports for n, r in rep.results.items()}
    return batch, results, sum(rep.total_network_bytes for rep in reports)


def _cycle(service, rounds: List[List[Query]], prefix: str
           ) -> Tuple[List[QueryRecord], List[Tuple[Tuple[str, str], Dict]],
                      float]:
    """Run one cycle's rounds in ``service``; fetch every result."""
    records, answers, network = [], [], 0.0
    for r, picked in enumerate(rounds):
        names = [f"{prefix}.{r}.{i}" for i in range(len(picked))]
        batch, results, net = _execute(service, picked, names)
        network += net
        for q, t, plan_s, name in batch:
            with _span("fetch"):
                cols = results[name].table.to_numpy()
            records.append(QueryRecord(q, t, plan_s, time.perf_counter()))
            answers.append((q.key, cols))
    return records, answers, network


def run(cell: Cell, seed: int, seconds: float, t_process: float,
        trace: bool = False, control: bool = False,
        log: Callable[[str], None] = print) -> Dict:
    """Run ``cell`` once; return its numbers (see ``run.py``)."""
    import jax

    from repro.sql import QueryService

    counter = CompileCounter()
    cfg = cell.config
    t_gen = time.perf_counter()
    tables = data.make_tables(cfg, seed)
    catalog = build_catalog(tables, int(cfg["p"]), data.key_domains(cfg))
    t_data = time.perf_counter()
    traffic = Traffic(cell.traffic, cell.queries_dir, tables)
    rounds = traffic.cycle(seed)
    _cycle(QueryService(catalog), rounds, "warm")
    gc.collect()
    t_warm = time.perf_counter()
    log(f"set-up: {t_data - t_gen:.3f} s for data and catalog, "
        f"{t_warm - t_data:.3f} s of warm-up over one cycle of "
        f"{len(rounds)} rounds, {t_warm - t_process:.3f} s in all")

    records: List[QueryRecord] = []
    answers: List[Tuple[Tuple[str, str], Dict[str, np.ndarray]]] = []
    network = 0.0
    trace_dir = tempfile.TemporaryDirectory() if trace else None
    if trace_dir is not None:
        from jax.profiler import ProfileOptions
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
    counter.active = True
    with _span("window"):
        t_start = time.perf_counter()
        done = t_start
        while done - t_start < seconds:
            before = counter.count
            cyc_records, cyc_answers, net = _cycle(
                QueryService(catalog), rounds, f"c{len(records)}")
            if counter.count > before:
                log(f"window: compiled or loaded {counter.names[before:]}")
            records += cyc_records
            answers += cyc_answers
            network += net
            done = records[-1].done
    counter.active = False
    window_s = done - t_start
    parsed = None
    if trace_dir is not None:
        jax.profiler.stop_trace()
        parsed = load(find(Path(trace_dir.name)))
        trace_dir.cleanup()
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    del catalog
    gc.collect()

    t_ref = time.perf_counter()
    checked = _sample(sorted({key for key, _ in answers}), seed)
    readings, control_readings = [], []
    n_answers = 0
    for key in checked:
        want = reference.answer(key[1], tables)
        for got in (cols for k, cols in answers if k == key):
            readings.append(compare.compare_answer(got, want))
            n_answers += 1
        if control:
            low = reference.answer(key[1], tables, compare.accumulate_bf16)
            control_readings.append(compare.compare_answer(low, want))
    numbers = compare.worst(readings)
    limits = compare.load_limits(cell.bench_dir / "limits.json")
    wrong = sum(1 for r in readings if not compare.within(r, limits))
    log(f"check: {n_answers} answers of {len(checked)} distinct queries "
        f"against the reference in {time.perf_counter() - t_ref:.3f} s")

    latencies = np.array([r.latency_s for r in records]) * 1e3
    return {
        "setup_s": t_warm - t_process,
        "window_s": window_s,
        "qps": len(records) / window_s,
        "query_p50_ms": float(np.percentile(latencies, 50)),
        "query_p90_ms": float(np.percentile(latencies, 90)),
        "attempted": len(records),
        "failed": wrong,
        "numbers": numbers,
        "limits": limits,
        "correct": compare.within(numbers, limits),
        "control": compare.worst(control_readings) if control else None,
        "memory_peak_bytes": peak,
        "context": Context(records, window_s, network, counter.count, parsed),
    }


def _sample(keys: List[Tuple[str, str]], seed: int
            ) -> List[Tuple[str, str]]:
    """Every distinct query, or ``MAX_CHECKED`` of them drawn from the
    seed, spread evenly over the templates."""
    if len(keys) <= MAX_CHECKED:
        return keys
    rng = np.random.default_rng([seed, 99])
    by_template: Dict[str, List[Tuple[str, str]]] = {}
    for k in keys:
        by_template.setdefault(k[0], []).append(k)
    pools = [list(rng.permutation(len(v))) for v in by_template.values()]
    out: List[Tuple[str, str]] = []
    while len(out) < MAX_CHECKED:
        for (name, ks), pool in zip(by_template.items(), pools):
            if pool and len(out) < MAX_CHECKED:
                out.append(ks[pool.pop()])
    return out

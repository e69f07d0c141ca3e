"""Run one cell once, traced, and split its device time by engine span.

    python3 chipbench/profile_spans.py --workload <cell> --seed <n> \
        --seconds <s> [--guard 1] [--out <dir>] [--rows <store_sales rows>]

The run is ``run.py``'s ``--trace 1`` run (``bench/cell.py``), read a
second way: the trace is also handed to ``bench/spans.py``, which ties
each device program to the engine span (``repro.obs``) open at its launch,
and the engine's counters are read over the window. The last line of
standard output is one JSON object: the per-query numbers of
``spans.metrics``, the device seconds by launching span and by module,
how programs were linked to launches, the modules whose launches and
programs differ in count, the longest idle gaps labelled by span, and the
benchmark's own per-layer metrics for comparison. ``--guard 1`` then runs
one more cycle under ``jax.transfer_guard_device_to_host("disallow")``
and reports whether it finished. ``--out`` also writes there what was read
from the trace (``spans.json.gz``) and which events of the trace carry a
``run_id`` (``run_ids.json``). ``--rows`` shrinks the fact table, for a
trial on the CPU; without it the run needs a TPU, like ``run.py``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def keep(path: Path, st, out: Path) -> None:
    """What was read from the trace, and where its ``run_id`` stats
    are: per plane and line, the events that carry one."""
    from jax.profiler import ProfileData

    out.mkdir(parents=True, exist_ok=True)
    with gzip.open(out / "spans.json.gz", "wt") as f:
        json.dump(dataclasses.asdict(st), f)
    found: dict = collections.defaultdict(collections.Counter)
    keys: dict = collections.defaultdict(set)
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            where = f"{plane.name}/{line.name}"
            for ev in line.events:
                stats = dict(ev.stats)
                if len(keys[where]) < 64:
                    keys[where].update(stats)
                if "run_id" in stats:
                    found[where][ev.name.split("(")[0]] += 1
    (out / "run_ids.json").write_text(json.dumps(
        {"with_run_id": {k: v.most_common(8) for k, v in found.items()},
         "stat_keys": {k: sorted(v) for k, v in keys.items()}}, indent=1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--guard", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", type=Path, default=None)
    ap.add_argument("--rows", type=int, default=None)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import run as entry
    from chipbench.bench.spec import load_cell

    cell_spec = load_cell(ROOT, args.workload)
    if args.rows is not None:
        cell_spec.config["rows"]["store_sales"] = args.rows
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(entry.CACHE_DIR))
    import jax
    if args.rows is None and jax.devices()[0].platform != "tpu":
        print("profile_spans: no TPU; pass --rows for a CPU trial",
              file=sys.stderr)
        return 2
    from repro import obs
    from repro.compile_cache import enable_compile_cache
    from repro.sql import QueryService

    from chipbench.bench import cell, spans
    enable_compile_cache()

    seen = {}
    real_cycle, real_load = cell._cycle, cell.load

    def cycle(service, rounds, prefix):
        # The window's cycles are the ones not named "warm".
        if prefix != "warm" and "before" not in seen:
            seen.update(before=obs.snapshot(), catalog=service.catalog,
                        rounds=rounds)
        return real_cycle(service, rounds, prefix)

    def load(path):
        seen["after"] = obs.snapshot()
        t = time.perf_counter()
        seen["spans"] = spans.load(path)
        seen["load_s"] = time.perf_counter() - t
        if args.out is not None:
            keep(path, seen["spans"], args.out)
        return real_load(path)

    cell._cycle, cell.load = cycle, load
    res = cell.run(cell_spec, args.seed, args.seconds, T_PROCESS,
                   trace=True,
                   log=lambda m: print(m, file=sys.stderr, flush=True))
    ctx = res["context"]
    st: spans.SpanTrace = seen["spans"]
    counters = {k: v - seen["before"].get(k, 0)
                for k, v in seen["after"].items()}
    n = len(ctx.records)
    how, linked = st.link()
    by_op = st.device_seconds_by_op()
    line = {
        "correct": res["correct"], "attempted": n,
        "metrics": spans.metrics(st, counters, n),
        "benchmark_metrics": {
            m["name"]: cell_spec.metric_reader(m["name"]).read(ctx)
            for m in cell_spec.per_layer},
        "counters": counters,
        "link": how,
        "programs": len(st.programs), "launches": len(st.launches),
        "programs_unlinked": sum(1 for i in linked if i is None),
        "count_mismatch_by_module": st.counts_by_module(),
        "device_s": st.device_s,
        "device_s_by_op": by_op,
        "device_s_by_op_and_module": sorted(
            ([k[0], k[1], v] for k, v in
             st.device_seconds_by_op_and_module().items()),
            key=lambda x: -x[2])[:40],
        "idle_gaps": st.idle_gaps(10),
        "busy_s": ctx.trace.busy_s, "window_s": ctx.trace.window_s,
        "span_load_s": seen["load_s"],
    }
    if args.guard:
        service = QueryService(seen["catalog"])
        try:
            with jax.transfer_guard_device_to_host("disallow"):
                cell._cycle = real_cycle
                cell._cycle(service, seen["rounds"], "guard")
            line["guard"] = "passed"
        except Exception as e:  # noqa: BLE001 - reported, not raised
            line["guard"] = f"failed: {type(e).__name__}: {e}"[:2000]
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())

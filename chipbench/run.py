"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, this
directory and the engine's ``src/``. The cell's configuration, traffic and
metrics are found by name (``bench/spec.py``). The run needs a TPU with as
many chips as the cell asks for; without one it exits non-zero and prints
no result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` (``--trace 1``) and, last, ``checks``: each number the
correctness check compared, with its limit. The same numbers close
standard error. ``--control 1`` also reads the control (the reference in
bfloat16) over the same answers, for setting the limits; no measured run
passes it.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
#: JAX's persistent compilation cache, at a fixed path inside the checkout.
CACHE_DIR = ROOT / "chipbench" / ".cache" / "jax"


def _fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr)
    return 2


def _peaks(kind: str) -> dict:
    table = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json"
                       f" ({sorted(table['devices'])})")
    return table["devices"][kind]


def _metric(value: float, unit: str) -> dict:
    return {"value": value, "unit": unit}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        return _fail("--seed must be a whole number of at least 0")

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench.bench.spec import load_cell
    try:
        cell = load_cell(ROOT, args.workload)
    except (FileNotFoundError, KeyError) as e:
        return _fail(f"cannot load workload {args.workload}: {e}")

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    devices = jax.devices()
    print(f"set-up: {time.perf_counter() - T_PROCESS:.3f} s to JAX's devices",
          file=sys.stderr)
    if devices[0].platform != "tpu":
        return _fail(f"no TPU: JAX sees {devices[0].platform} devices; "
                     f"this benchmark measures only on the chip")
    if len(devices) < cell.chips:
        return _fail(f"{args.workload} needs {cell.chips} chips, JAX sees "
                     f"{len(devices)}")
    kind = devices[0].device_kind
    try:
        _peaks(kind)
        from repro.compile_cache import enable_compile_cache
    except (KeyError, ImportError) as e:
        return _fail(str(e))
    enable_compile_cache()

    from chipbench.bench import cell as runner
    res = runner.run(cell, args.seed, args.seconds, T_PROCESS,
                     trace=bool(args.trace), control=bool(args.control),
                     log=lambda m: print(m, file=sys.stderr, flush=True))
    print(json.dumps(result_line(cell, res, kind, len(devices),
                                 bool(args.trace))))
    return 0


def result_line(cell, res: dict, kind: str, n_devices: int,
                trace: bool) -> dict:
    """The result object of one run (see the module docstring)."""
    ctx = res["context"]
    device = {"platform": "tpu", "kind": kind, "count": n_devices,
              "memory_peak_bytes": res["memory_peak_bytes"]}
    metrics = {}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"]}
    if trace:
        for m in cell.per_layer:
            value = cell.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = _metric(value, m["unit"])
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        line["breakdown"] = {
            "device_ops": [list(x) for x in ctx.trace.top_modules(10)],
            "idle_gaps": [list(x) for x in ctx.trace.idle_gaps(10)]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = _metric(res[m["name"]], m["unit"])
    line["metrics"] = metrics
    line["device"] = device
    if res["control"] is not None:
        line["control"] = res["control"]
    checks = {k: {"value": v, "limit": res["limits"][k]}
              for k, v in res["numbers"].items()}
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    line["checks"] = checks
    return line


if __name__ == "__main__":
    sys.exit(main())

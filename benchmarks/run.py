"""Benchmark orchestrator: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (us_per_call is 0.0 for
derived-metric rows). Engine benchmarks use the measured-cluster-workload
metric as primary (the paper's own §3.1.1 cost metric); wall-clock on this
1-core container is a secondary signal.

    PYTHONPATH=src python -m benchmarks.run [--quick|--smoke] [--only a,b]
                                            [--json-out DIR]

``--smoke`` imports and runs EVERY registered benchmark at scale 0.01 with
minimal repeats — the CI job that keeps new benchmarks from rotting
unexecuted. Registration is the ``REGISTRY`` table below: a benchmark that
is not in it does not exist as far as run.py and CI are concerned.

``--json-out DIR`` additionally writes one ``DIR/<bench>.json`` per
benchmark — the emitted rows plus profile metadata and wall time — which
the CI smoke job uploads as the ``bench-smoke-json`` artifact, seeding the
cross-PR benchmark trajectory.

``--json-bundle FILE`` writes the same payloads as ONE file holding a
JSON list — the committable form. ``BENCH_BASELINE.json`` at the repo
root is such a bundle (from ``--smoke``); CI compares every push's fresh
smoke run against it.

``--compare OLD NEW`` diffs two such artifacts (files, bundles, or
directories of ``<bench>.json`` files) instead of running anything: every
tracked metric — per-benchmark wall seconds and every timed row's
``us_per_call`` — is compared, and any regression beyond ``--threshold``
(default 10%) exits non-zero with the offenders listed.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

from repro.compile_cache import enable_compile_cache

from . import common

#: Tiny scale for the CI smoke profile: every fact shrinks to its 8-row
#: floor .. ~1k rows; dimensions keep their fixed sizes. Fast enough to run
#: the whole registry in one CI job, big enough to execute every code path.
SMOKE_SCALE = 0.01


def _registry():
    """name -> (module, default_kwargs, quick_kwargs, smoke_kwargs).

    Bench modules are imported here rather than at module top level so
    ``--help`` and argument errors don't pay for the jax-heavy stack.
    (``--only`` still imports every registered module — imports are cheap
    relative to any single benchmark run.)"""
    from . import (bench_accuracy, bench_cost_model, bench_filters,
                   bench_hypercube, bench_kernels, bench_psts,
                   bench_reorder, bench_reopt, bench_roofline,
                   bench_service, bench_skew, bench_strategies,
                   bench_w_sweep)

    s = SMOKE_SCALE
    return {
        "cost_model": (bench_cost_model, {}, {}, {}),
        "kernels": (bench_kernels, {}, {}, {}),
        "strategies": (bench_strategies,
                       {"scales": (0.2, 0.5), "runs": 2},
                       {"scales": (0.2,), "runs": 1},
                       {"scales": (s,), "runs": 1}),
        "accuracy": (bench_accuracy, {"scale": 0.3, "runs": 2},
                     {"scale": 0.2, "runs": 1}, {"scale": s, "runs": 1}),
        "psts": (bench_psts, {"scale": 0.3, "runs": 2},
                 {"scale": 0.2, "runs": 1}, {"scale": s, "runs": 1}),
        "w_sweep": (bench_w_sweep, {"scale": 0.3, "runs": 2},
                    {"scale": 0.2, "runs": 1}, {"scale": s, "runs": 1}),
        "reorder": (bench_reorder, {"scale": 0.2}, {"scale": 0.2},
                    {"scale": s}),
        "hypercube": (bench_hypercube, {"scale": 0.2}, {"scale": 0.2},
                      {"scale": s}),
        "skew": (bench_skew, {"scale": 0.2, "zipfs": (0.0, 0.8, 1.2, 1.4)},
                 {"scale": 0.2, "zipfs": (0.0, 1.2)},
                 {"scale": s, "zipfs": (0.0, 1.2)}),
        "filters": (bench_filters, {"scale": 0.2}, {"scale": 0.2},
                    {"scale": s}),
        "reopt": (bench_reopt, {"scale": 0.1}, {"scale": 0.1},
                  {"scale": s}),
        "service": (bench_service, {"scale": 0.2}, {"scale": 0.1},
                    {"scale": s}),
        "roofline": (bench_roofline, {}, {}, {}),
    }


def _load_artifacts(path: pathlib.Path) -> dict:
    """Load bench-JSON artifacts keyed by benchmark name: one per-bench
    file, a bundle file holding a JSON list of payloads (the committed
    ``BENCH_BASELINE.json`` form), or a directory of ``*.json`` files
    (each itself a payload or a bundle)."""
    if path.is_dir():
        files = sorted(path.glob("*.json"))
    else:
        files = [path]
    out = {}
    for f in files:
        payload = json.loads(f.read_text())
        for p in payload if isinstance(payload, list) else [payload]:
            out[p["bench"]] = p
    return out


def _tracked_metrics(artifacts: dict) -> dict:
    """Flatten artifacts into ``metric-name -> value`` for comparison:
    per-benchmark wall seconds plus every row's ``us_per_call`` — zero
    rows (derived metrics, warm-cache passes) included, so a baseline
    that was 0 still has teeth via the absolute-delta fallback."""
    metrics = {}
    for bench, payload in artifacts.items():
        metrics[f"{bench}:seconds"] = float(payload["seconds"])
        for row in payload.get("rows", []):
            metrics[f"{bench}/{row['name']}:us_per_call"] = float(
                row.get("us_per_call", 0.0))
    return metrics


def compare_artifacts(old_path: str, new_path: str,
                      threshold: float = 0.10,
                      abs_threshold: float = 100.0) -> list:
    """Regressions of ``new`` vs ``old``: tracked metrics that grew by
    more than ``threshold`` (fraction), plus tracked metrics that vanished
    (a silently dropped benchmark is a regression, not a win). A
    zero-valued baseline has no ratio to regress against — dividing by it
    (or guarding on ``old > 0`` alone) would let any blowup through
    silently — so those metrics fall back to an absolute gate: new value
    beyond ``abs_threshold`` (same unit as the metric) is an offense.
    Returns a list of human-readable offense lines, empty when clean."""
    old = _tracked_metrics(_load_artifacts(pathlib.Path(old_path)))
    new = _tracked_metrics(_load_artifacts(pathlib.Path(new_path)))
    offenses = []
    for name, old_val in sorted(old.items()):
        if name not in new:
            offenses.append(f"{name}: missing from new artifact "
                            f"(was {old_val:g})")
            continue
        new_val = new[name]
        if old_val > 0:
            if new_val > old_val * (1 + threshold):
                pct = 100.0 * (new_val / old_val - 1)
                offenses.append(f"{name}: {old_val:g} -> {new_val:g} "
                                f"(+{pct:.1f}% > {100 * threshold:.0f}%)")
        elif new_val > abs_threshold:
            offenses.append(f"{name}: {old_val:g} -> {new_val:g} "
                            f"(zero baseline; exceeds absolute "
                            f"threshold {abs_threshold:g})")
    return offenses


def new_benchmarks(old_path: str, new_path: str) -> list:
    """Benchmarks present only in the NEW artifact (freshly registered, no
    baseline entry). ``--compare`` used to skip these silently — CI passed
    while tracking none of their metrics. They are informational, not
    offenses (a new benchmark is not a regression), but surfacing them
    prompts the re-baseline that gives their metrics teeth."""
    old = _load_artifacts(pathlib.Path(old_path))
    new = _load_artifacts(pathlib.Path(new_path))
    return sorted(set(new) - set(old))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller scales / fewer repeats")
    ap.add_argument("--smoke", action="store_true",
                    help=f"run every registered benchmark at scale "
                         f"{SMOKE_SCALE} (CI rot-guard)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of registered names")
    ap.add_argument("--json-out", default="",
                    help="directory for per-benchmark JSON result files")
    ap.add_argument("--json-bundle", default="",
                    help="write all results as one JSON-list bundle file "
                         "(the BENCH_BASELINE.json form)")
    ap.add_argument("--compare", nargs=2, metavar=("OLD", "NEW"),
                    help="diff two bench-JSON artifacts (files or "
                         "directories) instead of running; exit non-zero "
                         "on any regression beyond --threshold")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="--compare regression threshold as a fraction "
                         "(default 0.10 = 10%%)")
    ap.add_argument("--abs-threshold", type=float, default=100.0,
                    help="--compare absolute fallback gate for metrics "
                         "whose baseline is 0 (default 100, metric units)")
    args = ap.parse_args(argv)
    if args.compare:
        offenses = compare_artifacts(args.compare[0], args.compare[1],
                                     args.threshold, args.abs_threshold)
        for line in offenses:
            print(f"REGRESSION {line}")
        for bench in new_benchmarks(args.compare[0], args.compare[1]):
            print(f"NEW {bench}: not in the baseline — informational only; "
                  f"re-baseline to start tracking its metrics")
        if offenses:
            sys.exit(1)
        print(f"no regressions beyond {100 * args.threshold:.0f}%")
        return
    enable_compile_cache()
    json_dir = pathlib.Path(args.json_out) if args.json_out else None
    if json_dir is not None:
        json_dir.mkdir(parents=True, exist_ok=True)
    bundle_path = (pathlib.Path(args.json_bundle) if args.json_bundle
                   else None)
    capture = json_dir is not None or bundle_path is not None
    bundle = []
    registry = _registry()
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - set(registry)
        if unknown:
            ap.error(f"unknown benchmarks: {sorted(unknown)}; "
                     f"registered: {sorted(registry)}")

    print("name,us_per_call,derived")
    t0 = time.time()
    for name, (module, default, quick, smoke) in registry.items():
        if only is not None and name not in only:
            continue
        kwargs = smoke if args.smoke else (quick if args.quick else default)
        t1 = time.time()
        if capture:
            common.start_capture()
        module.run(**kwargs)
        dt = time.time() - t1
        if capture:
            profile = ("smoke" if args.smoke
                       else "quick" if args.quick else "default")
            payload = {"bench": name, "profile": profile, "kwargs": kwargs,
                       "seconds": round(dt, 3), "rows": common.end_capture()}
            bundle.append(payload)
            if json_dir is not None:
                (json_dir / f"{name}.json").write_text(
                    json.dumps(payload, indent=1, default=str) + "\n")
        print(f"# {name} {dt:.1f}s", file=sys.stderr)

    if bundle_path is not None:
        bundle_path.write_text(
            json.dumps(bundle, indent=1, default=str) + "\n")
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()

"""A new configuration, traffic mix or per-layer metric is a new file plus
its entry in BENCHMARK.json: the harness finds each by name, with no other
file of the benchmark edited."""

import json
import shutil
import time
from pathlib import Path

from chipbench import run as entry
from chipbench.bench import cell
from chipbench.bench.spec import load_cell

ROOT = Path(__file__).resolve().parents[2]


def _snapshot(bench: Path) -> dict:
    return {p.relative_to(bench): p.read_bytes()
            for p in bench.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_files_are_found_by_name(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    bench = tmp_path / "chipbench"
    shutil.copytree(ROOT / "chipbench", bench,
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    before = _snapshot(bench)

    cfg = json.loads((bench / "configs" / "tpcds_sf1_p8.json").read_text())
    cfg.update(name="tiny_p4", p=4)
    cfg["rows"]["store_sales"] = 20_000
    (bench / "configs" / "tiny_p4.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "one_stream.json").write_text(json.dumps({
        "kind": "closed_loop", "streams": 1,
        "templates": {"tpcds_q42": {
            "params": {"month": {"range": [11, 12, 1]},
                       "year": {"range": [1998, 2002, 1]}}}}}))
    (bench / "metrics" / "max_latency_ms.py").write_text(
        "def read(ctx):\n"
        "    return 1e3 * max(r.latency_s for r in ctx.records)\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_p4", "source": "a test",
                            "file": "chipbench/configs/tiny_p4.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "tiny_p4.one_stream",
                              "config": "tiny_p4", "traffic": "one_stream",
                              "chips": 1, "why": "a test"})
    spec["per_layer"].append({"name": "max_latency_ms", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "service / planner",
                              "moves": "query_p90_ms",
                              "workloads": ["tiny_p4.one_stream"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    c = load_cell(tmp_path, "tiny_p4.one_stream")
    assert c.config["p"] == 4
    assert list(c.traffic["templates"]) == ["tpcds_q42"]
    assert "max_latency_ms" in [m["name"] for m in c.per_layer]
    assert "max_latency_ms" not in [
        m["name"] for m in load_cell(tmp_path,
                                     "sf1_p8.star_x4").per_layer]

    res = cell.run(c, 7, 0.3, time.perf_counter(), trace=True,
                   log=lambda m: None)
    assert res["correct"]
    line = entry.result_line(c, res, "cpu", 1, trace=True)
    assert line["metrics"]["max_latency_ms"]["value"] > 0
    assert list(line)[-1] == "checks"
    line = entry.result_line(c, res, "cpu", 1, trace=False)
    assert set(line["metrics"]) == {"qps", "query_p50_ms", "query_p90_ms",
                                    "setup_s"}

    after = _snapshot(bench)
    added = set(after) - set(before)
    assert {str(p) for p in added} == {"configs/tiny_p4.json",
                                       "traffic/one_stream.json",
                                       "metrics/max_latency_ms.py"}
    assert all(after[p] == before[p] for p in before)

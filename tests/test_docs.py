"""Docs stay true: the cost-model equation map covers the module's whole
public surface, ``__all__`` itself can't rot, and no markdown link or
referenced repo path dangles.

These are the safety nets behind the ``docs/`` satellite: a cost function
added without a row in docs/cost_model.md — or a doc reorganization that
breaks a cross-link — fails tier-1, not a reader.
"""

import inspect
import pathlib
import re

import pytest

import repro.core.cost_model as cost_model
import repro.obs as obs
import repro.sql.binder as sql_binder
import repro.sql.parser as sql_parser
import repro.sql.plan_analysis as plan_analysis
import repro.sql.printer as sql_printer
import repro.sql.selectivity as sql_selectivity
import repro.sql.service as sql_service

ROOT = pathlib.Path(__file__).parent.parent
DOCS = ROOT / "docs"


def _public_surface(module):
    """Names the module actually defines publicly (functions, classes,
    upper-case constants) — the ground truth ``__all__`` must match."""
    names = set()
    for name, obj in vars(module).items():
        if name.startswith("_"):
            continue
        if inspect.isfunction(obj) or inspect.isclass(obj):
            if getattr(obj, "__module__", None) == module.__name__:
                names.add(name)
        elif name.isupper():
            names.add(name)
    return names


def test_cost_model_all_matches_public_surface():
    assert set(cost_model.__all__) == _public_surface(cost_model)


def test_cost_model_doc_covers_every_public_name():
    """docs/cost_model.md documents every name in cost_model.__all__ —
    the acceptance criterion of the docs satellite. Names must appear in
    backticks so the doc references them as code, not in passing."""
    doc = (DOCS / "cost_model.md").read_text()
    documented = set(re.findall(r"`([A-Za-z_][A-Za-z0-9_]*)`", doc))
    missing = set(cost_model.__all__) - documented
    assert not missing, (
        f"docs/cost_model.md is missing {sorted(missing)} — every public "
        "cost-model name needs a row in the equation map")


def test_plan_analysis_all_matches_public_surface():
    assert set(plan_analysis.__all__) == _public_surface(plan_analysis)


def test_plan_analysis_doc_covers_every_rule_and_name():
    """docs/plan_analysis.md documents every rule in the RULES registry
    (as a `### `-headed section, so each rule gets invariant + failure
    example, not a passing mention) and backticks every public name."""
    doc = (DOCS / "plan_analysis.md").read_text()
    for rule_id in plan_analysis.RULES:
        assert f"### `{rule_id}`" in doc, (
            f"docs/plan_analysis.md has no section for {rule_id}")
    documented = set(re.findall(r"`([A-Za-z_][A-Za-z0-9_]*)`", doc))
    missing = set(plan_analysis.__all__) - documented
    assert not missing, (
        f"docs/plan_analysis.md is missing {sorted(missing)}")


def test_rule_registry_is_consistent():
    """Registry hygiene: ids key their own Rule objects, severities are
    from the documented vocabulary, invariants are real sentences."""
    for rule_id, rule in plan_analysis.RULES.items():
        assert rule.rule_id == rule_id
        assert rule.severity in ("error", "perf"), rule_id
        assert len(rule.invariant) > 20, rule_id


@pytest.mark.parametrize("module", [sql_parser, sql_binder, sql_printer,
                                    sql_selectivity],
                         ids=lambda m: m.__name__)
def test_sql_frontend_all_matches_public_surface(module):
    assert set(module.__all__) == _public_surface(module)


def test_sql_frontend_doc_covers_every_public_name():
    """docs/sql_frontend.md backticks every public name of the front end
    (parser, binder, printer, selectivity) — grammar, lowering table and
    binder rules must name the code they describe."""
    doc = (DOCS / "sql_frontend.md").read_text()
    documented = set(re.findall(r"`([A-Za-z_][A-Za-z0-9_]*)`", doc))
    surface = (set(sql_parser.__all__) | set(sql_binder.__all__)
               | set(sql_printer.__all__) | set(sql_selectivity.__all__))
    missing = surface - documented
    assert not missing, (
        f"docs/sql_frontend.md is missing {sorted(missing)}")


def test_service_all_matches_public_surface():
    assert set(sql_service.__all__) == _public_surface(sql_service)


def test_serving_doc_covers_every_public_name():
    """docs/serving.md backticks every public service name (plus the
    PlanCache it documents the key discipline of) — the lifecycle
    description must name the code that implements each step."""
    doc = (DOCS / "serving.md").read_text()
    documented = set(re.findall(r"`([A-Za-z_][A-Za-z0-9_]*)`", doc))
    missing = (set(sql_service.__all__) | {"PlanCache"}) - documented
    assert not missing, (
        f"docs/serving.md is missing {sorted(missing)} — every public "
        "service name needs a place in the lifecycle doc")


def test_architecture_links_to_statistics():
    """The architecture page must point readers at the statistics /
    checkpoint-re-optimization page (the PR-10 subsystem doc)."""
    arch = (DOCS / "architecture.md").read_text()
    assert "](statistics.md)" in arch, (
        "docs/architecture.md no longer links to docs/statistics.md")


def test_statistics_doc_covers_the_stats_surface():
    """docs/statistics.md backticks every load-bearing statistics name:
    the shapes, the estimator entry points, and the re-opt machinery."""
    doc = (DOCS / "statistics.md").read_text()
    documented = set(re.findall(r"`([A-Za-z_][A-Za-z0-9_.]*)`", doc))
    required = {"ColumnSummary", "ColumnStats", "column_stats_from_summary",
                "build_summary", "merge_summaries", "filter_summary",
                "q_error", "derive_selectivity", "stats_retain_fraction",
                "ReoptDecision", "CardinalityRecord", "R2_REOPT_DISCIPLINE",
                "MCV_TOP_K", "HISTOGRAM_BUCKETS"}
    missing = required - documented
    assert not missing, (
        f"docs/statistics.md is missing {sorted(missing)}")


def test_architecture_links_to_serving():
    """The single-query architecture page must point readers at the
    multi-tenant serving page (and the link must resolve, which
    test_markdown_links_resolve separately enforces)."""
    arch = (DOCS / "architecture.md").read_text()
    assert "](serving.md)" in arch, (
        "docs/architecture.md no longer links to docs/serving.md")


def test_observability_doc_names_every_span_and_counter():
    """docs/observability.md backticks every span and counter the engine
    uses (``obs.SPANS``, ``obs.COUNTERS``): an operator reading a trace
    finds each name explained."""
    doc = (DOCS / "observability.md").read_text()
    documented = set(re.findall(r"`([A-Za-z_][A-Za-z0-9_.]*)`", doc))
    missing = (set(obs.SPANS) | set(obs.COUNTERS)) - documented
    assert not missing, (
        f"docs/observability.md is missing {sorted(missing)}")


def _markdown_files():
    return [ROOT / "README.md", *sorted(DOCS.glob("*.md"))]


def test_markdown_links_resolve():
    """Every relative markdown link in README.md and docs/*.md points at
    a file that exists (anchors and external URLs are out of scope)."""
    broken = []
    for md in _markdown_files():
        for text, target in re.findall(r"\[([^\]]*)\]\(([^)]+)\)",
                                       md.read_text()):
            target = target.split("#")[0]
            if not target or target.startswith(("http://", "https://")):
                continue
            if not (md.parent / target).exists():
                broken.append(f"{md.name}: [{text}]({target})")
    assert not broken, f"dangling markdown links: {broken}"


def test_documented_repo_paths_exist():
    """Backticked repo paths (src/..., tests/..., benchmarks/..., docs/...)
    quoted in the docs must exist — module renames must update the docs
    in the same PR."""
    pat = re.compile(r"`((?:src|tests|benchmarks|docs|examples)/[\w./-]+)`")
    missing = []
    for md in _markdown_files():
        for path in pat.findall(md.read_text()):
            if not (ROOT / path).exists():
                missing.append(f"{md.name}: {path}")
    assert not missing, f"docs reference nonexistent paths: {missing}"

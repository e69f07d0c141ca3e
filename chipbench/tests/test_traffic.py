"""The traffic generator: streams come from the seed alone, every stream
walks the templates as a shuffled deck, values are drawn without
replacement, and a substituted parameter keeps its template's selectivity
inside the template's zone."""

import collections
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench.bench import data, reference
from chipbench.bench.traffic import Traffic

BENCH = Path(__file__).resolve().parents[1]
SEED = 2**31 + 3


@pytest.fixture(scope="module")
def world():
    cfg = json.loads((BENCH / "configs" / "tpcds_sf1_p8.json").read_text())
    spec = json.loads((BENCH / "traffic" / "star_x4.json").read_text())
    tables = data.make_tables(cfg, SEED)
    return spec, tables, Traffic(spec, BENCH / "queries", tables)


def _rounds(traffic, seed, n):
    streams = traffic.streams(seed)
    return [[next(s) for s in streams] for _ in range(n)]


def test_streams_come_from_the_seed(world):
    spec, tables, traffic = world
    again = Traffic(spec, BENCH / "queries", tables)
    assert _rounds(traffic, SEED, 8) == _rounds(again, SEED, 8)
    assert _rounds(traffic, SEED, 8) != _rounds(traffic, SEED + 1, 8)


def test_every_deck_holds_each_template_once(world):
    _, _, traffic = world
    names = sorted(traffic.templates)
    rounds = _rounds(traffic, SEED, 3 * traffic.deck)
    for stream in range(traffic.n_streams):
        sent = [r[stream].template for r in rounds]
        for d in range(3):
            assert sorted(sent[d * traffic.deck:(d + 1) * traffic.deck]) \
                == names


def test_values_are_drawn_without_replacement(world):
    _, _, traffic = world
    rounds = _rounds(traffic, SEED, 40)
    sent = collections.defaultdict(list)
    for q in (q for r in rounds for q in r):
        sent[q.template].append(q.params)
    for name, params in sent.items():
        n = len(traffic.templates[name].candidates)
        first = params[:n]
        assert len(set(first)) == len(first), name
        if len(params) > n:
            assert params[n:2 * n] == first[:len(params[n:2 * n])], name


def _fact_rows(q, tables):
    out = reference.answer(q.sql, tables)
    return int(out["count_ss_quantity"].sum())


def test_parameters_keep_the_selectivity_in_the_zone(world):
    _, tables, traffic = world
    rounds = _rounds(traffic, SEED, 12)
    by_template = collections.defaultdict(list)
    for q in (q for r in rounds for q in r):
        by_template[q.template].append(q)
    for name, qs in by_template.items():
        t = traffic.templates[name]
        if t.zone is not None:
            lo, hi = t.zone["band"]
            for q in qs:
                assert lo <= t.zone_count(dict(q.params), tables) <= hi
        rows = [_fact_rows(q, tables) for q in qs]
        mean = np.mean(rows)
        assert min(rows) > 0.5 * mean and max(rows) < 1.5 * mean, \
            (name, rows)


def test_a_cycle_is_whole_decks_of_every_stream(world):
    _, _, traffic = world
    cycle = traffic.cycle(SEED)
    assert len(cycle) == traffic.decks * traffic.deck
    assert all(len(r) == traffic.n_streams for r in cycle)
    assert cycle == _rounds(traffic, SEED, len(cycle))
    assert cycle == traffic.cycle(SEED)
    sent = collections.Counter(q.template for r in cycle for q in r)
    assert set(sent.values()) == {traffic.decks * traffic.n_streams}

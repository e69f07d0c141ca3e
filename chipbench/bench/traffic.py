"""The general traffic generator: query streams from a traffic file.

A traffic file (``traffic/<name>.json``) names SQL templates
(``queries/<template>.sql``, with ``{param}`` placeholders) and, for each,
how its parameters are substituted:

- ``{"range": [start, stop, step]}``: an integer of that inclusive range;
- ``{"choice": [a, b, ...]}``: one of the listed values (numbers or text);
- ``{"offset": [other, n]}``: another parameter plus ``n``.

A ``zone`` keeps a template inside a TPC-DS comparability zone: its ``sql``
(a COUNT over a dimension, with the same placeholders) is evaluated by the
reference on the benchmark's host copy of the data, and only parameter
values whose count lies in ``band`` (inclusive) are substituted. So every
query of a template selects about the same number of rows, whatever the
seed.

``streams`` closed-loop streams each walk the templates as a shuffled deck:
every template once per deck, in an order drawn from the seed. A cycle is
``decks`` decks (default 1): its rounds, each holding every stream's next
query, are what one session of the mix sends. Parameter values are drawn
without replacement: each template's admissible values are shuffled from
the seed once, and the streams take them in turn, from the start again
only once all have been sent. So a cycle sends every template equally
often, and repeats a value only where a template has fewer values than the
cycle sends it.
"""

from __future__ import annotations

import dataclasses
import itertools
from pathlib import Path
from typing import Dict, Iterator, List, Mapping, Tuple

import numpy as np

from . import reference

Params = Dict[str, object]


@dataclasses.dataclass(frozen=True)
class Query:
    template: str
    params: Tuple[Tuple[str, object], ...]
    sql: str

    @property
    def key(self) -> Tuple[str, str]:
        """Identity of the answer: the template and its filled text."""
        return (self.template, self.sql)


def _values(rule: Mapping) -> list:
    if "range" in rule:
        start, stop, step = rule["range"]
        return list(range(start, stop + 1, step))
    return list(rule["choice"])


class Template:
    """One SQL template with its parameter rules and its zone."""

    def __init__(self, name: str, text: str, spec: Mapping,
                 dims: Mapping[str, Dict[str, np.ndarray]]):
        self.name = name
        self.text = text
        self.spec = dict(spec.get("params", {}))
        self.zone = spec.get("zone")
        free = {k: _values(v) for k, v in self.spec.items()
                if "offset" not in v}
        self.candidates: List[Params] = []
        for values in itertools.product(*free.values()):
            params = self._derive(dict(zip(free, values)))
            if self.zone is not None:
                lo, hi = self.zone["band"]
                if not lo <= self.zone_count(params, dims) <= hi:
                    continue
            self.candidates.append(params)
        if not self.candidates:
            raise ValueError(f"template {name}: no parameter value lies in "
                             f"its zone {self.zone}")

    def _derive(self, params: Params) -> Params:
        for k, v in self.spec.items():
            if "offset" in v:
                other, n = v["offset"]
                params[k] = params[other] + int(n)
        return params

    def zone_count(self, params: Mapping, dims) -> int:
        out = reference.answer(self.zone["sql"].format(**params), dims)
        return int(next(iter(out.values()))[0])

    def fill(self, params: Mapping) -> Query:
        sql = " ".join(self.text.format(**params).split())
        return Query(self.name, tuple(sorted(params.items())), sql)

    def pool(self, rng: np.random.Generator) -> Iterator[Query]:
        """The admissible values in an order drawn from ``rng``, cycled."""
        order = rng.permutation(len(self.candidates))
        for i in itertools.cycle(order):
            yield self.fill(self.candidates[int(i)])


class Traffic:
    """A closed-loop mix: its templates and the streams drawn from a seed."""

    def __init__(self, spec: Mapping, queries_dir: Path,
                 dims: Mapping[str, Dict[str, np.ndarray]]):
        if spec.get("kind") != "closed_loop":
            raise ValueError(f"unknown traffic kind {spec.get('kind')!r}")
        self.n_streams = int(spec["streams"])
        self.decks = int(spec.get("decks", 1))
        self.templates = {
            name: Template(name, (queries_dir / f"{name}.sql").read_text(),
                           t, dims)
            for name, t in sorted(spec["templates"].items())}

    @property
    def deck(self) -> int:
        """Rounds in one deck: every stream sends each template once."""
        return len(self.templates)

    def streams(self, seed: int) -> List[Iterator[Query]]:
        """The streams of a run; they share each template's pool of
        values, so the ``i``-th query of a template in the run, whichever
        stream sends it, takes the pool's ``i``-th value."""
        rng = np.random.default_rng([seed, 0])
        pools = {name: t.pool(rng) for name, t in self.templates.items()}
        return [self._stream(np.random.default_rng([seed, 1 + i]), pools)
                for i in range(self.n_streams)]

    def _stream(self, rng: np.random.Generator,
                pools: Mapping[str, Iterator[Query]]) -> Iterator[Query]:
        names = list(self.templates)
        while True:
            for i in rng.permutation(len(names)):
                yield next(pools[names[i]])

    def cycle(self, seed: int) -> List[List[Query]]:
        """The rounds of one cycle: ``decks`` decks of every stream."""
        streams = self.streams(seed)
        return [[next(s) for s in streams]
                for _ in range(self.decks * self.deck)]

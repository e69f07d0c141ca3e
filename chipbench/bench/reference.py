"""Plain numpy evaluation of the benchmark's SQL, the yardstick of `correct`.

The dialect is the one the query templates use: a SELECT list of columns
and ``SUM``/``COUNT`` calls, a FROM list of tables joined by column = column
equalities in the WHERE, AND-ed single-column comparisons (``= <> < <= >
>=``, ``BETWEEN``), and an optional ``GROUP BY`` of one column. Every join
is an inner equi-join on a key that is unique on one side, as a fact's
foreign key into a dimension is; sums are taken in float64 over the
float32 columns. Column names are unique across the schema, as in TPC-DS,
so no qualifier is needed.

Nothing here imports the engine: the tables are ``data.py``'s host columns.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

Columns = Dict[str, np.ndarray]

_TOKEN = re.compile(r"\s*(?:(-?\d+\.\d*|-?\d+)|([A-Za-z_][A-Za-z_0-9]*)"
                    r"|(<>|<=|>=|[(),=<>]))")
_KEYWORDS = {"SELECT", "FROM", "WHERE", "AND", "GROUP", "BY", "BETWEEN"}
_AGGS = {"SUM": "sum", "COUNT": "count"}
_OPS = {"=": "eq", "<>": "ne", "<": "lt", "<=": "le", ">": "gt", ">=": "ge"}
_NAME = re.compile(r"[A-Za-z_][A-Za-z_0-9]*")


class SqlError(ValueError):
    """Text outside the dialect above."""


@dataclasses.dataclass(frozen=True)
class Pred:
    column: str
    op: str                      # eq ne lt le gt ge between eqcol
    values: Tuple = ()


@dataclasses.dataclass(frozen=True)
class Select:
    items: Tuple[Tuple[str, Optional[str]], ...]   # (column, agg or None)
    tables: Tuple[str, ...]
    where: Tuple[Pred, ...]
    group_by: Optional[str]


def tokenize(text: str) -> List[str]:
    out, pos = [], 0
    text = text.strip()
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if m is None or m.end() == pos:
            raise SqlError(f"cannot read {text[pos:pos + 20]!r}")
        tok = next(g for g in m.groups() if g is not None)
        out.append(tok.upper() if tok.upper() in _KEYWORDS | set(_AGGS)
                   else tok)
        pos = m.end()
    return out


class _Parser:
    def __init__(self, tokens: List[str]):
        self.toks = tokens
        self.i = 0

    def peek(self) -> Optional[str]:
        return self.toks[self.i] if self.i < len(self.toks) else None

    def take(self, want: Optional[str] = None) -> str:
        tok = self.peek()
        if tok is None or (want is not None and tok != want):
            raise SqlError(f"expected {want or 'a token'} at {self.i}, "
                           f"found {tok!r}")
        self.i += 1
        return tok

    def ident(self) -> str:
        tok = self.take()
        if not _NAME.fullmatch(tok) or tok in _KEYWORDS | set(_AGGS):
            raise SqlError(f"expected a name, found {tok!r}")
        return tok

    def number(self) -> float:
        tok = self.take()
        try:
            return float(tok)
        except ValueError:
            raise SqlError(f"expected a number, found {tok!r}") from None

    def listed(self, item) -> list:
        out = [item()]
        while self.peek() == ",":
            self.take()
            out.append(item())
        return out

    def select(self) -> Select:
        self.take("SELECT")
        items = self.listed(self.item)
        self.take("FROM")
        tables = self.listed(self.ident)
        where = []
        if self.peek() == "WHERE":
            self.take()
            where.append(self.pred())
            while self.peek() == "AND":
                self.take()
                where.append(self.pred())
        group_by = None
        if self.peek() == "GROUP":
            self.take()
            self.take("BY")
            group_by = self.ident()
        return Select(tuple(items), tuple(tables), tuple(where), group_by)

    def item(self) -> Tuple[str, Optional[str]]:
        if self.peek() in _AGGS:
            agg = _AGGS[self.take()]
            self.take("(")
            col = self.ident()
            self.take(")")
            return col, agg
        return self.ident(), None

    def pred(self) -> Pred:
        col = self.ident()
        tok = self.take()
        if tok == "BETWEEN":
            lo = self.number()
            self.take("AND")
            return Pred(col, "between", (lo, self.number()))
        if tok not in _OPS:
            raise SqlError(f"unknown comparison {tok!r}")
        if tok == "=" and self.peek() is not None and \
                _NAME.fullmatch(self.peek()):
            return Pred(col, "eqcol", (self.ident(),))
        return Pred(col, _OPS[tok], (self.number(),))


def parse(text: str) -> Select:
    p = _Parser(tokenize(text))
    out = p.select()
    if p.peek() is not None:
        raise SqlError(f"unread text from token {p.i}: {p.peek()!r}")
    return out


def _mask(cols: Columns, pred: Pred) -> np.ndarray:
    c = cols[pred.column]
    v = pred.values
    if pred.op == "between":
        return (c >= v[0]) & (c <= v[1])
    return {"eq": np.equal, "ne": np.not_equal, "lt": np.less,
            "le": np.less_equal, "gt": np.greater,
            "ge": np.greater_equal}[pred.op](c, v[0])


def _rows(cols: Columns) -> int:
    return len(next(iter(cols.values())))


def _unique(keys: np.ndarray) -> bool:
    return len(np.unique(keys)) == len(keys)


def inner_join(left: Columns, right: Columns, lkey: str,
               rkey: str) -> Columns:
    """Every pair of rows with equal keys; the keys of one side have to be
    unique, and that side is looked up by a sorted search."""
    if _unique(right[rkey]):
        build, probe, bkey, pkey = right, left, rkey, lkey
    elif _unique(left[lkey]):
        build, probe, bkey, pkey = left, right, lkey, rkey
    else:
        raise SqlError(f"join {lkey} = {rkey}: neither side is unique")
    order = np.argsort(build[bkey], kind="stable")
    keys = build[bkey][order]
    probe_keys = probe[pkey]
    pos = np.minimum(np.searchsorted(keys, probe_keys), max(len(keys) - 1,
                                                             0))
    hit = np.flatnonzero(keys[pos] == probe_keys) if len(keys) else \
        np.zeros(0, np.int64)
    out = {k: v[hit] for k, v in probe.items()}
    bi = order[pos[hit]]
    out.update({k: v[bi] for k, v in build.items()})
    return out


#: How an aggregate reduces one group's values; ``Accumulate`` lets the
#: control swap in a lower precision.
Accumulate = Callable[[str, np.ndarray, np.ndarray, int], np.ndarray]


def accumulate64(agg: str, values: np.ndarray, group: np.ndarray,
                 n_groups: int) -> np.ndarray:
    """Per-group reduction: float64 sums, int64 counts."""
    if agg == "count":
        return np.bincount(group, minlength=n_groups).astype(np.int64)
    return np.bincount(group, weights=values.astype(np.float64),
                       minlength=n_groups)


def evaluate(query: Select, tables: Dict[str, Columns],
             accumulate: Accumulate = accumulate64) -> Columns:
    """The query's result columns, named as the engine names them
    (``<agg>_<column>`` for an aggregate)."""
    filtered = {}
    for name in query.tables:
        if name not in tables:
            raise SqlError(f"unknown table {name!r}")
        cols = tables[name]
        preds = [p for p in query.where
                 if p.op != "eqcol" and p.column in cols]
        if preds:
            keep = np.ones(_rows(cols), bool)
            for p in preds:
                keep &= _mask(cols, p)
            cols = {k: v[keep] for k, v in cols.items()}
        filtered[name] = cols
    edges = [p for p in query.where if p.op == "eqcol"]
    joined, cols = {query.tables[0]}, filtered[query.tables[0]]
    while len(joined) < len(query.tables):
        for e in edges:
            a, b = e.column, e.values[0]
            if (a in cols) == (b in cols):
                continue
            inner, outer = (a, b) if a in cols else (b, a)
            name = next(t for t in query.tables
                        if t not in joined and outer in filtered[t])
            cols = inner_join(cols, filtered[name], inner, outer)
            joined.add(name)
            break
        else:
            raise SqlError(f"tables {sorted(set(query.tables) - joined)} "
                           f"are not joined to the rest")
    aggs = [(c, a) for c, a in query.items if a is not None]
    if not aggs and query.group_by is None:
        return {c: cols[c] for c, _ in query.items}
    if query.group_by is None:
        group = np.zeros(_rows(cols), np.int64)
        out: Columns = {}
        n_groups = 1
    else:
        keys, group = np.unique(cols[query.group_by], return_inverse=True)
        out = {query.group_by: keys}
        n_groups = len(keys)
    for col, agg in aggs:
        out[f"{agg}_{col}"] = accumulate(agg, cols[col], group, n_groups)
    return out


def answer(text: str, tables: Dict[str, Columns],
           accumulate: Accumulate = accumulate64) -> Columns:
    """``evaluate`` over only the columns the text names (the same result,
    with less to gather through the joins)."""
    names = set(tokenize(text))
    pruned = {t: {c: v for c, v in cols.items() if c in names}
              for t, cols in tables.items()}
    return evaluate(parse(text), pruned, accumulate)

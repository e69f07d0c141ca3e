"""Device time by the engine span that launched it, from a profiler trace.

``trace.py`` splits the device's time by program name. This module splits
it by the engine's own spans (``repro.obs``): every device program is tied
to its launch on the host, and the launch to the innermost ``op.*`` span
open when it was made. ``load`` keeps, from one ``.xplane.pb`` file:

- the device's programs (line ``XLA Modules`` of a ``/device:`` plane),
  each with its ``run_id`` where the trace gives one;
- the launches: the outermost ``PjitFunction(<fn>)`` event of a host line,
  with the ``run_id`` of the execute event nested in it, where there is
  one;
- the spans: the engine's (``obs.SPANS``, with their metadata, such as
  ``query``) and the benchmark's own (``submit``, ``run``, ``fetch``,
  ``window``).

``link`` ties programs to launches by ``run_id`` where both carry it,
and otherwise by order within each module: one device runs a module's
programs in the order they were launched. (On a TPU v5e the launch does
not carry it: the program's ``run_id`` is on an enqueue event of another
host thread.) The rest is
arithmetic on intervals, so a test builds a ``SpanTrace`` by hand.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

#: Engine spans (``repro.obs.SPANS``); kept here so that a trace can be
#: read without the engine.
ENGINE_SPANS = ("service.submit", "service.batch", "service.shared",
                "service.query", "op.filter", "op.select", "op.exchange",
                "op.local_join", "op.aggregate", "op.compact", "sync")
#: The benchmark's own spans (``cell.py``).
BENCH_SPANS = ("submit", "run", "fetch")
WINDOW = "window"
#: Operator spans: a program's device time goes to the innermost one open
#: at its launch, or to ``UNATTRIBUTED`` where none is.
OP_PREFIX = "op."
UNATTRIBUTED = "unattributed"

_LAUNCH = re.compile(r"^PjitFunction\((.*)\)$")


@dataclasses.dataclass(frozen=True)
class Program:
    """One program execution on the device."""

    module: str                 # XLA module, without the fingerprint
    start_ns: float
    end_ns: float
    run_id: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class Launch:
    """One program launch on a host line (``PjitFunction(<fn>)``)."""

    fn: str
    start_ns: float
    end_ns: float
    line: str
    run_id: Optional[int] = None

    @property
    def module(self) -> str:
        return f"jit_{self.fn}"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float
    line: str
    meta: Tuple[Tuple[str, str], ...] = ()

    def get(self, key: str) -> Optional[str]:
        return dict(self.meta).get(key)


def module_key(name: str) -> str:
    """What a launch and its module share: ``jit__take(123)``,
    ``jit__take`` and ``PjitFunction(_take)``'s ``jit__take`` all give
    ``jittake`` (the compiler drops the characters a name may not hold,
    as in ``<lambda>``)."""
    return re.sub(r"[^0-9A-Za-z]", "", re.sub(r"\(\d+\)$", "", name))


@dataclasses.dataclass
class SpanTrace:
    programs: List[Program]
    launches: List[Launch]
    spans: List[Span]
    window: Tuple[float, float]

    # -- linking ---------------------------------------------------------

    def link(self) -> Tuple[str, List[Optional[int]]]:
        """``(how, launch index of each program or None)``; ``how`` is
        ``run_id`` where every program's ``run_id`` is a launch's, else
        ``order``."""
        by_id = {la.run_id: i for i, la in enumerate(self.launches)
                 if la.run_id is not None}
        if self.programs and all(p.run_id in by_id for p in self.programs):
            return "run_id", [by_id[p.run_id] for p in self.programs]
        queues: Dict[str, collections.deque] = collections.defaultdict(
            collections.deque)
        for i in sorted(range(len(self.launches)),
                        key=lambda i: self.launches[i].start_ns):
            queues[module_key(self.launches[i].module)].append(i)
        out: List[Optional[int]] = [None] * len(self.programs)
        for j in sorted(range(len(self.programs)),
                        key=lambda j: self.programs[j].start_ns):
            q = queues.get(module_key(self.programs[j].module))
            out[j] = q.popleft() if q else None
        return "order", out

    def counts_by_module(self) -> Dict[str, Tuple[int, int]]:
        """Per module, (launches, programs) inside the window, where they
        differ: linking by order is sound only where they agree."""
        lo, hi = self.window
        n: Dict[str, List[int]] = collections.defaultdict(lambda: [0, 0])
        for la in self.launches:
            if lo <= la.start_ns < hi:
                n[module_key(la.module)][0] += 1
        for p in self.programs:
            if lo <= p.start_ns < hi:
                n[module_key(p.module)][1] += 1
        return {k: (a, b) for k, (a, b) in n.items() if a != b}

    # -- attribution -----------------------------------------------------

    def op_labels(self) -> List[str]:
        """Per launch, the innermost ``op.*`` span of its line open at its
        start, or ``UNATTRIBUTED``. Spans of a line nest, so one sweep in
        time order with a stack of the open spans finds them all."""
        labels = [UNATTRIBUTED] * len(self.launches)
        by_line: Dict[str, List[int]] = collections.defaultdict(list)
        for i, la in enumerate(self.launches):
            by_line[la.line].append(i)
        spans: Dict[str, List[Span]] = collections.defaultdict(list)
        for sp in self.spans:
            if sp.name.startswith(OP_PREFIX):
                spans[sp.line].append(sp)
        for line, idx in by_line.items():
            todo = sorted(spans[line], key=lambda sp: (sp.start_ns,
                                                       -sp.end_ns))
            stack: List[Span] = []
            k = 0
            for i in sorted(idx, key=lambda i: self.launches[i].start_ns):
                t = self.launches[i].start_ns
                while k < len(todo) and todo[k].start_ns <= t:
                    stack.append(todo[k])
                    k += 1
                open_ = [sp for sp in stack if sp.end_ns > t]
                stack = open_
                if open_:
                    labels[i] = max(open_, key=lambda sp: sp.start_ns).name
        return labels

    def device_seconds_by_op(self) -> Dict[str, float]:
        """Device seconds in the window per launching ``op.*`` span
        (``unattributed`` outside any; ``unlinked`` where no launch was
        found for the program)."""
        out: Dict[str, float] = collections.Counter()
        for (label, _), sec in self.device_seconds_by_op_and_module().items():
            out[label] += sec
        return dict(out)

    def device_seconds_by_op_and_module(self) -> Dict[Tuple[str, str],
                                                      float]:
        """The same split, per module as well."""
        _, linked = self.link()
        labels = self.op_labels()
        lo, hi = self.window
        out: Dict[Tuple[str, str], float] = collections.Counter()
        for p, i in zip(self.programs, linked):
            sec = max(0.0, min(p.end_ns, hi) - max(p.start_ns, lo)) / 1e9
            if sec > 0:
                label = "unlinked" if i is None else labels[i]
                out[(label, p.module)] += sec
        return dict(out)

    @property
    def device_s(self) -> float:
        """Summed device seconds of every program, clipped to the window
        (the sum ``trace.Trace.device_seconds`` takes)."""
        lo, hi = self.window
        return sum(max(0.0, min(p.end_ns, hi) - max(p.start_ns, lo))
                   for p in self.programs) / 1e9

    # -- host spans ------------------------------------------------------

    def in_window(self, name: str) -> List[Span]:
        lo, hi = self.window
        return [s for s in self.spans
                if s.name == name and lo <= s.start_ns and s.end_ns <= hi]

    def span_seconds(self, name: str) -> float:
        """Host seconds inside spans ``name`` in the window (the outermost
        of nested ones only)."""
        total, reach = 0.0, {}
        for s in sorted(self.in_window(name), key=lambda s: s.start_ns):
            end = reach.get(s.line, float("-inf"))
            if s.end_ns > end:
                total += s.end_ns - max(s.start_ns, end)
                reach[s.line] = s.end_ns
        return total / 1e9

    def queue_waits_s(self) -> List[float]:
        """Per query, seconds from the end of its ``service.submit`` to the
        start of its ``service.query``."""
        submitted = {s.get("query"): s.end_ns
                     for s in self.in_window("service.submit")}
        return [(s.start_ns - submitted[q]) / 1e9
                for s in self.in_window("service.query")
                for q in [s.get("query")] if q in submitted]

    # -- idle gaps -------------------------------------------------------

    def busy(self) -> List[Tuple[float, float]]:
        lo, hi = self.window
        out: List[List[float]] = []
        for p in sorted(self.programs, key=lambda p: p.start_ns):
            s, e = max(p.start_ns, lo), min(p.end_ns, hi)
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [(s, e) for s, e in out]

    def label_interval(self, s: float, e: float) -> str:
        """The span innermost over most of ``[s, e)``: at each instant the
        innermost open span (engine or benchmark) of any line, then the
        one that held the longest (``none`` where no span was open)."""
        cuts = sorted({s, e} | {t for sp in self.spans
                                for t in (sp.start_ns, sp.end_ns)
                                if s < t < e})
        held: Dict[str, float] = collections.Counter()
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            open_ = [sp for sp in self.spans
                     if sp.name != WINDOW and sp.start_ns <= mid < sp.end_ns]
            name = (max(open_, key=lambda sp: sp.start_ns).name
                    if open_ else "none")
            held[name] += b - a
        return max(held.items(), key=lambda kv: kv[1])[0] if held else "none"

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` longest idle gaps of the window, each labelled by
        ``label_interval``."""
        lo, hi = self.window
        edges = [lo] + [t for iv in self.busy() for t in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(self.label_interval(s, e), (e - s) / 1e9)
                for s, e in gaps[:n]]


def metrics(trace: SpanTrace, counters: Dict[str, float],
            n_queries: int) -> Dict[str, float]:
    """The per-query numbers the spans and counters give."""
    if n_queries <= 0:
        return {}
    by_op = trace.device_seconds_by_op()
    out = {}
    for metric, label in (("filter_span_device_ms", "op.filter"),
                          ("exchange_span_device_ms", "op.exchange"),
                          ("join_span_device_ms", "op.local_join"),
                          ("aggregate_span_device_ms", "op.aggregate"),
                          ("compact_span_device_ms", "op.compact"),
                          ("unattributed_device_ms", UNATTRIBUTED)):
        out[metric] = by_op.get(label, 0.0) * 1e3 / n_queries
    out["submit_span_ms"] = (trace.span_seconds("service.submit") * 1e3
                             / n_queries)
    waits = trace.queue_waits_s()
    if waits:
        out["queue_wait_ms"] = 1e3 * sum(waits) / len(waits)
    out["sync_wait_ms"] = trace.span_seconds("sync") * 1e3 / n_queries
    out["host_syncs_per_query"] = counters.get("host_syncs", 0) / n_queries
    out["exchange_mb_per_query"] = (counters.get("exchange_bytes", 0)
                                    / 1e6 / n_queries)
    return out


# -- reading an .xplane.pb -------------------------------------------------

def _stats(ev) -> Dict[str, object]:
    return {k: v for k, v in ev.stats}


def _outermost(events: List[Tuple[str, float, float, Dict]]
               ) -> List[Tuple[str, float, float, Dict]]:
    """Launch events without those nested in a launch of the same line
    (the dispatcher records ``PjitFunction`` at two levels)."""
    out: List[Tuple[str, float, float, Dict]] = []
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        if out and ev[1] < out[-1][1] + out[-1][2]:
            continue
        out.append(ev)
    return out


def load(path: Path, line_name: str = "XLA Modules") -> SpanTrace:
    """Programs, launches and spans of one ``.xplane.pb`` trace."""
    from jax.profiler import ProfileData

    from .trace import module_name

    data = ProfileData.from_file(str(path))
    programs: List[Program] = []
    launches: List[Launch] = []
    spans: List[Span] = []
    names = set(ENGINE_SPANS) | set(BENCH_SPANS) | {WINDOW}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == line_name:
                    for ev in line.events:
                        rid = _stats(ev).get("run_id")
                        programs.append(Program(
                            module_name(ev.name), ev.start_ns, ev.end_ns,
                            None if rid is None else int(rid)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tag = f"{plane.name}/{line.name}"
                found, run_ids = [], []
                for ev in line.events:
                    if ev.name in names:
                        st = _stats(ev)
                        spans.append(Span(ev.name, ev.start_ns, ev.end_ns,
                                          tag, tuple(sorted(
                                              (k, str(v))
                                              for k, v in st.items()))))
                    elif _LAUNCH.match(ev.name):
                        found.append((ev.name, ev.start_ns,
                                      ev.end_ns - ev.start_ns, {}))
                    else:
                        rid = _stats(ev).get("run_id")
                        if rid is not None:
                            run_ids.append((ev.start_ns, int(rid)))
                run_ids.sort()
                starts = [t for t, _ in run_ids]
                for name, start, dur, _ in _outermost(found):
                    k = bisect.bisect_left(starts, start)
                    rid = (run_ids[k][1] if k < len(run_ids)
                           and run_ids[k][0] < start + dur else None)
                    launches.append(Launch(_LAUNCH.match(name).group(1),
                                           start, start + dur, tag, rid))
    windows = [(s.start_ns, s.end_ns) for s in spans if s.name == WINDOW]
    window = windows[0] if len(windows) == 1 else (
        min((p.start_ns for p in programs), default=0.0),
        max((p.end_ns for p in programs), default=0.0))
    return SpanTrace(programs, launches, spans, window)

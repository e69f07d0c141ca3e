"""From a profiler trace to device time, idle share and idle gaps.

``load`` reads the ``.xplane.pb`` file that ``jax.profiler`` writes and
keeps two things: the device's programs (on a TPU, the ``XLA Modules``
line of the ``/device:TPU:<n>`` plane: one event per execution of a
compiled program, named ``jit_<function>(<fingerprint>)``) and the
benchmark's own host spans (``submit``, ``run``, ``fetch`` and the
enclosing ``window``), which ``jax.profiler.TraceAnnotation`` puts on the
same clock. Everything after that is plain arithmetic on intervals, so a
test can build a ``Trace`` by hand.
"""

from __future__ import annotations

import collections
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

#: Host spans the benchmark records; ``window`` encloses the measured loop.
SPANS = ("submit", "run", "fetch")
WINDOW = "window"


@dataclasses.dataclass(frozen=True)
class Op:
    """One program execution on the device: its event name, its module
    (the name without the fingerprint), its interval."""

    name: str
    module: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Tuple[str, float, float]]     # (name, start_ns, end_ns)
    window: Tuple[float, float]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy(self) -> List[Tuple[float, float]]:
        """Union of the operations' intervals, clipped to the window."""
        lo, hi = self.window
        out: List[List[float]] = []
        for op in sorted(self.ops, key=lambda o: o.start_ns):
            s, e = max(op.start_ns, lo), min(op.end_ns, hi)
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [(s, e) for s, e in out]

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy()) / 1e9

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def device_seconds(self, module_pattern: str) -> float:
        """Seconds of the operations whose module matches the pattern
        (``re.search``), inside the window."""
        rx = re.compile(module_pattern)
        lo, hi = self.window
        return sum(max(0.0, min(op.end_ns, hi) - max(op.start_ns, lo))
                   for op in self.ops if rx.search(op.module)) / 1e9

    def top_modules(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` programs with the most device seconds in the window."""
        lo, hi = self.window
        tot: Dict[str, float] = collections.Counter()
        for op in self.ops:
            tot[op.module] += max(0.0, min(op.end_ns, hi)
                                  - max(op.start_ns, lo)) / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` longest idle gaps of the window, each labelled by the
        host span that covers most of it (``none`` where no span does)."""
        lo, hi = self.window
        edges = [lo] + [t for iv in self.busy() for t in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            cover = collections.Counter()
            for name, a, b in self.spans:
                if name in SPANS:
                    cover[name] += max(0.0, min(b, e) - max(a, s))
            label = cover.most_common(1)[0][0] if cover and \
                cover.most_common(1)[0][1] > 0 else "none"
            out.append((label, (e - s) / 1e9))
        return out


def _device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def module_name(event_name: str) -> str:
    """``jit_hash_join(1234)`` -> ``jit_hash_join``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def load(path: Path, line_name: str = "XLA Modules") -> Trace:
    """Device programs and host spans of one ``.xplane.pb`` trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: List[Op] = []
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if _device_plane(plane.name):
            for line in plane.lines:
                if line.name == line_name:
                    ops.extend(Op(ev.name, module_name(ev.name), ev.start_ns,
                                  ev.end_ns) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS or ev.name == WINDOW:
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{path}: expected one '{WINDOW}' span, found "
                         f"{len(windows)}")
    return Trace(ops, spans, windows[0])


def find(log_dir: Path) -> Path:
    """The one trace file ``jax.profiler`` wrote under ``log_dir``."""
    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"expected one trace under {log_dir}, "
                                f"found {len(files)}")
    return files[0]


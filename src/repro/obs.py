"""Spans and counters of the query path.

Three things, one module, no switch:

- ``span(name, **meta)`` marks a stretch of host work. It is a
  ``jax.profiler.TraceAnnotation``: inside a profiler session the span
  lands in the trace's host plane, on the clock of the device's events,
  with its metadata (``query=<name>`` on the per-query spans) and nested
  by thread; outside one it is an inactive TraceMe that records nothing.
  The device programs a span launches can then be attributed to it from
  the trace alone (``docs/observability.md``).
- ``count(name, n=1)`` adds to a process-wide counter; ``snapshot()``
  copies the counters, so a reader takes the difference of two.
- ``fetch(x)`` is the one way the engine reads a device value on the host
  (``wait(x)`` the one way it blocks on one without reading it): each
  opens span ``sync`` and counts ``host_syncs``.

``SPANS`` and ``COUNTERS`` list every name the engine uses; ``span`` and
``count`` refuse any other, so the lists stay the whole vocabulary.
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Dict

import jax
from jax.profiler import TraceAnnotation

#: Every span name. ``service.*`` spans belong to ``QueryService``, ``op.*``
#: spans to the operators a query runs; ``sync`` is a host read or wait.
SPANS = (
    "service.submit",   # QueryService.submit: parse, optimise, quote
    "service.batch",    # QueryService._execute_batch, CSE discovery inside
    "service.shared",   # one shared (CSE) producer of a batch
    "service.query",    # one query's own execution
    "op.filter",        # static predicates, runtime filters, semi-join
    "op.select",        # join method selection, key skew, audits
    "op.exchange",      # broadcast, shuffle, salted and hypercube shuffles
    "op.local_join",    # per-partition join and the gather of matches
    "op.aggregate",     # group-by segments and per-column aggregates
    "op.compact",       # compact_partitions
    "sync",             # obs.fetch / obs.wait
)

#: The kernels of ``repro.kernels.ops``, each counted as ``kernel.<name>``
#: per call from Python (once per trace under jit).
KERNELS = ("tiled_probe", "tiled_probe3", "partition_hist", "bloom_build",
           "bloom_probe", "key_range", "bitonic_sort_tile")

#: Every counter name.
COUNTERS = ("host_syncs",       # obs.fetch and obs.wait calls
            "exchange_bytes",   # network bytes of every exchange
            ) + tuple(f"kernel.{k}" for k in KERNELS)

_SPANS = frozenset(SPANS)
_COUNTERS = frozenset(COUNTERS)
_lock = threading.Lock()
_counters: Dict[str, float] = collections.Counter()


def span(name: str, **meta: Any) -> TraceAnnotation:
    """Context manager marking host work as ``name`` in a profiler trace."""
    if name not in _SPANS:
        raise ValueError(f"unknown span {name!r}; add it to obs.SPANS")
    return TraceAnnotation(name, **meta)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the process-wide counter ``name``."""
    if name not in _COUNTERS:
        raise ValueError(f"unknown counter {name!r}; add it to obs.COUNTERS")
    with _lock:
        _counters[name] += n


def snapshot() -> Dict[str, float]:
    """A copy of every counter (absent ones read 0 in a difference)."""
    with _lock:
        return dict(_counters)


def fetch(x: Any) -> Any:
    """``jax.device_get(x)``, spanned as ``sync`` and counted as a host
    sync: every device-to-host read of the engine goes through here."""
    with span("sync"):
        count("host_syncs")
        return jax.device_get(x)


def wait(x: Any) -> Any:
    """``jax.block_until_ready(x)``, spanned and counted like ``fetch``:
    a sync that copies nothing."""
    with span("sync"):
        count("host_syncs")
        return jax.block_until_ready(x)

"""Thread-safe cumulative counters for the engine's per-process stats.

``backend.stats`` and ``schedule_cache.stats`` started life as plain
dicts mutated with ``stats[k] += 1``.  That read-modify-write is not
atomic under threads: the analysis service (``serve/analysis.py``) runs
concurrent batches, and two replay chunks bumping ``certified_columns``
at once could lose an increment — harmless for correctness of results,
but the counters are exactly what the benchmarks and the fault-injection
suite assert on, so they must not drift under concurrency.

``Stats`` keeps the dict-shaped read API every existing caller uses
(``stats["chunks"]``, ``dict(stats)``, ``**stats``, iteration) while
funnelling every mutation through one lock:

* ``stats.add(key, n=1)``  — atomic accumulate (the only mutation the
  engine itself performs);
* ``stats[key] = v``       — locked assignment (tests zeroing counters);
* ``stats.reset()``        — zero every counter atomically.

Unknown keys raise ``KeyError`` on ``add`` — a typo'd counter name is a
bug worth surfacing, not a silently growing new key.

``span(name, **counts)`` names one stage of a query on the profiler's
clock: it opens ``jax.profiler.TraceAnnotation("edan." + name,
**counts)``, so the stage and its integer counts (bytes moved, levels
replayed, ...) appear as a host event with those stats in any trace
captured around the query (``jax.profiler.trace``), beside the device's
own events.  With no trace active the profiler drops the event; entering
and leaving costs about a microsecond.  Spans sit at stage boundaries
only, never per level, vertex or edge, and their counts are attribute
reads (``len``, ``.nbytes``, ``.shape``).  The spans the engine opens are
listed in ``docs/ARCHITECTURE.md`` ("Profiling a query").
"""
from __future__ import annotations

import threading

#: Prefix of every span name the engine opens.
SPAN_PREFIX = "edan."

_annotation = None              # jax.profiler.TraceAnnotation, once imported


def span(name: str, **counts):
    """Context manager naming one query stage in the profiler's trace as
    ``edan.<name>`` with ``counts`` as its stats; returns the annotation,
    whose ``set_metadata(**counts)`` adds stats known only inside."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(SPAN_PREFIX + name, **counts)


class Stats:
    """A fixed-key counter map whose mutations are serialized by a lock."""

    __slots__ = ("_lock", "_c")

    def __init__(self, **counters: int):
        self._lock = threading.Lock()
        self._c = dict(counters)

    # ------------------------------------------------------------ mutation
    def add(self, key: str, n: int = 1) -> None:
        """Atomically accumulate ``n`` into an existing counter."""
        with self._lock:
            self._c[key] += n

    def __setitem__(self, key: str, value) -> None:
        if key not in self._c:
            raise KeyError(key)
        with self._lock:
            self._c[key] = value

    def reset(self) -> None:
        """Zero every counter (tests and benchmarks)."""
        with self._lock:
            for k in self._c:
                self._c[k] = 0

    # ---------------------------------------------------------------- read
    def __getitem__(self, key: str):
        return self._c[key]

    def __iter__(self):
        return iter(self._c)

    def __len__(self) -> int:
        return len(self._c)

    def __contains__(self, key: str) -> bool:
        return key in self._c

    def keys(self):
        return self._c.keys()

    def values(self):
        return self._c.values()

    def items(self):
        return self._c.items()

    def snapshot(self) -> dict:
        """A consistent point-in-time copy (taken under the lock)."""
        with self._lock:
            return dict(self._c)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"Stats({self._c!r})"

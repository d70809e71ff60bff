"""The program's own spans in a traced run.

The engine names each stage of a query with a profiler annotation
(``repro.core.counters.span``): a host event ``edan.<stage>`` whose stats
carry its counts (bytes moved, levels, padded slots, ...).  ``load`` reads
the newest ``*.xplane.pb`` under a log directory with
``jax.profiler.ProfileData`` and keeps, as ``(plane, line, name, start_ns,
dur_ns, stats)`` tuples, the events on device planes, the
``bench.window`` span and every ``edan.*`` host event with its stats.
``reduce`` turns them into:

* ``spans``             -- per span name (prefix dropped), inside the
                           window: ``total_s``, ``self_s`` (less the
                           spans nested in it on its thread), ``count``
                           and ``stats``, each integer stat summed;
* ``program_idle_gaps`` -- device-idle seconds inside the window (first
                           device) by the innermost ``edan.*`` span open
                           over each part of each gap, ``host`` where
                           none is open.

A trace with no window or no device plane reduces to ``None``.  The
device side is read as ``tracereduce`` reads it, so the gaps here add up
to its ``window_s - busy_s``.

``METRICS`` holds three per-layer numbers computed from a reduction and
the traced queries' latencies: ``dispatch_host_pct``, ``transfer_mb`` and
``pad_efficiency_pct``.  Each is ``None`` on a program without the spans
it reads.  The harness (``run.py``) does not call this module yet: it
deletes the profile before its metric readers run, so wiring these in
takes a ``Run.program`` set from ``reduce(load(log_dir))`` there.
"""
from __future__ import annotations

from typing import Optional

import tracereduce as tr

PREFIX = "edan."


def load(log_dir: str) -> list:
    from jax.profiler import ProfileData
    path = tr.newest_profile(log_dir)
    if path is None:
        return []
    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = tr.is_device_plane(plane.name)
        for line in plane.lines:
            for e in line.events:
                if dev or e.name == tr.WINDOW or e.name.startswith(PREFIX):
                    stats = ({k: v for k, v in e.stats}
                             if e.name.startswith(PREFIX) else {})
                    out.append((plane.name, line.name, e.name,
                                int(e.start_ns), int(e.duration_ns), stats))
    return out


def reduce(events: list) -> Optional[dict]:
    window = [(s, s + d) for p, l, n, s, d, _ in events if n == tr.WINDOW]
    planes = sorted({p for p, *_ in events if tr.is_device_plane(p)})
    if not window or not planes:
        return None
    w0, w1 = window[0]
    spans: dict = {}
    lines: dict = {}
    for p, l, n, s, d, stats in events:
        if not n.startswith(PREFIX) or tr.is_device_plane(p):
            continue
        cs, ce = max(s, w0), min(s + d, w1)
        if ce <= cs:
            continue
        name = n[len(PREFIX):]
        lines.setdefault((p, l), []).append((cs, ce, name))
        rec = spans.setdefault(name, {"total_s": 0.0, "self_s": 0.0,
                                      "count": 0, "stats": {}})
        rec["total_s"] += (ce - cs) / 1e9
        rec["count"] += 1
        for k, v in stats.items():
            if isinstance(v, int):
                rec["stats"][k] = rec["stats"].get(k, 0) + v
    for segs in lines.values():
        for name, ns in tr._self_ns(segs, w0, w1).items():
            spans[name]["self_s"] += ns / 1e9
    busy = _busy(events, planes[0], w0, w1)
    # ``_attribute`` keys each part of a gap by the span's name less the
    # benchmark's prefix; the program's spans take it for the call
    named = [(s, e, tr.SPAN_PREFIX + n)
             for segs in lines.values() for s, e, n in segs]
    return {"spans": spans,
            "program_idle_gaps": tr._attribute(tr._gaps(busy, w0, w1),
                                               named)}


def _busy(events: list, plane: str, w0: int, w1: int) -> list:
    """The device's busy intervals inside the window, from its op line
    (its program line where it has none), as ``tracereduce`` takes
    them."""
    ops = [(s, s + d) for p, l, n, s, d, _ in events
           if p == plane and l == tr.OPS_LINE]
    if not ops:
        ops = [(s, s + d) for p, l, n, s, d, _ in events
               if p == plane and l == tr.PROGRAMS_LINE]
    iv = [(max(s, w0), min(e, w1)) for s, e in ops]
    return tr.union_ns([(s, e) for s, e in iv if e > s])


def self_seconds(program: Optional[dict], names) -> Optional[float]:
    """Summed self seconds of the spans ``names``; ``None`` when none of
    them ran."""
    if program is None:
        return None
    hit = [program["spans"][n]["self_s"] for n in names
           if n in program["spans"]]
    return sum(hit) if hit else None


def stat(program: Optional[dict], name: str, key: str) -> Optional[int]:
    """Summed stat ``key`` of the span ``name``; ``None`` when the span
    never ran or never carried the stat."""
    if program is None or name not in program["spans"]:
        return None
    return program["spans"][name]["stats"].get(key)


# ------------------------------------------------ per-layer numbers

#: The backend dispatch's own host stages, below ``edan.replay``.
DISPATCH_STAGES = ["replay.prescreen", "replay.cast", "replay.pad",
                   "replay.certify", "replay.merge", "replay.demote"]


def dispatch_host_pct(program: Optional[dict],
                      latencies: list) -> Optional[float]:
    """Share of the traced queries' wall time the backend dispatch spends
    on host work of its own: the self seconds of ``DISPATCH_STAGES`` over
    the summed query latencies."""
    t = self_seconds(program, DISPATCH_STAGES)
    total = sum(latencies)
    if t is None or not total:
        return None
    return 100.0 * t / total


def transfer_mb(program: Optional[dict],
                latencies: list) -> Optional[float]:
    """Megabytes (1e6 bytes) moved between host and device per traced
    query: the ``bytes`` of ``edan.replay.upload`` and ``.download``."""
    up = stat(program, "replay.upload", "bytes")
    down = stat(program, "replay.download", "bytes")
    if up is None or down is None or not latencies:
        return None
    return (up + down) / 1e6 / len(latencies)


def pad_efficiency_pct(program: Optional[dict],
                       latencies: list) -> Optional[float]:
    """Share of the level loop's padded gather rectangle that holds real
    predecessor edges: ``edges`` over ``slots`` ((levels - 1) x rows x
    width) of the ``edan.replay.run`` spans."""
    edges = stat(program, "replay.run", "edges")
    slots = stat(program, "replay.run", "slots")
    if edges is None or not slots:
        return None
    return 100.0 * edges / slots


METRICS = {"dispatch_host_pct": dispatch_host_pct,
           "transfer_mb": transfer_mb,
           "pad_efficiency_pct": pad_efficiency_pct}

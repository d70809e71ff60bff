"""From a profiler trace to the numbers a traced run reports.

``load`` reads the newest ``*.xplane.pb`` under a log directory with
``jax.profiler.ProfileData`` and keeps two kinds of event, as plain
``(plane, line, name, start_ns, dur_ns)`` tuples: every event on a device
plane, and the benchmark's own host spans (names starting ``bench.``).
``reduce`` turns those events into:

* ``window_s``   -- the length of the ``bench.window`` span;
* ``busy_s``     -- the union of the device-operation intervals inside the
                    window, averaged over the device planes;
* ``program_s``  -- device time of each program, summed per name;
* ``op_s``       -- self time of each device operation (its time less
                    that of the operations nested in it, such as a loop's
                    body), summed per short name (``%fusion.20``);
* ``idle_gaps``  -- device-idle time inside the window (first device), by
                    the innermost host span open over each part of it.

A trace with no device plane reduces to ``None``: nothing to read.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Optional

#: Lines of a TPU device plane: one event per operation, one per program.
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"


def newest_profile(log_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name


def load(log_dir: str) -> list:
    from jax.profiler import ProfileData
    path = newest_profile(log_dir)
    if path is None:
        return []
    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = is_device_plane(plane.name)
        for line in plane.lines:
            for e in line.events:
                if dev or e.name.startswith(SPAN_PREFIX):
                    out.append((plane.name, line.name, e.name,
                                int(e.start_ns), int(e.duration_ns)))
    return out


def union_ns(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(s: int, e: int, w0: int, w1: int):
    return max(s, w0), min(e, w1)


def reduce(events: list) -> Optional[dict]:
    window = [(s, s + d) for p, l, n, s, d in events if n == WINDOW]
    planes = sorted({p for p, *_ in events if is_device_plane(p)})
    if not window or not planes:
        return None
    w0, w1 = window[0]
    busy_by_plane = {}
    program_s: dict = {}
    op_s: dict = {}
    for p in planes:
        ops = [(s, s + d, n) for q, l, n, s, d in events
               if q == p and l == OPS_LINE]
        if not ops:                     # no op line: programs stand in
            ops = [(s, s + d, n) for q, l, n, s, d in events
                   if q == p and l == PROGRAMS_LINE]
        iv = [_clip(s, e, w0, w1) for s, e, _ in ops]
        busy_by_plane[p] = union_ns([(s, e) for s, e in iv if e > s])
        for n, t in _self_ns(ops, w0, w1).items():
            op_s[n] = op_s.get(n, 0.0) + t / 1e9
        for q, l, n, s, d in events:
            if q == p and l == PROGRAMS_LINE:
                cs, ce = _clip(s, s + d, w0, w1)
                if ce > cs:
                    program_s[n] = program_s.get(n, 0.0) + (ce - cs) / 1e9
    busy = [sum(e - s for s, e in iv) for iv in busy_by_plane.values()]
    spans = [(s, s + d, n) for p, l, n, s, d in events
             if n.startswith(SPAN_PREFIX) and n != WINDOW
             and not is_device_plane(p)]
    gaps = _gaps(busy_by_plane[planes[0]], w0, w1)
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": sum(busy) / len(busy) / 1e9,
            "devices": len(planes),
            "program_s": program_s, "op_s": op_s,
            "idle_gaps": _attribute(gaps, spans)}


def short_name(name: str) -> str:
    """``%fusion.20`` of ``%fusion.20 = f32[...] fusion(...)``."""
    return name.split(" = ", 1)[0]


def _self_ns(ops: list, w0: int, w1: int) -> dict:
    """Self nanoseconds inside the window per short op name: each op's
    clipped duration less the clipped durations of the ops it encloses
    on the same line."""
    out: dict = {}
    stack: list = []                # [end, name, clipped duration, child]
    def close(top):
        out[top[1]] = out.get(top[1], 0) + top[2] - top[3]
    for s, e, n in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        cs, ce = _clip(s, e, w0, w1)
        d = max(ce - cs, 0)
        if stack:
            stack[-1][3] += d
        stack.append([e, short_name(n), d, 0])
    while stack:
        close(stack.pop())
    return {n: t for n, t in out.items() if t > 0}


def _gaps(busy: list, w0: int, w1: int) -> list:
    out, t = [], w0
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if w1 > t:
        out.append((t, w1))
    return out


def _innermost(spans: list) -> list:
    """The host timeline as ``(start, end, name)`` segments, each named
    by the innermost span open over it (spans of one thread nest)."""
    segs, stack = [], []
    t = None

    def emit(upto):
        if stack and t is not None and upto > t:
            segs.append((t, upto, stack[-1][1]))
    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            t = stack.pop()[0]
        emit(s)
        t = s
        stack.append((e, n))
    while stack:
        emit(stack[-1][0])
        t = stack.pop()[0]
    return segs


def _attribute(gaps: list, spans: list) -> dict:
    """Idle seconds by the innermost host span over each part of each
    gap (``host`` where no span is open)."""
    out: dict = {}
    segs = _innermost(spans)
    i = 0
    for g0, g1 in gaps:
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        t, j = g0, i
        while j < len(segs) and segs[j][0] < g1:
            s0, s1, n = segs[j]
            if s0 > t:
                out["host"] = out.get("host", 0.0) + (min(s0, g1) - t)
            lo, hi = max(s0, g0), min(s1, g1)
            if hi > lo:
                key = n[len(SPAN_PREFIX):]
                out[key] = out.get(key, 0.0) + (hi - lo)
            t = max(t, hi)
            j += 1
        if g1 > t:
            out["host"] = out.get("host", 0.0) + (g1 - t)
    return {n: v / 1e9 for n, v in out.items()}


def program_seconds(summary: dict, pattern: str) -> Optional[float]:
    """Device seconds of the programs whose name matches ``pattern``;
    ``None`` when none ran."""
    rx = re.compile(pattern)
    hits = [s for n, s in summary["program_s"].items() if rx.search(n)]
    return sum(hits) if hits else None


def top(d: dict, k: int = 10) -> list:
    return [[n, s] for n, s in sorted(d.items(), key=lambda x: -x[1])[:k]]

"""Plain reference of what an EDAN analysis query answers.

Written from the paper's machine model (§3.3.1, §4) and its Eq 1-4, and
independent of the code under test: it imports nothing of ``repro`` and
reads only the trace itself (vertex count, memory flags, edge list).

* ``simulate``   -- the §4 greedy event loop: memory vertices take one of
  ``m`` issue slots for ``alpha`` cycles, FIFO by ready time; other
  vertices cost ``unit`` on unbounded ALUs, or on ``cs`` ALU slots.
  Same-instant ties resolve by vertex id; successors are released in
  (src, dst) edge order.
* ``report``     -- W, D, C, lambda, Lambda, the Eq 1-2 bounds, the span
  T_inf at each alpha and, optionally, the simulated grid.

``rnd`` rounds the result of every addition; it is the identity for the
float64 reference and a narrower rounding for the precision control.
"""
from __future__ import annotations

import heapq
import struct
from typing import Callable, Sequence

import numpy as np


class Trace:
    """One trace as plain arrays: ``n`` vertices, ``is_mem`` flags and
    the edge list, with the successor lists the event loop walks."""

    def __init__(self, n: int, is_mem, src, dst):
        self.n = int(n)
        self.is_mem = np.asarray(is_mem, dtype=bool).copy()
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        if len(src) and not bool((src < dst).all()):
            raise ValueError("edges must run from lower to higher vertex id")
        order = np.lexsort((dst, src))
        self.src, self.dst = src[order], dst[order]
        self.indeg = np.bincount(self.dst, minlength=self.n).tolist()
        ptr = np.zeros(self.n + 1, dtype=np.int64)
        np.cumsum(np.bincount(self.src, minlength=self.n), out=ptr[1:])
        self.succ_ptr = ptr.tolist()
        self.succ = self.dst.tolist()
        self.mem = self.is_mem.tolist()


def _identity(x: float) -> float:
    return x


def round_f32(x: float) -> float:
    return struct.unpack("f", struct.pack("f", x))[0]


def round_bf16(x: float) -> float:
    """Round to the nearest bfloat16 (ties to even), via float32."""
    (u,) = struct.unpack("I", struct.pack("f", x))
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return struct.unpack("f", struct.pack("I", u))[0]


ROUNDINGS = {"float64": _identity, "float32": round_f32,
             "bfloat16": round_bf16}


def simulate(tr: Trace, m: int, alpha: float, unit: float = 1.0,
             cs: int = 0, rnd: Callable[[float], float] = _identity
             ) -> float:
    """Makespan of the §4 machine model for one (alpha, m, cs) point."""
    if tr.n == 0:
        return 0.0
    alpha, unit = rnd(float(alpha)), rnd(float(unit))
    indeg = list(tr.indeg)
    mem, succ, ptr = tr.mem, tr.succ, tr.succ_ptr
    events: list = []              # (finish, vertex)
    waiting: list = []             # (ready, vertex): memory issue queue
    slots = [0.0] * m              # next free time of each memory slot
    alus = [0.0] * cs if cs else None
    push, pop, replace = heapq.heappush, heapq.heappop, heapq.heapreplace

    def ready(v: int, t: float) -> None:
        if mem[v]:
            push(waiting, (t, v))
        elif alus is not None:
            f = rnd(max(t, alus[0]) + unit)
            replace(alus, f)
            push(events, (f, v))
        else:
            push(events, (rnd(t + unit), v))

    def issue() -> None:
        while waiting:
            t, v = pop(waiting)
            f = rnd(max(t, slots[0]) + alpha)
            replace(slots, f)
            push(events, (f, v))

    for v in range(tr.n):
        if not indeg[v]:
            ready(v, 0.0)
    issue()
    makespan = 0.0
    while events:
        t, v = pop(events)
        if t > makespan:
            makespan = t
        for i in range(ptr[v], ptr[v + 1]):
            d = succ[i]
            indeg[d] -= 1
            if not indeg[d]:
                ready(d, t)
        issue()
    return makespan


def _longest(tr: Trace, cost: Sequence[float],
             rnd: Callable[[float], float] = _identity) -> list:
    """F[v] = cost[v] + max(0, F[u] for u in preds(v)), in vertex-id
    order (a topological order: every edge runs to a higher id)."""
    F = [0.0] * tr.n
    preds: list = [[] for _ in range(tr.n)]
    for u, v in zip(tr.src.tolist(), tr.dst.tolist()):
        preds[v].append(u)
    for v in range(tr.n):
        b = 0.0
        for u in preds[v]:
            if F[u] > b:
                b = F[u]
        F[v] = rnd(cost[v] + b)
    return F


def report(tr: Trace, alphas: Sequence[float], ms: Sequence[int],
           css: Sequence[int] = (0,), unit: float = 1.0,
           simulate_points: bool = True,
           rnd: Callable[[float], float] = _identity) -> dict:
    """The §3.3 report of one trace over the alpha x m grid (Eq 1-4),
    with the §4 simulated grid when ``simulate_points``.

    W counts memory vertices, D is the memory depth (the most memory
    vertices on one path), C = (n - W) * unit.  Per m: lambda = (W-D)/m
    + D (Eq 3); per (alpha, m): t_lower = max(D, W/m)*alpha + C,
    t_upper = lambda*alpha + C (Eq 1-2), Lambda = lambda / (lambda*alpha
    + C) (Eq 4, 0 where the denominator is not positive); per alpha the
    span T_inf with alpha on memory vertices and ``unit`` elsewhere."""
    mem = tr.mem
    W = int(sum(mem))
    depth = _longest(tr, [1.0 if x else 0.0 for x in mem])
    D = int(max((d for d, x in zip(depth, mem) if x), default=0))
    C = rnd(float(tr.n - W) * unit)
    lam = [rnd(rnd((W - D) / m) + D) for m in ms]
    t_inf, t_lower, t_upper, Lam = [], [], [], []
    for a in alphas:
        a = float(a)
        F = _longest(tr, [a if x else unit for x in mem], rnd)
        t_inf.append(max(F, default=0.0))
        lo, hi, rel = [], [], []
        for m, lm in zip(ms, lam):
            lo.append(rnd(rnd(max(float(D), W / m) * a) + C))
            up = rnd(lm * a)
            hi.append(rnd(up + C))
            den = rnd(up + C)
            rel.append(rnd(lm / den) if den > 0 else 0.0)
        t_lower.append(lo)
        t_upper.append(hi)
        Lam.append(rel)
    out = dict(W=W, D=D, C=C, lam=np.array(lam), Lam=np.array(Lam),
               t_inf=np.array(t_inf), t_lower=np.array(t_lower),
               t_upper=np.array(t_upper))
    if simulate_points:
        out["simulated"] = np.array(
            [[[simulate(tr, m, a, unit, cs, rnd) for cs in css]
              for m in ms] for a in alphas])
    return out

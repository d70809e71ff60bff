"""What decides ``correct``: the window's answers against the reference.

After the window a sample of the answers is drawn from the seed: single
makespans of grid queries (``points``), or whole reports (``reports``),
the same share of them from each trace of the cell and always one of the
largest.  Each is recomputed by ``reference.py`` in float64 and compared
bit for bit.  The numbers compared, each with its limit:

* ``mismatched_values`` -- sampled values not bit-identical to the
  reference (limit 0: the configuration states exact results);
* ``missing_answers``   -- window queries that raised instead of
  answering (limit 0);
* ``traces_unlike_config`` -- traces whose counts and digest differ from
  the figures the configuration states (limit 0): the reference reads
  the trace the program built, and this holds that trace to the one the
  original per-element tracers give.

``control`` puts the reference computed in a narrower precision in the
program's place, on the same sample, and counts the same numbers.
"""
from __future__ import annotations

import time

import reference
import traffic
import workload
import yardstick

LIMITS = {"mismatched_values": 0, "missing_answers": 0,
          "traces_unlike_config": 0}


def _draw(items: list, k: int, largest, g) -> list:
    """``k`` distinct indices into ``items``: ``k // traces`` of each
    trace's answers (all of them where it has fewer), the rest at random,
    and one of the largest trace where none was drawn."""
    by: dict = {}
    for i, it in enumerate(items):
        by.setdefault(it[1], []).append(i)
    share = k // len(by)
    idx = []
    for m in sorted(by):
        pool = by[m]
        idx += [int(i) for i in g.choice(pool, size=min(share, len(pool)),
                                         replace=False)]
    rest = sorted(set(range(len(items))) - set(idx))
    idx += [int(i) for i in g.choice(rest, size=k - len(idx), replace=False)]
    if largest in by and not any(items[i][1] == largest for i in idx):
        idx[-1] = by[largest][int(g.integers(len(by[largest])))]
    return sorted(idx)


def sample(answers: list, sizes: dict, spec: dict, seed: int) -> list:
    """Draw the answers to compare: ``("point", member, alpha, m, cs,
    got)`` and ``("report", member, query, got)`` items.  ``answers``
    holds ``(query, result, points)`` of every query that answered."""
    g = traffic.rng(seed, "check")
    largest = max(sizes, key=lambda m: sizes[m])
    pts = [("point",) + p for _, _, ps in answers for p in ps]
    reps = [("report", q["member"], q, res) for q, res, ps in answers
            if q["entry"] == "grid_report"]
    out = []
    for items, k in ((pts, spec.get("points", 0)),
                     (reps, spec.get("reports", 0))):
        k = min(int(k), len(items))
        if k:
            out += [items[i] for i in _draw(items, k, largest, g)]
    return out


def unlike_config(inputs: dict, figures: dict) -> list:
    """The traces whose figures differ from those the configuration
    states (a trace it states nothing of differs)."""
    return [m for m in sorted(inputs)
            if yardstick.trace_figures(*inputs[m]) != figures.get(m)]


def reference_answer(item, traces: dict, unit: float, rnd=None):
    rnd = rnd or reference.ROUNDINGS["float64"]
    tr = traces[item[1]]
    if item[0] == "point":
        _, _, alpha, m, cs, _ = item
        return reference.simulate(tr, m, alpha, unit, cs, rnd)
    q = item[2]
    return reference.report(tr, q["alphas"], q["ms"], q["compute_slots"],
                            unit, q["simulate_points"], rnd)


def _traces(items, inputs) -> dict:
    return {m: workload.reference_trace(inputs[m])
            for m in sorted({it[1] for it in items})}


def compare(items, got_of, traces, unit) -> tuple:
    """(values compared, values not bit-identical to the float64
    reference), where ``got_of(item)`` is the answer under test."""
    compared = mismatched = 0
    for it in items:
        want = reference_answer(it, traces, unit)
        got = got_of(it)
        if it[0] == "point":
            compared += 1
            mismatched += int(got is None or not got == want)
        else:
            c, mm = workload.compare_report(got, want)
            compared, mismatched = compared + c, mismatched + mm
    return compared, mismatched


def _numbers(mismatched: int, missing: int, unlike: int) -> dict:
    vals = {"mismatched_values": mismatched, "missing_answers": missing,
            "traces_unlike_config": unlike}
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in vals.items()}


def verdict(numbers: dict, compared: int) -> bool:
    return compared > 0 and all(v["value"] <= v["limit"]
                                for v in numbers.values())


def check(answers: list, inputs: dict, spec: dict, seed: int,
          unit: float, figures: dict) -> dict:
    """Compare a seeded sample of the window's answers with the
    reference.  ``answers`` holds ``(query, result, points)`` of every
    window query; a result of ``None`` is a query that raised.
    ``figures`` are the configuration's figures of each trace."""
    t0 = time.perf_counter()
    ok = [a for a in answers if a[1] is not None]
    sizes = {m: v[0] for m, v in inputs.items()}
    items = sample(ok, sizes, spec, seed)
    traces = _traces(items, inputs)
    compared, mismatched = compare(
        items, lambda it: it[-1] if it[0] == "point" else it[3],
        traces, unit)
    numbers = _numbers(mismatched, len(answers) - len(ok),
                       len(unlike_config(inputs, figures)))
    return {"correct": verdict(numbers, compared), "compared": compared,
            "numbers": numbers, "seconds": time.perf_counter() - t0}


def control(answers: list, inputs: dict, spec: dict, seed: int,
            unit: float, precision: str) -> dict:
    """The same check with the reference in ``precision`` in the
    program's place: the reading the limits are set against."""
    rnd = reference.ROUNDINGS[precision]
    sizes = {m: v[0] for m, v in inputs.items()}
    items = sample(answers, sizes, spec, seed)
    traces = _traces(items, inputs)
    compared, mismatched = compare(
        items, lambda it: reference_answer(it, traces, unit, rnd),
        traces, unit)
    numbers = _numbers(mismatched, 0, 0)
    return {"correct": verdict(numbers, compared), "compared": compared,
            "numbers": numbers}

"""What a cell runs: traces built from a configuration file, the library
entries its queries call, and the answers each query gives.

Only this module, the trace builders in ``apps/`` and ``spans`` touch
the program (``repro``); the check compares the answers with
``reference``, which touches nothing of it.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

import reference

HERE = os.path.dirname(os.path.abspath(__file__))


def build_trace(app: str, params: dict, home: str = HERE):
    """One trace of a configuration: ``<home>/apps/<app>.py`` builds it
    from the parameters the configuration file gives."""
    path = os.path.join(home, "apps", app + ".py")
    if not os.path.exists(path):
        raise ValueError(f"no trace builder {path} for app {app!r}")
    spec = importlib.util.spec_from_file_location("bench_app_" + app, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.build(params)


class Workload:
    """The traces of one cell, built once in set-up."""

    def __init__(self, config: dict, members, union: bool = False,
                 home: str = HERE):
        from repro.core import EDagSuite
        self.unit = float(config["unit"])
        traces = config["traces"]
        missing = [m for m in members if m not in traces]
        if missing:
            raise ValueError(f"traffic names traces {missing} that the "
                             f"configuration {config['name']!r} lacks")
        self.names = list(members)
        self.graphs = {m: build_trace(config["app"], traces[m], home)
                       for m in self.names}
        for g in self.graphs.values():
            g._finalize()
        self.suite = (EDagSuite([self.graphs[m] for m in self.names],
                                names=self.names) if union else None)
        # the trace as the query receives it, for the reference after
        # the window: flags and edges only, copied before any query runs;
        # the check holds it to the figures the configuration states
        self.inputs = {m: (g.n_vertices, np.array(g.is_mem),
                           np.array(g.src), np.array(g.dst))
                       for m, g in self.graphs.items()}

    def run(self, q: dict):
        """Answer one query through the library entry it names.  Every
        entry returns host arrays copied from the device, so the call
        ends after the device has finished."""
        from repro import core
        e, kw = q["entry"], dict(ms=q["ms"], compute_slots=q["compute_slots"])
        if e == "suite_sweep_grid":
            if q["member"] is not None:
                raise ValueError("suite_sweep_grid takes the union of the "
                                 "members: use pick 'union'")
            return core.suite_sweep_grid(self.suite, q["alphas"], unit=self.unit,
                                         **kw)
        g = self.graphs[q["member"]]
        if e == "sweep_grid":
            return core.sweep_grid(g, q["alphas"], unit=self.unit, **kw)
        if e == "grid_report":
            from repro.core.cost import CostModelParams
            return core.grid_report(
                g, q["alphas"], params=CostModelParams(unit=self.unit),
                simulate_points=q["simulate_points"], **kw)
        raise ValueError(f"unknown entry {e!r}")


# ------------------------------------------------------------- answers

def points(q: dict, result, names) -> list:
    """The single makespans a grid query answered, as ``(member, alpha,
    m, cs, value)``; a report query answers as a whole (empty list).
    Without a result (the control) every value is ``None``."""
    if q["entry"] == "grid_report":
        return []
    members = names if q["member"] is None else [q["member"]]
    shape = (len(members), len(q["alphas"]), len(q["ms"]),
             len(q["compute_slots"]))
    res = (np.full(shape, None, dtype=object) if result is None
           else np.asarray(result).reshape(shape))
    out = []
    for k, name in enumerate(members):
        for i, a in enumerate(q["alphas"]):
            for j, m in enumerate(q["ms"]):
                for l, cs in enumerate(q["compute_slots"]):
                    v = res[k, i, j, l]
                    out.append((name, float(a), m, cs,
                                None if v is None else float(v)))
    return out


def reference_trace(inputs) -> reference.Trace:
    n, is_mem, src, dst = inputs
    return reference.Trace(n, is_mem, src, dst)


def compare_report(got: dict, want: dict) -> tuple:
    """(values compared, values not bit-identical) over the reference
    report's keys."""
    compared = mismatched = 0
    for key, w in want.items():
        w = np.asarray(w, dtype=np.float64)
        g = np.asarray(got.get(key, np.full(w.shape, np.nan)),
                       dtype=np.float64)
        compared += w.size
        if g.shape != w.shape:
            mismatched += w.size
        else:
            mismatched += int(np.count_nonzero(
                ~((g == w) & (np.signbit(g) == np.signbit(w)))))
    return compared, mismatched

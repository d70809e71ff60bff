#!/usr/bin/env python3
"""Smoke run of the device replay on one TPU, through the normal entry points.

    python3 chip_smoke.py

Runs in one process (a chip belongs to one process at a time), phase by
phase:

* device  -- jax must see a TPU and the engine must pick the jax backend
             on its own.  Without a TPU the script fails; it never falls
             back to the CPU.
* paper   -- ``suite_sweep_grid`` over the paper's 15 PolyBench kernels
             at ``POLYBENCH_N``: the 11-point alpha sweep, m in {2, 4, 8},
             compute slots in {0, ``SIM_COMPUTE_SLOTS``}.
* hpcg    -- ``sweep_grid`` over the HPCG CG trace (n=8, 3 iterations,
             about 104k vertices) at alphas 50/150/300, m=4.
* service -- ``AnalysisService.process`` answers two co-batched PolyBench
             kernel requests, the HPCG ``cg`` request and one traced
             model step (``qwen3-0.6b`` decode).

Every device result must be bit-identical to the float64 numpy reference
(``backend="numpy"``), and every replay chunk must have run on the device
(``backend.stats``: ``jax_chunks == chunks``, no numpy chunk, no demoted
column); service results must be ``ok`` with no demotion.  Each phase
prints one JSON line with its wall seconds, its first-call seconds, the
seconds XLA spent compiling, ``backend.stats``, the device kind and the
device's peak memory.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
any failure exits non-zero without it.

The run keeps its schedule cache in a private, empty directory, so every
run records its schedules cold.  The phases are functions so that the
tests can run them at tiny sizes on the CPU.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np                                          # noqa: E402

from repro.apps import hpcg, polybench                      # noqa: E402
from repro.configs.paper_suite import (ANALYSIS, POLYBENCH_N,  # noqa: E402
                                       SIM_COMPUTE_SLOTS)
from repro.core import (EDagSuite, backend, suite_sweep_grid,  # noqa: E402
                        sweep_grid)
from repro.serve import AnalysisRequest, AnalysisService    # noqa: E402


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or non-device result."""


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class _CompileClock:
    """Seconds XLA spends compiling, from jax's own monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, duration: float, **_kw) -> None:
        if name.endswith("/backend_compile_duration"):
            self.seconds += duration


_COMPILE = None


def _compile_seconds() -> float:
    global _COMPILE
    if _COMPILE is None:
        _COMPILE = _CompileClock()
    return _COMPILE.seconds


def check_device() -> dict:
    """The device this run measures; raises unless it is a TPU and the
    engine selects the jax backend for it."""
    import jax
    devs = jax.devices()
    d = devs[0]
    _require(d.platform == "tpu",
             f"no TPU: jax's first device is {d.platform!r} "
             f"({d.device_kind}); this smoke run needs a TPU")
    _require(backend.select_backend() == "jax",
             f"the engine selected the {backend.select_backend()!r} "
             "backend on a TPU host; expected 'jax'")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def device_memory() -> dict:
    """Device kind and peak bytes in use so far, where the device reports
    them (the CPU does not)."""
    import jax
    d = jax.devices()[0]
    ms = d.memory_stats() or {}
    return {"device_kind": d.device_kind,
            "peak_bytes_in_use": ms.get("peak_bytes_in_use")}


def _check_stats(phase: str, st: dict) -> None:
    _require(st["chunks"] > 0, f"{phase}: no replay chunk was dispatched")
    _require(st["jax_chunks"] == st["chunks"] and st["numpy_chunks"] == 0
             and st["demoted_columns"] == 0,
             f"{phase}: replay left the device: {st}")


def _timed_grid(phase: str, run, ref: np.ndarray) -> dict:
    """Run a device grid twice (the first call compiles and records
    cold), check both bit-identical to ``ref`` and every chunk on the
    device.  The grids come back as host arrays copied from the device,
    so each clock stops after the device finished."""
    backend.reset_stats()
    c0 = _compile_seconds()
    t0 = time.perf_counter()
    first = run()
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = run()
    wall_s = time.perf_counter() - t0
    st = dict(backend.stats)
    for got in (first, again):
        _require(got.shape == ref.shape and np.array_equal(got, ref),
                 f"{phase}: device grid differs from the numpy reference")
    _check_stats(phase, st)
    return {"bitexact": True, "first_call_s": first_s, "wall_s": wall_s,
            "compile_s": _compile_seconds() - c0, "stats": st}


def phase_paper(kernels=tuple(polybench.PAPER_15), n: int = POLYBENCH_N,
                alphas=ANALYSIS.alpha_sweep, ms=(2, 4, 8),
                css=(0, SIM_COMPUTE_SLOTS)) -> dict:
    """The paper suite as one union ``suite_sweep_grid``."""
    t0 = time.perf_counter()
    traces = [polybench.trace_kernel(k, n) for k in kernels]
    suite = EDagSuite(traces, names=list(kernels))
    trace_s = time.perf_counter() - t0
    alphas = np.asarray(alphas, dtype=np.float64)
    t0 = time.perf_counter()
    ref = suite_sweep_grid(suite, alphas, ms=ms, compute_slots=css,
                           backend="numpy")
    numpy_s = time.perf_counter() - t0
    rec = _timed_grid("paper", lambda: suite_sweep_grid(
        suite, alphas, ms=ms, compute_slots=css), ref)
    return {"phase": "paper", "traces": len(traces),
            "vertices": suite.n_vertices, "grid_points": int(ref.size),
            "max_makespan": float(ref.max()), "trace_s": trace_s,
            "numpy_s": numpy_s, **rec}


def phase_hpcg(n: int = 8, iters: int = 3, alphas=(50.0, 150.0, 300.0),
               m: int = 4, cs: int = 0) -> dict:
    """One HPCG CG trace through ``sweep_grid``."""
    t0 = time.perf_counter()
    g, _ = hpcg.trace_cg(n=n, iters=iters)
    trace_s = time.perf_counter() - t0
    alphas = np.asarray(alphas, dtype=np.float64)
    t0 = time.perf_counter()
    ref = sweep_grid(g, alphas, ms=(m,), compute_slots=(cs,),
                     backend="numpy")
    numpy_s = time.perf_counter() - t0
    rec = _timed_grid("hpcg", lambda: sweep_grid(
        g, alphas, ms=(m,), compute_slots=(cs,)), ref)
    return {"phase": "hpcg", "vertices": g.n_vertices,
            "grid_points": int(ref.size), "max_makespan": float(ref.max()),
            "trace_s": trace_s, "numpy_s": numpy_s, **rec}


def _same(a, b) -> bool:
    """Exact equality of two report values (nested dicts, lists, arrays)."""
    if isinstance(a, dict):
        return (isinstance(b, dict) and a.keys() == b.keys()
                and all(_same(a[k], b[k]) for k in a))
    if isinstance(a, (list, tuple)) and not isinstance(b, np.ndarray):
        return (isinstance(b, (list, tuple)) and len(a) == len(b)
                and all(_same(x, y) for x, y in zip(a, b)))
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        a, b = np.asarray(a), np.asarray(b)
        return a.shape == b.shape and np.array_equal(a, b)
    return a == b


def phase_service(kernels=("atax", "gemm"), n: int = POLYBENCH_N,
                  cg_n: int = 8, model: str = "qwen3-0.6b",
                  alphas=ANALYSIS.alpha_sweep) -> dict:
    """``AnalysisService.process``: kernel, HPCG and model requests."""
    def requests(**kw):
        grid = dict(alphas=tuple(alphas), ms=(4,), compute_slots=(0,), **kw)
        return ([AnalysisRequest(kernel=k, n=n, **grid) for k in kernels]
                + [AnalysisRequest(kernel="cg", n=cg_n, **grid),
                   AnalysisRequest(config=model, phase="decode",
                                   kind="model", **grid)])

    t0 = time.perf_counter()
    ref = AnalysisService(start=False).process(requests(backend="numpy"))
    numpy_s = time.perf_counter() - t0
    for r in ref:
        _require(r.ok, f"service: numpy reference request failed: {r.error}")
    backend.reset_stats()
    c0 = _compile_seconds()
    t0 = time.perf_counter()
    out = AnalysisService(start=False).process(requests())
    wall_s = time.perf_counter() - t0
    st = dict(backend.stats)
    for r, want in zip(out, ref):
        _require(r.ok, f"service: request {r.rid} failed: {r.error}")
        _require(r.policy.get("demotions") == 0,
                 f"service: request {r.rid} demoted: {r.policy}")
        _require(_same(r.report, want.report),
                 f"service: request {r.rid} report differs from numpy")
    _require(set(out[0].batch_rids) >= {out[0].rid, out[1].rid},
             f"service: the two kernel requests were not co-batched: "
             f"{out[0].batch_rids}")
    _check_stats("service", st)
    return {"phase": "service", "requests": len(out),
            "all_ok": True, "demotions": 0, "bitexact": True,
            "batches": sorted({r.batch_rids for r in out}),
            "wall_s": wall_s, "compile_s": _compile_seconds() - c0,
            "numpy_s": numpy_s, "stats": st}


PHASES = (phase_paper, phase_hpcg, phase_service)


def main() -> int:
    try:
        device = check_device()
        print(json.dumps({"phase": "device", **device}), flush=True)
        _compile_seconds()
        with tempfile.TemporaryDirectory(prefix="edan-smoke-") as td:
            os.environ["EDAN_SCHEDULE_CACHE"] = os.path.join(td, "sched")
            for phase in PHASES:
                rec = phase()
                rec.update(device_memory())
                print(json.dumps(rec), flush=True)
    except Exception:
        traceback.print_exc()
        print("chip smoke FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

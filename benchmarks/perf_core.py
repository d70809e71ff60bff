"""Core-engine performance benchmark: the vectorized eDAG engine vs the
retained seed scalar engine.

Measures, at paper-size PolyBench traces (plus HPCG for tracing):

* **tracing**     traced vertices/sec — bulk block emission vs the
                  per-element reference tracer;
* **accumulate**  longest-path edges/sec — level-synchronous segmented
                  reductions vs the per-edge Python loop;
* **sweep**       latency-sweep points/sec — one batched multi-cost level
                  pass vs one scalar accumulate per point;
* **chunks**      the cache-chunk crossover behind the trace-size-aware
                  ``t_inf_sweep_mem`` default;
* **sim**         §4 simulator sweeps — the batched schedule-replay engine
                  vs the retained per-point heapq reference (written to
                  ``BENCH_sim.json``; acceptance floor 10x at paper sizes);
* **grid**        alpha × m × compute_slots capacity-planning grids —
                  ``sweep_grid`` vs per-point ``simulate_reference``, with
                  every grid point asserted bit-identical;
* **suite**       the whole-suite union grid — ``suite_sweep_grid`` over
                  one block-diagonal union eDAG of all kernels vs the
                  per-kernel ``sweep_grid`` loop, both schedule-cache-warm
                  (one stacked level pass vs K independent pipelines);
                  every per-trace row asserted bit-identical, aggregate
                  speedup floor 2x at paper sizes;
* **device**      the accelerator-resident grid — ``sweep_grid`` and
                  ``suite_sweep_grid`` forced onto the jax backend with
                  no x64 flag, so every replay chunk runs the
                  error-bounded float32 device mode; >= 90% of replay
                  chunks must execute on the jax backend
                  (``backend.stats``) and every returned grid point is
                  asserted bit-identical to the float64 numpy reference;
* **cache**       the persistent schedule cache across two successive
                  *processes*: a cold child records every (m, slots)
                  schedule, a warm child sharing the same cache directory
                  must record none.

Timed sim/grid runs pass ``use_cache=False`` so the engine numbers stay
comparable across runs and PRs; the cache rows measure the cache itself.

Writes ``BENCH_core.json`` / ``BENCH_sim.json`` next to the repo root and
prints one CSV row per measurement.  ``--smoke`` shrinks sizes for CI
wall-clock.

Usage: PYTHONPATH=src python -m benchmarks.perf_core [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

try:
    import resource
except ImportError:                  # pragma: no cover - non-POSIX hosts
    resource = None


def _mem_probe() -> dict:
    """Point-in-time memory/allocation counters: peak RSS (MB, process
    high-water mark), cumulative minor page faults (fresh-page demand —
    the allocation-behavior signal wall clock hides) and live Python
    allocator blocks."""
    out = dict(alloc_blocks=sys.getallocatedblocks())
    if resource is not None:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        out["peak_rss_mb"] = ru.ru_maxrss / 1024.0  # Linux: KB -> MB
        out["minor_faults"] = ru.ru_minflt
    return out


def _mem_cols(before: dict) -> dict:
    """Bench-row memory columns relative to a ``_mem_probe`` snapshot.

    ``peak_rss_mb`` is absolute (the kernel keeps one high-water mark per
    process, so per-bench deltas are only meaningful when they grow);
    the fault/allocation deltas are per-bench."""
    after = _mem_probe()
    cols = dict(alloc_blocks_delta=after["alloc_blocks"]
                - before["alloc_blocks"])
    if "peak_rss_mb" in after:
        cols["peak_rss_mb"] = round(after["peak_rss_mb"], 1)
        cols["minor_faults_delta"] = (after["minor_faults"]
                                      - before["minor_faults"])
    return cols

from repro.apps import hpcg, polybench, reference
from repro.configs.paper_suite import SIM_COMPUTE_SLOTS
from repro.core import (EDagSuite, Tracer, cost_matrix, latency_sweep,
                        simulate_reference, suite_sweep_grid, sweep_grid)


def _best_of(fn, repeats: int = 5) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _timed_best(fn, repeats: int):
    """(best wall-clock, last result) over ``repeats`` runs."""
    best, res = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        res = fn()
        best = min(best, time.perf_counter() - t0)
    return best, res


def bench_tracing(N: int, repeats: int) -> dict:
    def run_block():
        return polybench.trace_kernel("gemm", N)

    def run_ref():
        tr = Tracer()
        reference.REF_POLYBENCH_KERNELS["gemm"](tr, N,
                                                np.random.default_rng(0))
        return tr.edag

    mem0 = _mem_probe()
    nv = run_block().n_vertices
    t_blk = _best_of(run_block, repeats)
    t_ref = _best_of(run_ref, repeats)
    return dict(name=f"trace_gemm_N{N}", n_vertices=nv,
                block_vps=nv / t_blk, scalar_vps=nv / t_ref,
                speedup=t_ref / t_blk, **_mem_cols(mem0))


def bench_tracing_hpcg(n: int, iters: int, repeats: int) -> dict:
    nv = hpcg.trace_cg(n=n, iters=iters)[0].n_vertices
    t_blk = _best_of(lambda: hpcg.trace_cg(n=n, iters=iters), repeats)
    t_ref = _best_of(lambda: reference.trace_cg_ref(n=n, iters=iters),
                     repeats)
    return dict(name=f"trace_hpcg_n{n}x{iters}", n_vertices=nv,
                block_vps=nv / t_blk, scalar_vps=nv / t_ref,
                speedup=t_ref / t_blk)


def bench_accumulate(N: int, repeats: int) -> dict:
    mem0 = _mem_probe()
    g = polybench.trace_kernel("gemm", N)
    g._finalize()
    ne = g.n_edges
    g._accumulate(g.cost)                       # warm derived arrays
    t_vec = _best_of(lambda: g._accumulate(g.cost), repeats)
    t_ref = _best_of(lambda: g._accumulate_scalar(g.cost), repeats)
    assert np.array_equal(g._accumulate(g.cost), g._accumulate_scalar(g.cost))
    return dict(name=f"accumulate_gemm_N{N}", n_edges=ne,
                vector_eps=ne / t_vec, scalar_eps=ne / t_ref,
                speedup=t_ref / t_vec, **_mem_cols(mem0))


def bench_sweep(N: int, n_points: int, repeats: int) -> dict:
    mem0 = _mem_probe()
    g = polybench.trace_kernel("gemm", N)
    g._finalize()
    alphas = np.linspace(50, 300, n_points)
    costs = cost_matrix(g, alphas)
    g.t_inf_sweep_mem(alphas[:2])               # warm

    def run_batch():
        return g.t_inf_sweep_mem(alphas)

    def run_scalar():                            # the seed per-point rebuild
        return np.array([g._accumulate_scalar(c).max() for c in costs])

    t_vec = _best_of(run_batch, repeats)
    t_ref = _best_of(run_scalar, max(1, repeats - 1))
    assert np.array_equal(run_batch(), run_scalar())
    return dict(name=f"sweep_gemm_N{N}x{n_points}", n_points=n_points,
                batch_pps=n_points / t_vec, scalar_pps=n_points / t_ref,
                speedup=t_ref / t_vec, **_mem_cols(mem0))


def bench_sweep_chunks(N: int, n_points: int, repeats: int) -> list:
    """Crossover study for the trace-size-aware sweep chunking: times the
    batched span sweep at fixed chunk sizes vs the auto default."""
    g = polybench.trace_kernel("gemm", N)
    g._finalize()
    alphas = np.linspace(50, 300, n_points)
    g.t_inf_sweep_mem(alphas[:2])               # warm
    want = g.t_inf_sweep_mem(alphas, chunk=1)
    rows = []
    for chunk in (6, 12, 24, 48, None):
        t = _best_of(lambda: g.t_inf_sweep_mem(alphas, chunk=chunk), repeats)
        assert np.array_equal(g.t_inf_sweep_mem(alphas, chunk=chunk), want)
        rows.append(dict(name=f"sweep_chunk_gemm_N{N}x{n_points}",
                         chunk="auto" if chunk is None else chunk,
                         pps=n_points / t, seconds=t))
    return rows


def bench_sim(names, N: int, n_points: int, repeats: int,
              m: int = 4, compute_slots: int = SIM_COMPUTE_SLOTS) -> dict:
    """§4 simulator sweep: batched schedule replay vs the retained heapq
    reference, per kernel, with bit-identical makespans asserted."""
    alphas = np.linspace(50.0, 300.0, n_points)
    rows = []
    tot_b = tot_r = 0.0
    mem0 = _mem_probe()
    for name in names:
        g = polybench.trace_kernel(name, N)
        g._finalize()
        g._sim_lists()
        latency_sweep(g, alphas[:3], m=m, compute_slots=compute_slots,
                      use_cache=False)                                # warm

        t_b, got = _timed_best(lambda: latency_sweep(
            g, alphas, m=m, compute_slots=compute_slots,
            use_cache=False), repeats)
        t_r, want = _timed_best(lambda: latency_sweep(
            g, alphas, m=m, compute_slots=compute_slots, batch=False),
            repeats)
        assert np.array_equal(got, want), f"batched sim diverged on {name}"
        tot_b += t_b
        tot_r += t_r
        rows.append(dict(name=f"sim_{name}_N{N}x{n_points}",
                         n_vertices=g.n_vertices, n_points=n_points,
                         batch_s=t_b, ref_s=t_r, speedup=t_r / t_b))
    return dict(kernels=rows, total_batch_s=tot_b, total_ref_s=tot_r,
                total_speedup=tot_r / tot_b, **_mem_cols(mem0),
                config=dict(N=N, n_points=n_points, m=m,
                            compute_slots=compute_slots))


def bench_grid(names, N: int, alphas, ms, css, repeats: int) -> dict:
    """alpha × m × compute_slots capacity-planning grid: ``sweep_grid``
    (one recorded schedule per (m, slots) pair, stacked alpha replay)
    vs per-point ``simulate_reference``, bit-identity asserted at every
    grid point of every kernel."""
    alphas = np.asarray(alphas, dtype=np.float64)
    rows = []
    tot_g = tot_r = 0.0
    for name in names:
        g = polybench.trace_kernel(name, N)
        g._finalize()
        g._sim_lists()
        sweep_grid(g, alphas[:2], ms=ms, compute_slots=css,
                   use_cache=False)                                   # warm

        t_g, grid = _timed_best(lambda: sweep_grid(
            g, alphas, ms=ms, compute_slots=css, use_cache=False),
            repeats)
        t0 = time.perf_counter()
        for i, a in enumerate(alphas):
            for j, m in enumerate(ms):
                for l, cs in enumerate(css):
                    want = simulate_reference(g, m=m, alpha=float(a),
                                              compute_slots=cs)
                    assert grid[i, j, l] == want, \
                        f"grid diverged on {name} at {(a, m, cs)}"
        t_r = time.perf_counter() - t0
        tot_g += t_g
        tot_r += t_r
        rows.append(dict(name=f"grid_{name}_N{N}", n_vertices=g.n_vertices,
                         n_points=grid.size, grid_s=t_g, ref_s=t_r,
                         speedup=t_r / t_g))
    return dict(kernels=rows, total_grid_s=tot_g, total_ref_s=tot_r,
                total_speedup=tot_r / tot_g,
                config=dict(N=N, alphas=list(map(float, alphas)),
                            ms=list(ms), compute_slots=list(css)))


def bench_suite_grid(names, N: int, alphas, ms, css, repeats: int,
                     floor: float) -> dict:
    """Whole-suite union grid vs the per-kernel ``sweep_grid`` loop.

    Both sides run schedule-cache-warm against a private cache directory
    (a cold suite pass records and persists every (member, m, slots)
    schedule first), so the timed comparison isolates exactly what the
    union batches: one stacked (max,+) level pass over the block-diagonal
    union eDAG versus K independent finalize/replay pipelines.  Every
    per-trace row of the suite grid is asserted bit-identical to the
    single-trace loop, and the timed section must record nothing —
    recording costs are identical on both sides by construction and are
    reported separately as ``cold_s``."""
    from repro.core import schedule_cache as sc

    alphas = np.asarray(alphas, dtype=np.float64)
    traces = [polybench.trace_kernel(nm, N) for nm in names]
    for g in traces:
        g._finalize()
        g._sim_lists()
    suite = EDagSuite(traces, names=list(names))
    keys = ("EDAN_SCHEDULE_CACHE", "EDAN_SCHEDULE_CACHE_MIN",
            "EDAN_SCHEDULE_CACHE_MAX")
    saved = {k: os.environ.get(k) for k in keys}
    with tempfile.TemporaryDirectory() as td:
        os.environ.update(EDAN_SCHEDULE_CACHE=td,
                          EDAN_SCHEDULE_CACHE_MIN="0",
                          EDAN_SCHEDULE_CACHE_MAX=str(10 ** 6))
        try:
            sc.reset_stats()
            t0 = time.perf_counter()
            suite_sweep_grid(suite, alphas, ms=ms, compute_slots=css)
            cold_s = time.perf_counter() - t0
            cold_records = sc.stats["record_runs"]

            def run_loop():
                return [sweep_grid(g, alphas, ms=ms, compute_slots=css)
                        for g in traces]

            def run_suite():
                return suite_sweep_grid(suite, alphas, ms=ms,
                                        compute_slots=css)

            run_loop()                 # warm the member plan memos too
            sc.reset_stats()
            t_loop, singles = _timed_best(run_loop, repeats)
            t_suite, sgrid = _timed_best(run_suite, repeats)
            warm_records = sc.stats["record_runs"]
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
    assert warm_records == 0, \
        "suite bench timed section re-recorded despite a warm cache"
    for k, nm in enumerate(names):
        assert np.array_equal(sgrid[k], singles[k]), \
            f"suite grid diverged from single-trace sweep_grid on {nm}"
    speedup = t_loop / t_suite
    assert speedup > floor, \
        f"suite grid speedup collapsed: {speedup:.2f}x (floor {floor}x)"
    return dict(name=f"suite_grid_{len(names)}x_N{N}",
                n_traces=len(names), n_vertices=suite.n_vertices,
                n_points=int(sgrid.size), cold_s=cold_s,
                cold_records=cold_records, loop_s=t_loop, suite_s=t_suite,
                warm_record_runs=warm_records, speedup=speedup,
                config=dict(N=N, alphas=list(map(float, alphas)),
                            ms=list(ms), compute_slots=list(css),
                            kernels=list(names), floor=floor))


def bench_device_grid(names, N: int, alphas, ms, css) -> dict:
    """Accelerator-resident replay: the capacity-planning grid forced
    onto the jax backend *without* the x64 flag, i.e. through the
    error-bounded float32 device mode of ``backend.replay_accumulate``.

    The alpha grid is paper-protocol clean (integer multiples), so the
    per-column exactness certificate holds and the replay stays on
    device: the bench asserts that >= 90% of replay chunks executed on
    the jax backend (``backend.stats``) and that every grid point of
    both ``sweep_grid`` and ``suite_sweep_grid`` is bit-identical to the
    float64 numpy reference — f32 is an execution strategy, never an
    answer.  On a TPU every chunk must run on the device: a device pass
    that fails raises rather than run on numpy.  On CPU hosts the pallas
    step runs in interpret mode, so the timings there measure the
    interpreter and the dispatch pipeline, not the device; the assertions
    are the gate."""
    from repro.core import backend as bk

    try:
        import jax
    except Exception:                # pragma: no cover - jax ships in CI
        return dict(name=f"device_grid_{len(names)}x_N{N}",
                    skipped="jax unavailable")
    # the bench measures the f32 replay mode, so pin the x64 flag off
    # for its duration (restored below)
    x64_was = bool(jax.config.jax_enable_x64)
    if x64_was:
        jax.config.update("jax_enable_x64", False)
    try:
        return _device_grid_body(bk, names, N, alphas, ms, css)
    finally:
        if x64_was:
            jax.config.update("jax_enable_x64", True)


def _device_grid_body(bk, names, N: int, alphas, ms, css) -> dict:
    alphas = np.asarray(alphas, dtype=np.float64)
    assert np.array_equal(alphas.astype(np.float32).astype(np.float64),
                          alphas), "device bench needs f32-clean alphas"
    traces = [polybench.trace_kernel(nm, N) for nm in names]
    for g in traces:
        g._finalize()
        g._sim_lists()
    suite = EDagSuite(traces, names=list(names))

    t0 = time.perf_counter()
    ref = [sweep_grid(g, alphas, ms=ms, compute_slots=css,
                      backend="numpy", use_cache=False) for g in traces]
    numpy_s = time.perf_counter() - t0
    sref = suite_sweep_grid(suite, alphas, ms=ms, compute_slots=css,
                            backend="numpy", use_cache=False)

    # replay_dtype is pinned explicitly so an ambient EDAN_X64 /
    # EDAN_REPLAY_DTYPE cannot silently flip the bench to x64 mode —
    # this row must measure the f32 device mode, nothing else
    bk.reset_stats()
    t0 = time.perf_counter()
    dev = [sweep_grid(g, alphas, ms=ms, compute_slots=css,
                      backend="jax", replay_dtype="float32",
                      use_cache=False) for g in traces]
    device_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    sdev = suite_sweep_grid(suite, alphas, ms=ms, compute_slots=css,
                            backend="jax", replay_dtype="float32",
                            use_cache=False)
    suite_device_s = time.perf_counter() - t0
    stats = dict(bk.stats)
    assert stats["jax_f64_chunks"] == 0, \
        "device bench leaked into x64 mode; it must measure f32 replay"

    for k, nm in enumerate(names):
        assert np.array_equal(dev[k], ref[k]), \
            f"device grid diverged from the f64 reference on {nm}"
        assert np.array_equal(sdev[k], ref[k]), \
            f"device suite grid diverged from the f64 reference on {nm}"
        assert np.array_equal(sref[k], ref[k])
    frac = stats["jax_chunks"] / max(stats["chunks"], 1)
    assert frac >= 0.9, \
        f"only {frac:.0%} of replay chunks ran on the jax backend"
    if bk.on_tpu():
        assert stats["numpy_chunks"] == 0 and stats["demoted_columns"] == 0, \
            f"replay chunks left the TPU: {stats}"
    return dict(name=f"device_grid_{len(names)}x_N{N}",
                n_traces=len(names),
                n_points=int(sum(r.size for r in ref)),
                jax_chunk_fraction=frac, bitexact=True,
                device_s=device_s, suite_device_s=suite_device_s,
                numpy_s=numpy_s, **{k: int(v) for k, v in stats.items()},
                config=dict(N=N, alphas=list(map(float, alphas)),
                            ms=list(ms), compute_slots=list(css),
                            kernels=list(names)))


def _cache_child(cfg: dict) -> None:
    """One benchmark process: trace the kernel, run the grid, report how
    many schedules had to be recorded.  Driven twice by
    ``bench_schedule_cache`` against one shared cache directory."""
    from repro.core import schedule_cache as sc

    g = polybench.trace_kernel(cfg["kernel"], cfg["N"])
    g._finalize()
    g._sim_lists()
    sc.reset_stats()
    t0 = time.perf_counter()
    grid = sweep_grid(g, np.asarray(cfg["alphas"]), ms=cfg["ms"],
                      compute_slots=cfg["compute_slots"])
    dt = time.perf_counter() - t0
    print("CACHE_CHILD " + json.dumps(dict(
        seconds=dt, makespan_sum=float(grid.sum()),
        n_vertices=g.n_vertices, **sc.stats)))


def bench_schedule_cache(name: str, N: int, alphas, ms, css,
                         repeats: int = 2) -> dict:
    """Persistent-cache proof across successive *processes*: a cold
    child records one schedule per (m, compute_slots) pair and persists
    it; warm children, sharing only the on-disk cache directory, must
    record zero and produce the identical grid.

    Cold and warm sides each run ``repeats`` times (cold reps against
    fresh cache directories, warm reps against the seeded one) and the
    reported ``speedup`` is best-of/best-of — a single cold/warm shot is
    subprocess start-up plus one short grid, whose timing noise has
    historically swamped the real effect (a snapshot once published
    0.38x for a workload that measures ~1.5x under repeats).  The
    structural proof (``record_runs`` cold > 0, warm == 0) is
    noise-free either way."""
    cfg = dict(kernel=name, N=N, alphas=list(map(float, alphas)),
               ms=list(ms), compute_slots=list(css))
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")

    def child(env: dict, label: str) -> dict:
        p = subprocess.run(
            [sys.executable, "-m", "benchmarks.perf_core",
             "--cache-child", json.dumps(cfg)],
            env=env, capture_output=True, text=True,
            cwd=os.path.dirname(src))
        if p.returncode != 0:
            # surface the child's traceback in the CI log before dying
            sys.stderr.write(p.stdout + p.stderr)
            raise RuntimeError(f"{label} cache child exited {p.returncode}")
        line = next((ln for ln in p.stdout.splitlines()
                     if ln.startswith("CACHE_CHILD ")), None)
        if line is None:
            sys.stderr.write(p.stdout + p.stderr)
            raise RuntimeError(
                f"{label} cache child produced no CACHE_CHILD line")
        return json.loads(line[len("CACHE_CHILD "):])

    cold_runs, warm_runs = [], []
    with tempfile.TemporaryDirectory() as td:
        base = dict(os.environ,
                    # the children measure host-side recording: keep them
                    # off the accelerator, which belongs to one process
                    # (this one may already hold it)
                    JAX_PLATFORMS="cpu", EDAN_BACKEND="numpy",
                    # self-contained: don't inherit caller floors/caps
                    EDAN_SCHEDULE_CACHE_MIN="0",
                    EDAN_SCHEDULE_CACHE_MAX=str(10 ** 6),
                    PYTHONPATH=src + os.pathsep +
                    os.environ.get("PYTHONPATH", ""))
        shared = os.path.join(td, "shared")
        for rep in range(max(repeats, 1)):
            # rep 0 seeds the shared dir the warm side reads; later cold
            # reps get fresh dirs so they genuinely re-record
            cdir = shared if rep == 0 else os.path.join(td, f"cold{rep}")
            cold_runs.append(child(dict(base, EDAN_SCHEDULE_CACHE=cdir),
                                   f"cold[{rep}]"))
        for rep in range(max(repeats, 1)):
            warm_runs.append(child(dict(base, EDAN_SCHEDULE_CACHE=shared),
                                   f"warm[{rep}]"))
    cold = min(cold_runs, key=lambda r: r["seconds"])
    warm = min(warm_runs, key=lambda r: r["seconds"])
    assert all(r["record_runs"] > 0 for r in cold_runs)
    assert all(r["record_runs"] == 0 for r in warm_runs), \
        "warm process re-recorded despite a persistent schedule cache"
    assert all(r["makespan_sum"] == cold["makespan_sum"]
               for r in cold_runs + warm_runs)
    return dict(config=cfg, cold=cold, warm=warm, repeats=repeats,
                cold_seconds=[r["seconds"] for r in cold_runs],
                warm_seconds=[r["seconds"] for r in warm_runs],
                speedup=cold["seconds"] / warm["seconds"])


def run(smoke: bool = False) -> dict:
    repeats = 2 if smoke else 5
    N = 12 if smoke else 32
    out = dict(
        tracing=[bench_tracing(N, repeats),
                 bench_tracing_hpcg(4 if smoke else 8, 2, repeats)],
        accumulate=[bench_accumulate(N, repeats)],
        sweep=[bench_sweep(N, 11 if smoke else 51, repeats)],
        sweep_chunks=bench_sweep_chunks(N, 11 if smoke else 51, repeats),
    )
    return out


def run_sim(smoke: bool = False) -> dict:
    if smoke:
        # big enough that the one recording run amortizes (the gate floor
        # is loose, but a return to per-point simulation must still trip it)
        sim = bench_sim(("gemm", "mvt", "lu"), N=14, n_points=21,
                        repeats=2)
        sim["grid"] = bench_grid(("gemm", "mvt"), N=12,
                                 alphas=np.linspace(50.0, 300.0, 7),
                                 ms=(2, 4), css=(0, 4), repeats=1)
        sim["suite"] = bench_suite_grid(
            ("gemm", "mvt", "lu"), N=14,
            alphas=np.linspace(50.0, 300.0, 11), ms=(2, 4), css=(0, 4),
            repeats=2, floor=1.0)
        sim["cache"] = bench_schedule_cache(
            "gemm", 14, np.linspace(50.0, 300.0, 11), (2, 4), (0, 8))
        sim["device"] = bench_device_grid(
            ("gemm", "mvt"), N=12, alphas=np.arange(50.0, 301.0, 50.0),
            ms=(2, 4), css=(0, 4))
    else:
        sim = bench_sim(polybench.PAPER_15, N=20, n_points=51, repeats=2)
        sim["grid"] = bench_grid(polybench.PAPER_15, N=20,
                                 alphas=np.linspace(50.0, 300.0, 13),
                                 ms=(2, 4, 8), css=(0, 8), repeats=1)
        # the acceptance config: PAPER_15 at N=20 over the full 78-point
        # grid, whole-suite union pass >= 2x the 15-call loop
        sim["suite"] = bench_suite_grid(
            polybench.PAPER_15, N=20,
            alphas=np.linspace(50.0, 300.0, 13), ms=(2, 4, 8), css=(0, 8),
            repeats=2, floor=2.0)
        sim["cache"] = bench_schedule_cache(
            "gemm", 20, np.linspace(50.0, 300.0, 26), (2, 4, 8), (0, 8))
        # the acceptance config: PAPER_15 on the jax backend without x64
        # — >= 90% of replay chunks on device, every point bit-identical
        sim["device"] = bench_device_grid(
            polybench.PAPER_15, N=20, alphas=np.arange(50.0, 301.0, 10.0),
            ms=(2, 4, 8), css=(0, 8))
    return sim


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for CI wall-clock")
    ap.add_argument("--out", default="BENCH_core.json")
    ap.add_argument("--out-sim", default="BENCH_sim.json")
    ap.add_argument("--cache-child", metavar="JSON", default=None,
                    help=argparse.SUPPRESS)   # bench_schedule_cache driver
    args = ap.parse_args()
    if args.cache_child:
        _cache_child(json.loads(args.cache_child))
        return
    res = run(smoke=args.smoke)
    print("name,metric,vectorized,scalar,speedup")
    for group, key in (("tracing", "vps"), ("accumulate", "eps"),
                       ("sweep", "pps")):
        for row in res[group]:
            vec = row.get(f"block_{key}", row.get(f"vector_{key}",
                                                  row.get(f"batch_{key}")))
            print(f"{row['name']},{group}/{key},{vec:.0f},"
                  f"{row[f'scalar_{key}']:.0f},{row['speedup']:.1f}x")
    for row in res["sweep_chunks"]:
        print(f"{row['name']},chunk={row['chunk']},{row['pps']:.0f},,")
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"# wrote {args.out}")
    core = res["accumulate"][0]["speedup"]
    swp = res["sweep"][0]["speedup"]
    print(f"# accumulate speedup {core:.1f}x, sweep speedup {swp:.1f}x "
          f"(acceptance floor: 10x)")

    sim = run_sim(smoke=args.smoke)
    for row in sim["kernels"]:
        print(f"{row['name']},sim/sweep,{row['batch_s']:.3f}s,"
              f"{row['ref_s']:.3f}s,{row['speedup']:.1f}x")
    for row in sim["grid"]["kernels"]:
        print(f"{row['name']},sim/grid,{row['grid_s']:.3f}s,"
              f"{row['ref_s']:.3f}s,{row['speedup']:.1f}x")
    suite = sim["suite"]
    print(f"{suite['name']},sim/suite,{suite['suite_s']:.3f}s,"
          f"{suite['loop_s']:.3f}s,{suite['speedup']:.1f}x "
          f"(cold {suite['cold_s']:.3f}s / "
          f"{suite['cold_records']} recordings)")
    dev = sim["device"]
    if dev.get("skipped"):
        print(f"{dev['name']},sim/device,skipped ({dev['skipped']})")
    else:
        print(f"{dev['name']},sim/device,{dev['device_s']:.3f}s,"
              f"{dev['numpy_s']:.3f}s,"
              f"{dev['jax_chunk_fraction']:.0%} chunks on jax "
              f"(demoted columns: {dev['demoted_columns']}, bit-identical)")
    cache = sim["cache"]
    print(f"grid_cache_{cache['config']['kernel']}"
          f"_N{cache['config']['N']},sim/cache,"
          f"{cache['warm']['seconds']:.3f}s,"
          f"{cache['cold']['seconds']:.3f}s,{cache['speedup']:.2f}x "
          f"(records cold={cache['cold']['record_runs']} "
          f"warm={cache['warm']['record_runs']})")
    # read-modify-write: perf_scale owns the "scale" section of the same
    # file and perf_placement the "placement" section — carry foreign
    # sections over instead of clobbering them
    if os.path.exists(args.out_sim):
        try:
            with open(args.out_sim) as f:
                prev = json.load(f)
            sim = {**{k: v for k, v in prev.items()
                      if k in ("scale", "placement")}, **sim}
        except (OSError, ValueError):
            pass
    with open(args.out_sim, "w") as f:
        json.dump(sim, f, indent=2)
    print(f"# wrote {args.out_sim}")
    print(f"# simulator sweep speedup {sim['total_speedup']:.1f}x over "
          f"{len(sim['kernels'])} kernels "
          "(acceptance floor: 10x at paper sizes)")
    print(f"# grid speedup {sim['grid']['total_speedup']:.1f}x over "
          f"{len(sim['grid']['kernels'])} kernels; warm schedule cache: "
          f"{cache['warm']['record_runs']} re-recordings across processes")
    print(f"# suite grid speedup {suite['speedup']:.1f}x over the "
          f"{suite['n_traces']}-call loop "
          f"(floor {suite['config']['floor']}x, every per-trace row "
          "bit-identical)")


if __name__ == "__main__":
    main()

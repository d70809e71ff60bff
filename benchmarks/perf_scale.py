"""Million-vertex scale benchmark: wall clock, peak RSS and allocation
behavior of the full pipeline at true HPCG sizes.

Each tier (~100k / ~500k / ~1M vertices, HPCG CG traces) runs in a
*fresh subprocess* so its RSS high-water mark measures that tier alone,
and walks the whole pipeline end-to-end:

  trace -> _finalize (streaming counting-sort merge) -> levelize
  -> sweep_grid under a small ``$EDAN_REPLAY_MEM_BUDGET`` (64 MB)
  -> trace_store save -> memory-mapped load -> sweep on the mapped graph

Per stage it records wall seconds plus the memory counters wall clock
hides (mind-malloc-bench methodology — allocation behaviour, not just
time): resident-set deltas from ``/proc/self/status`` (VmRSS / VmHWM),
minor/major page-fault deltas from ``getrusage`` and live Python
allocator blocks from ``sys.getallocatedblocks``.

Acceptance assertions (the reason this bench exists):

* the ~1M tier's peak-RSS *delta* over the post-import baseline stays
  below **2x the theoretical working set**: the trace's CSR footprint
  (``EDag.array_nbytes`` — the int32 arrays actually installed) plus
  the recorded replay plan's arrays (``scheduler._Plan.array_nbytes`` —
  the order-augmented partition the simulator must keep to replay the
  sweep).  I.e. construction and replay never hold a *second* full
  copy of either structure;
* the 100k tier re-traces under ``$EDAN_LEGACY_BUILD=1`` and asserts
  the streaming build is **bit-identical** to the legacy list build
  (digest, levels, edge arrays and a sweep row);
* the warm memory-mapped reload produces the identical sweep row.

Children run with ``MALLOC_MMAP_THRESHOLD_=131072``: glibc's dynamic
mmap threshold otherwise grows to 32 MB the first time a large block
is freed, after which multi-MB numpy transients (sort permutations,
concatenations) land on the main arena and never return to the OS —
RSS then reports the *sum* of all transients ever live instead of the
actual working set.  Pinning the threshold makes every >=128 KB array
an mmap that is unmapped on free, so VmRSS/VmHWM measure what the
pipeline genuinely holds.

Results merge into the ``scale`` section of ``BENCH_sim.json``
(read-modify-write; ``perf_core`` owns the other sections).  ``--smoke``
runs only the 100k tier with an absolute RSS ceiling — the CI gate.

Usage: PYTHONPATH=src python -m benchmarks.perf_scale [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import tempfile
import time

#: (label, hpcg n, iters) — vertex counts ~104k / ~492k / ~1.09M.
TIERS = (("100k", 8, 3), ("500k", 12, 4), ("1m", 13, 7))

#: Replay budget the child sweeps under: small enough that the ~1M
#: tier's replay matrices must be chunked (one full (n, k) f64 pair at
#: k=3 would be ~52 MB), proving the pipeline honours the budget.
CHILD_MEM_BUDGET = str(64 * 1024 * 1024)

#: Absolute ceiling for the --smoke CI gate (MB): the 100k child peaks
#: around 410 MB (python + numpy + jax import baseline dominates); the
#: ceiling catches a structural regression (a second resident copy of
#: everything), not import-size drift.
SMOKE_RSS_CEILING_MB = 900.0


def _vm_mb(key: str) -> float:
    """Read a /proc/self/status field (VmRSS, VmHWM) in MB."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith(key + ":"):
                return int(line.split()[1]) / 1024.0
    return 0.0


def _probe() -> dict:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return dict(rss_mb=_vm_mb("VmRSS"), hwm_mb=_vm_mb("VmHWM"),
                minflt=ru.ru_minflt, majflt=ru.ru_majflt,
                blocks=sys.getallocatedblocks())


def _stage(stages: list, name: str, t0: float, before: dict) -> dict:
    after = _probe()
    row = dict(stage=name, seconds=time.perf_counter() - t0,
               rss_mb=round(after["rss_mb"], 1),
               hwm_mb=round(after["hwm_mb"], 1),
               rss_delta_mb=round(after["rss_mb"] - before["rss_mb"], 1),
               minflt_delta=after["minflt"] - before["minflt"],
               majflt_delta=after["majflt"] - before["majflt"],
               alloc_blocks_delta=after["blocks"] - before["blocks"])
    stages.append(row)
    return after


def _child(cfg: dict) -> None:
    """One tier, one process: walk the pipeline, print one JSON line."""
    import gc

    import numpy as np

    from repro.apps import hpcg
    from repro.core import load_edag, save_edag, scheduler, sweep_grid

    n, iters = cfg["n"], cfg["iters"]
    alphas = np.asarray([50.0, 150.0, 300.0])
    ms, css = (4,), (0,)
    stages: list = []

    baseline = _probe()        # post-import: interpreter + numpy + jax
    before = baseline

    t0 = time.perf_counter()
    g = hpcg.trace_cg(n=n, iters=iters)[0]
    before = _stage(stages, "trace", t0, before)

    t0 = time.perf_counter()
    g._finalize()
    before = _stage(stages, "finalize", t0, before)

    footprint = sum(g.array_nbytes().values())
    n_vertices, n_edges, n_levels = g.n_vertices, g.n_edges, g.n_levels

    t0 = time.perf_counter()
    grid = sweep_grid(g, alphas, ms=ms, compute_slots=css)
    before = _stage(stages, "sweep_grid", t0, before)

    # the recorded plan is live working set too (augmented partition,
    # issue orders) — count it in the denominator of the peak bound
    sched, plan, _ = scheduler._schedule(g, ms[0], css[0], 1.0, alphas[0],
                                         lookup=True)
    plan = plan or scheduler._build_plan([sched])
    plan_bytes = sum(plan.array_nbytes().values())
    del sched, plan

    legacy_ok = None
    if cfg.get("check_legacy"):
        # re-trace through the retained list build: the tracer's graphs
        # honour $EDAN_LEGACY_BUILD at construction time
        os.environ["EDAN_LEGACY_BUILD"] = "1"
        try:
            gl = hpcg.trace_cg(n=n, iters=iters)[0]
        finally:
            os.environ.pop("EDAN_LEGACY_BUILD", None)
        assert gl._legacy, "legacy build env knob was not honoured"
        gl._finalize()
        assert np.array_equal(g.src, gl.src)
        assert np.array_equal(g.dst, gl.dst)
        assert np.array_equal(g.level, gl.level)
        assert g.trace_digest() == gl.trace_digest()
        assert np.array_equal(
            sweep_grid(gl, alphas, ms=ms, compute_slots=css), grid), \
            "legacy build swept to different makespans"
        del gl
        legacy_ok = True

    store = os.path.join(cfg["tmpdir"], "trace")
    t0 = time.perf_counter()
    save_edag(g, store)
    before = _stage(stages, "store_save", t0, before)

    # the memory-mapped phase must *replace* the in-core trace, not
    # stack on it — that is the point of the store
    del g
    gc.collect()

    t0 = time.perf_counter()
    g2 = load_edag(store)      # memory-mapped, digest-verified
    before = _stage(stages, "store_load", t0, before)

    t0 = time.perf_counter()
    grid2 = sweep_grid(g2, alphas, ms=ms, compute_slots=css)
    before = _stage(stages, "sweep_mmap", t0, before)
    assert np.array_equal(grid, grid2), \
        "memory-mapped reload changed sweep results"

    final = _probe()
    peak_delta = final["hwm_mb"] - baseline["hwm_mb"]
    working_set = footprint + plan_bytes
    out = dict(
        tier=cfg["tier"], n=n, iters=iters,
        n_vertices=n_vertices, n_edges=n_edges, n_levels=n_levels,
        footprint_mb=round(footprint / 1e6, 1),
        plan_mb=round(plan_bytes / 1e6, 1),
        working_set_mb=round(working_set / 1e6, 1),
        baseline_rss_mb=round(baseline["rss_mb"], 1),
        peak_rss_mb=round(final["hwm_mb"], 1),
        peak_delta_mb=round(peak_delta, 1),
        peak_over_ws=round(peak_delta / (working_set / 1048576.0), 2),
        makespan_sum=float(grid.sum()), legacy_bitexact=legacy_ok,
        stages=stages)
    if cfg.get("assert_footprint"):
        assert peak_delta < 2.0 * working_set / 1048576.0, (
            f"peak RSS delta {peak_delta:.0f} MB exceeds 2x the "
            f"theoretical working set {working_set / 1048576.0:.0f} MB "
            f"(CSR {footprint / 1048576.0:.0f} MB + replay plan "
            f"{plan_bytes / 1048576.0:.0f} MB) — the pipeline is holding "
            f"a second copy of the trace")
    if cfg.get("rss_ceiling_mb"):
        assert final["hwm_mb"] < cfg["rss_ceiling_mb"], (
            f"peak RSS {final['hwm_mb']:.0f} MB exceeds the "
            f"{cfg['rss_ceiling_mb']:.0f} MB smoke ceiling")
    print("SCALE_CHILD " + json.dumps(out))


def run(smoke: bool = False) -> dict:
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    tiers = TIERS[:1] if smoke else TIERS
    rows = []
    with tempfile.TemporaryDirectory() as td:
        for tier, n, iters in tiers:
            cfg = dict(tier=tier, n=n, iters=iters, tmpdir=td,
                       check_legacy=(tier == "100k"),
                       assert_footprint=(tier == "1m"))
            if smoke:
                cfg["rss_ceiling_mb"] = SMOKE_RSS_CEILING_MB
            env = dict(os.environ,
                       EDAN_REPLAY_MEM_BUDGET=CHILD_MEM_BUDGET,
                       # private schedule cache: the first sweep persists
                       # its recorded plan (format-4 memory-mapped dirs
                       # at these sizes), the post-reload sweep warms
                       # from it instead of re-recording
                       EDAN_SCHEDULE_CACHE=os.path.join(td, "sched"),
                       # pin glibc's dynamic mmap threshold so freed
                       # numpy transients return to the OS (see module
                       # docstring) — RSS then measures live data
                       MALLOC_MMAP_THRESHOLD_="131072",
                       PYTHONPATH=src + os.pathsep +
                       os.environ.get("PYTHONPATH", ""))
            p = subprocess.run(
                [sys.executable, "-m", "benchmarks.perf_scale",
                 "--child", json.dumps(cfg)],
                env=env, capture_output=True, text=True,
                cwd=os.path.dirname(src))
            if p.returncode != 0:
                sys.stderr.write(p.stdout + p.stderr)
                raise RuntimeError(f"scale child {tier} exited "
                                   f"{p.returncode}")
            line = next((ln for ln in p.stdout.splitlines()
                         if ln.startswith("SCALE_CHILD ")), None)
            if line is None:
                sys.stderr.write(p.stdout + p.stderr)
                raise RuntimeError(f"scale child {tier} produced no "
                                   "SCALE_CHILD line")
            rows.append(json.loads(line[len("SCALE_CHILD "):]))
    return dict(tiers=rows,
                config=dict(mem_budget=int(CHILD_MEM_BUDGET), smoke=smoke))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="100k tier only, with an absolute RSS ceiling")
    ap.add_argument("--out-sim", default="BENCH_sim.json")
    ap.add_argument("--child", metavar="JSON", default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        _child(json.loads(args.child))
        return
    res = run(smoke=args.smoke)
    print("tier,n_vertices,n_edges,footprint_mb,plan_mb,peak_delta_mb,"
          "peak/ws,trace_s,finalize_s,sweep_s")
    for row in res["tiers"]:
        by = {s["stage"]: s for s in row["stages"]}
        print(f"{row['tier']},{row['n_vertices']},{row['n_edges']},"
              f"{row['footprint_mb']},{row['plan_mb']},"
              f"{row['peak_delta_mb']},{row['peak_over_ws']},"
              f"{by['trace']['seconds']:.2f},"
              f"{by['finalize']['seconds']:.2f},"
              f"{by['sweep_grid']['seconds']:.2f}")
    # merge into BENCH_sim.json: perf_core owns the other sections
    doc = {}
    if os.path.exists(args.out_sim):
        try:
            with open(args.out_sim) as f:
                doc = json.load(f)
        except ValueError:
            doc = {}
    doc["scale"] = res
    with open(args.out_sim, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"# merged scale section into {args.out_sim}")


if __name__ == "__main__":
    main()

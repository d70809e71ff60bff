#!/usr/bin/env python3
"""The on-chip benchmark of EDAN's analysis queries.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

One run is one process on one TPU host.  It reads the cell from
``BENCHMARK.json`` at the checkout root, its configuration from the
configuration's ``file``, its traffic mix from ``traffic/<traffic>.json``
and each metric's reader from ``metrics/<metric>.py`` beside this file,
so a cell, mix or metric is added by adding files and entries.  In order
it:

1. builds the configuration's traces and warms up every plan the mix
   uses (one query per plan, at fixed latencies): this is set-up;
2. runs the mix's queries back to back, one client, until the first query
   that completes at or after ``--seconds``;
3. reads the device's peak memory, frees the program's state, and checks
   a sample of the answers, drawn from the seed, against the plain
   reference (``reference.py``);
4. prints the metrics as the last line of standard output.

``--trace 0`` reports the cell's end-to-end metrics.  ``--trace 1``
installs the span wrappers of ``spans.json``, profiles the first
``trace_queries`` queries of the window and reports the per-layer metrics,
with ``busy_s``, ``window_s`` and a ``breakdown`` from the trace.

Without a TPU, or with fewer chips than the cell asks for, or on a device
the peaks table does not know, the run exits non-zero and prints no
result.  The JAX compilation cache and EDAN's schedule cache live at
fixed paths under ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up is timed from here

import argparse                                            # noqa: E402
import contextlib                                          # noqa: E402
import gc                                                  # noqa: E402
import importlib.util                                      # noqa: E402
import json                                                # noqa: E402
import os                                                  # noqa: E402
import shutil                                              # noqa: E402
import sys                                                 # noqa: E402
import traceback                                           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import check                                               # noqa: E402
import traffic as traffic_mod                              # noqa: E402
import yardstick                                           # noqa: E402

CACHE = os.path.join(ROOT, ".jax_cache")


def pin_environment(cache: str = CACHE) -> None:
    """Fixed cache paths inside the checkout; no EDAN knob from outside
    changes what the program runs."""
    for k in [k for k in os.environ if k.startswith("EDAN_")]:
        del os.environ[k]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache, "xla")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["EDAN_SCHEDULE_CACHE"] = os.path.join(cache, "schedules")


# ------------------------------------------------------------ the files

class Bench:
    """``BENCHMARK.json`` and the files it names."""

    def __init__(self, path: str):
        self.path = os.path.abspath(path)
        self.root = os.path.dirname(self.path)
        with open(self.path) as f:
            self.spec = json.load(f)
        self.home = os.path.join(self.root, self.spec["paths"][0])

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise yardstick.BenchError(
            f"no workload {name!r} in {self.path}; known: "
            f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise yardstick.BenchError(f"no configuration {name!r}")

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.home, "traffic", name + ".json")) as f:
            return json.load(f)

    def metrics(self, cell: str, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        path = os.path.join(self.home, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


class Run:
    """What one run measured; the metric readers read it."""

    def __init__(self):
        self.setup_s = None
        self.setup_compile_s = None
        self.setup_compiles = None
        self.window_s = None
        self.latencies: list = []
        self.recorder = None
        self.trace = None
        self.memory_peak_bytes = None
        self.peaks: dict = {}


# --------------------------------------------------------------- one run

def run_cell(bench: Bench, name: str, seed: int, seconds: float,
             trace: bool, t_start: float = T_START,
             cache: str = CACHE) -> dict:
    """One run of one cell; returns the result line as a dict, and the
    failed queries with the reasons."""
    cell = bench.cell(name)
    config = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    table = yardstick.load_peaks(os.path.join(bench.home, "peaks.json"))

    import jax
    from repro.core import backend
    from workload import Workload, points

    clock = yardstick.CompileClock()
    device = yardstick.check_device(int(cell["chips"]), table)
    run = Run()
    run.peaks = table["devices"].get(device["kind"], {})
    members = (list(mix["members"]) if mix.get("members")
               else list(config["traces"]))
    work = Workload(config, members, union=mix["pick"] == "union",
                    home=bench.home)

    recorder = None
    if trace:
        import spans
        recorder = spans.Recorder.from_file(
            os.path.join(bench.home, "spans.json"))
        recorder.install()
    try:
        for q in traffic_mod.warmup(mix):
            work.run(q)
        stream = traffic_mod.queries(mix, seed)
        log_dir = os.path.join(cache, "profile", name)
        if trace:
            shutil.rmtree(log_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            recorder.recording = True
        done, failures = _window(work, stream, seconds, run, clock,
                                 backend, mix, trace)
    finally:
        if recorder is not None:
            recorder.recording = False
            recorder.uninstall()
    run.setup_s = run.setup_s - t_start
    lat = sorted(run.latencies)
    print(f"window: {len(lat)} queries in {run.window_s:.3f} s, latency "
          f"min {lat[0]:.6f} median {lat[len(lat) // 2]:.6f} max "
          f"{lat[-1]:.6f} s; set-up {run.setup_s:.3f} s, of it "
          f"{run.setup_compile_s:.3f} s compiling {run.setup_compiles} "
          "programs", file=sys.stderr)
    if trace:
        jax.profiler.stop_trace()
    run.memory_peak_bytes = yardstick.device_memory_peak()
    names = work.names
    inputs = work.inputs
    del work
    gc.collect()

    if trace:
        import tracereduce
        run.recorder = recorder
        run.trace = tracereduce.reduce(tracereduce.load(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)

    answers = [(q, res, points(q, res, names)) for q, res in done]
    checks = check.check(answers, inputs, mix["check"], seed,
                         unit=float(config["unit"]),
                         figures=config.get("figures", {}))
    print(f"reference: {checks['compared']} values compared in "
          f"{checks['seconds']:.3f} s", file=sys.stderr)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench.metrics(name, kind):
        v = bench.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device["memory_peak_bytes"] = run.memory_peak_bytes
    out = {"correct": checks["correct"], "attempted": len(done),
           "failed": len(failures), "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        import tracereduce
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {
            "device_ops": tracereduce.top(run.trace["op_s"]),
            "idle_gaps": tracereduce.top(run.trace["idle_gaps"])}
    out["checks"] = checks["numbers"]
    return out, failures


def _window(work, stream, seconds, run, clock, backend, mix, trace):
    """Queries back to back until the first completion at or after
    ``seconds``; a traced run stops after ``trace_queries`` queries."""
    import jax
    span = (jax.profiler.TraceAnnotation if trace
            else lambda name: contextlib.nullcontext())
    cap = int(mix["trace_queries"]) if trace else None
    done, failures = [], []
    with span("bench.window"):
        t0 = run.setup_s = time.perf_counter()
        run.setup_compile_s, run.setup_compiles = clock.seconds, clock.events
        while True:
            q = next(stream)
            before = backend.stats.snapshot()
            compiled = clock.events
            with span("bench.query"):
                s = time.perf_counter()
                try:
                    res, err = work.run(q), None
                except Exception as exc:    # counted as failed, not fatal
                    res, err = None, f"{type(exc).__name__}: {exc}"
                e = time.perf_counter()
            run.latencies.append(e - s)
            why = ([err] if err else
                   yardstick.stats_gate(before, backend.stats.snapshot()))
            if clock.events != compiled:
                why.append(f"{clock.events - compiled} programs compiled "
                           "inside the window")
            if why:
                failures.append({"query": len(done), "why": why})
            done.append((q, res))
            if e - t0 >= seconds or (cap is not None and len(done) >= cap):
                break
    run.window_s = e - t0
    return done, failures


# ----------------------------------------------------------------- main

def _print_result(out: dict, failures: list) -> None:
    for f in failures[:10]:
        print(f"failed query {f['query']}: {'; '.join(f['why'])}",
              file=sys.stderr)
    for k, v in out["checks"].items():
        print(f"check {k} = {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    pin_environment()
    try:
        bench = Bench(os.path.join(ROOT, "BENCHMARK.json"))
        out, failures = run_cell(bench, args.workload, args.seed,
                                 args.seconds, bool(args.trace))
    except Exception:
        traceback.print_exc()
        print("benchmark run FAILED", file=sys.stderr)
        return 1
    _print_result(out, failures)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Record the trace fixture of one traced query on a TPU.

    python3 benchmarks/chip/tests/record_profile.py [OUT.json]

Runs one ``grid_report(simulate_points=True)`` query of PolyBench trisolv
at N = 20 (11 alphas, m = 4) after a warm-up query, under the profiler and
the benchmark's span wrappers, as a ``--trace 1`` run does.  Writes the
device events (op names shortened), the benchmark's ``bench.*`` spans and
the program's ``edan.*`` spans with their stats as ``(plane, line, name,
start_ns, dur_ns, stats)`` rows, and the replay passes the wrappers
recorded.  The default output is
``tests/data/profile_trisolv_spans_v5e.json``, the file the tests read.
"""
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

ALPHAS = [50, 63, 88, 101, 137, 150, 172, 199, 230, 264, 300]


def main(out: str) -> int:
    import run
    run.pin_environment()
    import jax
    import progspans
    import spans
    import tracereduce
    from workload import build_trace
    from repro.core import grid_report
    from repro.core.cost import CostModelParams

    if jax.default_backend() != "tpu":
        print("no TPU: nothing recorded", file=sys.stderr)
        return 1
    g = build_trace("polybench", {"kernel": "trisolv", "N": 20})
    g._finalize()

    def query():
        return grid_report(g, ALPHAS, ms=[4], compute_slots=[0],
                           params=CostModelParams(unit=1.0),
                           simulate_points=True)
    query()                                 # compiles every shape
    rec = spans.Recorder.from_file()
    rec.install()
    log_dir = tempfile.mkdtemp()
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        rec.recording = True
        with jax.profiler.TraceAnnotation(tracereduce.WINDOW):
            with jax.profiler.TraceAnnotation("bench.query"):
                query()
        rec.recording = False
        jax.profiler.stop_trace()
    finally:
        rec.uninstall()
    rows = {e[:5]: e[5] for e in progspans.load(log_dir)}
    for e in tracereduce.load(log_dir):
        rows.setdefault(e, {})
    shutil.rmtree(log_dir, ignore_errors=True)
    events = [[p, l, tracereduce.short_name(n) if tracereduce.is_device_plane(p)
               else n, s, d, st]
              for (p, l, n, s, d), st in sorted(rows.items(),
                                                key=lambda x: x[0][3])]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"about": "one grid_report query of trisolv at N=20, 11 "
                   "alphas, m=4, on one " + jax.devices()[0].device_kind
                   + ": device events, the benchmark's host spans and "
                   "the program's spans with their stats, op names "
                   "shortened",
                   "passes": [{k: p[k] for k in ("levels", "columns",
                                                 "bytes", "device")}
                              for p in rec.passes],
                   "events": events}, f, separators=(",", ":"))
    print(f"{len(events)} events -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        HERE, "data", "profile_trisolv_spans_v5e.json")))

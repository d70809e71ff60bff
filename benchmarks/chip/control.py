#!/usr/bin/env python3
"""The control of a cell's check: the reference, in a narrower precision,
put in the program's place.

    python3 benchmarks/chip/control.py --workload <cell> --queries <n> \\
        --seeds 11 12 13 [--precision bfloat16 float32]

For each seed it builds the cell's traces, takes the first ``n`` queries
of the seed's stream (as many as one run completes), draws the sample a
run draws, and answers it with ``reference.py`` computed in each
precision: every addition rounded to it.  It prints one JSON line per
seed and precision with the numbers ``check.py`` compares.  A sound
limit passes the program and fails the control.  Needs no device.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import check                                               # noqa: E402
import traffic as traffic_mod                              # noqa: E402
from run import Bench                                      # noqa: E402
from workload import Workload, points                      # noqa: E402


def readings(bench: Bench, name: str, seed: int, n_queries: int,
             precision: str) -> dict:
    cell = bench.cell(name)
    config = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    members = (list(mix["members"]) if mix.get("members")
               else list(config["traces"]))
    work = Workload(config, members, home=bench.home)
    qs = list(itertools.islice(traffic_mod.queries(mix, seed), n_queries))
    answers = [(q, None, points(q, None, work.names)) for q in qs]
    out = check.control(answers, work.inputs, mix["check"], seed,
                        float(config["unit"]), precision)
    return {"workload": name, "seed": seed, "precision": precision,
            "correct": out["correct"], "compared": out["compared"],
            **{k: v["value"] for k, v in out["numbers"].items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--queries", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", nargs="+",
                    default=["bfloat16", "float32"])
    args = ap.parse_args(argv)
    bench = Bench(os.path.join(ROOT, "BENCHMARK.json"))
    for seed in args.seeds:
        for p in args.precision:
            print(json.dumps(readings(bench, args.workload, seed,
                                      args.queries, p)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

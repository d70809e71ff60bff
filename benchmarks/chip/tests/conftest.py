"""Fixtures of the benchmark's CPU tests: a copy of the benchmark at tiny
sizes, run without the look for a chip."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: Sizes the CPU tests run at: the same kernels, much smaller traces.
TINY = {"polybench-paper15": ("N", 5)}


def stated_figures(kernel: str, N: int) -> dict:
    """A configuration's figures of one PolyBench trace, from the
    original per-element tracer."""
    import yardstick
    from repro.apps.reference import trace_kernel_ref
    g = trace_kernel_ref(kernel, N)
    g._finalize()
    return yardstick.trace_figures(g.n_vertices, g.is_mem, g.src, g.dst)


def make_bench(root, edit=None):
    """A benchmark tree under ``root``: BENCHMARK.json and a copy of this
    directory as ``bench/``, its configurations cut to ``TINY``.
    ``edit(spec, home)`` may change the spec and add files first."""
    home = os.path.join(root, "bench")
    shutil.copytree(BENCH, home,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["paths"] = ["bench"]
    for c in spec["configs"]:
        c["file"] = c["file"].replace(spec_home(), "bench")
        path = os.path.join(root, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        key, val = TINY[c["name"]]
        for name, t in cfg["traces"].items():
            t[key] = val
            cfg["figures"][name] = stated_figures(t["kernel"], val)
        with open(path, "w") as f:
            json.dump(cfg, f)
    if edit is not None:
        edit(spec, home)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(spec, f)
    import run
    return run.Bench(path)


def spec_home():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)["paths"][0]


@pytest.fixture
def cpu_env(monkeypatch, tmp_path):
    """The program's knobs as a run pins them, with caches in tmp."""
    for k in [k for k in os.environ if k.startswith("EDAN_")]:
        monkeypatch.delenv(k)
    monkeypatch.setenv("EDAN_SCHEDULE_CACHE", str(tmp_path / "sched"))
    return tmp_path


@pytest.fixture
def run_tiny(cpu_env, monkeypatch):
    """Run one cell of a tiny benchmark on the CPU, past the look for a
    chip: ``run_tiny(cell, trace=False, edit=None, seed=...)`` ->
    (result line, failures)."""
    import jax
    import run
    import yardstick

    def cpu_device(chips, peaks):
        d = jax.devices()
        return {"platform": d[0].platform, "kind": d[0].device_kind,
                "count": len(d)}
    monkeypatch.setattr(yardstick, "check_device", cpu_device)

    def go(cell, trace=False, edit=None, seed=2 ** 31 + 5, seconds=0.5):
        bench = make_bench(str(cpu_env / "tree"), edit)
        return run.run_cell(bench, cell, seed, seconds, trace,
                            cache=str(cpu_env / "cache"))
    return go

"""Tiny sizes for the on-chip benchmark's CPU tests (``chip/tests``) of
configurations whose traces are not PolyBench kernels.

Those tests build a copy of the benchmark in which every configuration
of ``BENCHMARK.json`` is cut to the size ``chip/tests/conftest.py`` lists
in ``TINY``, with its figures recomputed by that file's
``stated_figures(kernel, N)``, which knows only PolyBench kernels.  This
hook gives the HPCG configuration its tiny size (n = 4, the
configuration's iterations kept) and its figures from the original
per-element CG tracer, as the configuration file takes them at n = 16.
"""
import os

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP_TESTS = os.path.join(HERE, "chip", "tests", "conftest.py")

#: Tiny sizes of the non-PolyBench configurations: (trace key, value).
TINY = {"hpcg-cg16": ("n", 4)}


def pytest_plugin_registered(plugin, manager):
    path = getattr(plugin, "__file__", None)
    if not path or os.path.realpath(path) != os.path.realpath(CHIP_TESTS):
        return
    plugin.TINY.update(TINY)
    polybench = plugin.stated_figures

    def stated_figures(kernel, N):
        if kernel != "cg":
            return polybench(kernel, N)
        import json
        import yardstick
        from repro.apps.reference import trace_cg_ref
        with open(os.path.join(HERE, "chip", "configs",
                               "hpcg-cg16.json")) as f:
            iters = json.load(f)["traces"]["cg16"]["iters"]
        g, _ = trace_cg_ref(N, iters)
        g._finalize()
        return yardstick.trace_figures(g.n_vertices, g.is_mem, g.src, g.dst)
    plugin.stated_figures = stated_figures

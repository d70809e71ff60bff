"""HPCG's conjugate-gradient solve traced by the block tracer:
``{"kernel": "cg", "n", "iters"}`` -- the n^3 grid and the CG iterations."""


def build(params: dict):
    from repro.apps import hpcg
    if params["kernel"] != "cg":
        raise ValueError(f"no HPCG trace {params['kernel']!r}; known: cg")
    g, _ = hpcg.trace_cg(int(params["n"]), int(params["iters"]))
    return g

"""A PolyBench kernel traced by the scalar frontend: ``{"kernel", "N"}``."""


def build(params: dict):
    from repro.apps import polybench
    return polybench.trace_kernel(params["kernel"], int(params["N"]))

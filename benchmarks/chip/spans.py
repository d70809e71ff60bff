"""Host spans of a traced run, taken from the benchmark's own wrappers.

``spans.json`` lists program functions by module and attribute; in a
``--trace 1`` run each is replaced, for the run, by a wrapper that names
the call in the profiler's trace (``jax.profiler.TraceAnnotation``) and
adds its host time to its span.  The wrapper of ``replay_accumulate``
also records each pass: its levels, columns and algorithmic bytes, and
whether it ran on the device.  A listed function the program no longer
has is skipped: its span then reads nothing.  ``--trace 0`` runs install
nothing.
"""
from __future__ import annotations

import functools
import importlib
import json
import os
import time

import yardstick

HERE = os.path.dirname(os.path.abspath(__file__))
PREFIX = "bench."


class Recorder:
    """Span totals and replay passes of one traced run."""

    def __init__(self, targets: list):
        self.targets = targets
        self.seconds: dict = {}
        self.passes: list = []
        self.recording = False
        self._installed: list = []

    @classmethod
    def from_file(cls, path: str = os.path.join(HERE, "spans.json")):
        with open(path) as f:
            return cls(json.load(f)["wrap"])

    def install(self) -> None:
        for t in self.targets:
            owner = importlib.import_module(t["module"])
            *path, attr = t["attr"].split(".")
            for p in path:
                owner = getattr(owner, p, None)
            orig = getattr(owner, attr, None)
            if orig is None:
                continue
            self._installed.append((owner, attr, orig))
            setattr(owner, attr, self._wrap(orig, t["span"],
                                            t.get("pass", False)))

    def uninstall(self) -> None:
        for owner, attr, orig in reversed(self._installed):
            setattr(owner, attr, orig)
        self._installed.clear()

    def _wrap(self, fn, span: str, records_pass: bool):
        import jax
        from repro.core import backend

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not self.recording:
                return fn(*args, **kwargs)
            rec = None
            if records_pass:
                lv, F = args[0], args[1]
                rec = {"levels": max(int(lv.n_levels) - 1, 0),
                       "columns": int(F.shape[1]),
                       "bytes": yardstick.replay_bytes(lv, F.shape[1]),
                       "jax_chunks": backend.stats["jax_chunks"]}
            with jax.profiler.TraceAnnotation(PREFIX + span):
                t0 = time.perf_counter()
                try:
                    return fn(*args, **kwargs)
                finally:
                    dt = time.perf_counter() - t0
                    self.seconds[span] = self.seconds.get(span, 0.0) + dt
                    if rec is not None:
                        rec["seconds"] = dt
                        rec["device"] = (backend.stats["jax_chunks"]
                                         > rec.pop("jax_chunks"))
                        self.passes.append(rec)
        return wrapper

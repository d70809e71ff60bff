"""The benchmark's own checks and arithmetic, kept apart from the program.

Copied, not imported, from ``chip_smoke.py`` so that a later change to the
program cannot change the yardstick: the device check, the compile clock,
the gate on ``backend.stats``.  Beside them sit the peaks table, the
figures a configuration states of each trace, and the algorithmic bytes
of one replay pass.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class BenchError(RuntimeError):
    """The run cannot measure what the cell asks for (no chip, an unknown
    device, a malformed benchmark file)."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise BenchError(msg)


class CompileClock:
    """Seconds and events of XLA compilation, from jax's own monitoring
    events (``chip_smoke._CompileClock``, plus an event count)."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, duration: float, **_kw) -> None:
        if name.endswith("/backend_compile_duration"):
            self.seconds += duration
            self.events += 1


def load_peaks(path: str = os.path.join(HERE, "peaks.json")) -> dict:
    with open(path) as f:
        return json.load(f)


def peaks_for(device_kind: str, table: dict) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    dev = table["devices"].get(device_kind)
    require(dev is not None,
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(known: {sorted(table['devices'])})")
    return dev


def check_device(chips: int, peaks: dict) -> dict:
    """The devices this run measures (``chip_smoke.check_device``): jax's
    devices must be TPUs, at least ``chips`` of them, of a kind the peaks
    table knows, and the engine must pick the jax backend on its own."""
    import jax
    from repro.core import backend
    devs = jax.devices()
    d = devs[0]
    require(d.platform == "tpu",
            f"no TPU: jax's first device is {d.platform!r} "
            f"({d.device_kind}); this benchmark needs a TPU")
    require(len(devs) >= chips,
            f"the cell asks for {chips} chips, jax sees {len(devs)}")
    peaks_for(d.device_kind, peaks)
    require(backend.select_backend() == "jax",
            f"the engine selected the {backend.select_backend()!r} backend "
            "on a TPU host; expected 'jax'")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def device_memory_peak() -> int | None:
    """Peak bytes in use on the fullest local device, where the device
    reports it (the CPU does not)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


#: ``backend.stats`` counters that mark a replay which left the device.
OFF_DEVICE = ("numpy_chunks", "demoted_columns", "numpy_f64_passes")


def stats_gate(before: dict, after: dict) -> list:
    """Why a query's replay was not all on the device (``chip_smoke.
    _check_stats`` on a per-query delta): an empty list when every chunk
    ran on the device and none was demoted or routed to numpy."""
    d = {k: after[k] - before[k] for k in after}
    why = [f"{k}={d[k]}" for k in OFF_DEVICE if d.get(k)]
    if d.get("chunks", 0) == 0:
        why.append("no replay chunk was dispatched")
    elif d.get("jax_chunks", 0) != d["chunks"]:
        why.append(f"jax_chunks={d.get('jax_chunks', 0)} of "
                   f"chunks={d['chunks']}")
    return why


def trace_figures(n: int, is_mem, src, dst) -> dict:
    """What a configuration states of one trace: its vertex, memory-vertex
    and edge counts, and a digest of its flags and edge set that does not
    depend on the order the edges are held in."""
    import hashlib
    is_mem = np.asarray(is_mem, dtype=bool)
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    order = np.lexsort((dst, src))
    h = hashlib.sha256()
    h.update(np.int64(n).tobytes())
    h.update(is_mem.astype(np.uint8).tobytes())
    h.update(np.stack([src[order], dst[order]]).tobytes())
    return {"vertices": int(n), "mem_vertices": int(is_mem.sum()),
            "edges": int(len(src)), "sha256": h.hexdigest()}


def replay_bytes(lv, k: int, itemsize: int = 4) -> int:
    """Algorithmic bytes of one stacked replay pass over ``k`` columns,
    from the unpadded level partition: each predecessor edge reads its
    source row, each slot-chain link reads its queue predecessor's row,
    and each destination reads its base row and writes its finish row.
    Padding never counts, so the number is the same whatever implements
    the pass."""
    edges = len(lv.esrc)
    dsts = len(lv.run_dst)
    chain = 0
    if lv.qpred is not None:
        chain = int(np.count_nonzero(np.asarray(lv.qpred) < lv.n))
    if lv.qonly_dst is not None:
        dsts += len(lv.qonly_dst)
    return (edges + chain + 2 * dsts) * int(k) * itemsize

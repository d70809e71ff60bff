"""The device work of the level loop in a traced run.

The level loop is the program ``jax.jit`` builds from
``backend._level_loop``'s ``run``; the trace names it after that
function.  ``device_work`` pairs its device time with the levels and
algorithmic bytes the span wrapper recorded for the passes that ran on
the device.
"""
import tracereduce

#: Name of the level loop's program in the trace.
PROGRAM = r"^jit_run\b"


def device_work(run):
    """(device seconds, levels replayed, algorithmic bytes) of the level
    loop; zeros where the trace or the passes are missing."""
    if run.trace is None or run.recorder is None:
        return 0.0, 0, 0
    t = tracereduce.program_seconds(run.trace, PROGRAM) or 0.0
    dev = [p for p in run.recorder.passes if p["device"]]
    return t, sum(p["levels"] for p in dev), sum(p["bytes"] for p in dev)

"""Share of the device level loop's padded row slots that hold an edge:
100 x ``replay_edges`` / ``replay_slots`` of the backend's counters
(``backend.stats``), where a slot is one (level, row, predecessor) entry
of the padded gather tensors the loop walks.  The counters are the whole
process's, so they include set-up's warm-up queries, which run the same
plans as the window.  Nothing where the program keeps no such counters
or no pass of the traced window ran on the device."""


def read(run):
    from repro.core import backend
    rec = run.recorder
    if rec is None or not any(p["device"] for p in rec.passes):
        return None
    counts = backend.stats.snapshot()
    slots = counts.get("replay_slots")
    if not slots:
        return None
    return 100.0 * counts["replay_edges"] / slots

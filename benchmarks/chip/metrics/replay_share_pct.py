"""Share of the traced queries' wall time spent inside
``backend.replay_accumulate`` (host clock of the benchmark's wrapper);
the rest is the host stages above it."""


def read(run):
    rec = run.recorder
    total = sum(run.latencies)
    if rec is None or not total or "replay_accumulate" not in rec.seconds:
        return None
    return 100.0 * rec.seconds["replay_accumulate"] / total

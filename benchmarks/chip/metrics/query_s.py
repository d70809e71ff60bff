"""Wall seconds of the window over the queries completed in it (host
clock; the window closes at the first completion at or after
``--seconds``)."""


def read(run):
    if not run.latencies:
        return None
    return run.window_s / len(run.latencies)

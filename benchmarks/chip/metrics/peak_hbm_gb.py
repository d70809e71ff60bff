"""Peak device memory in use after the window, in GB (1e9 bytes), from
the device's own ``memory_stats()``."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9

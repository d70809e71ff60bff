"""Seconds from the start of the process to the first timed query:
imports, tracing the configuration, schedule load or record, compile or
cache load, and the warm-up queries (host clock)."""


def read(run):
    return run.setup_s

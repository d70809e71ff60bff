"""95th percentile (nearest rank) of every window query's latency, in
seconds (host clock)."""
import math


def read(run):
    lat = sorted(run.latencies)
    if not lat:
        return None
    return lat[math.ceil(0.95 * len(lat)) - 1]

"""Device microseconds per replayed level: the device time of the level
loop's program in the trace over the levels its passes replayed."""
import levelloop


def read(run):
    t, levels, _ = levelloop.device_work(run)
    if not t or not levels:
        return None
    return 1e6 * t / levels

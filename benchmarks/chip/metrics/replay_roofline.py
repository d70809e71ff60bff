"""Share of the HBM roofline the level loop reaches: the algorithmic
bytes of its passes (``yardstick.replay_bytes``, from the unpadded plan)
at the chip's peak bandwidth, over the loop's device time in the trace."""
import levelloop


def read(run):
    t, _, nbytes = levelloop.device_work(run)
    bw = run.peaks.get("hbm_bytes_per_s")
    if not t or not nbytes or not bw:
        return None
    return 100.0 * nbytes / bw / t

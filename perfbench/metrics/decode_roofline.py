"""Kernels: least time for the work the window's decode rounds need
(work.decode_round: weights once, each live slot's actual K/V) over their
device busy time, in percent."""
import work


def read(run):
    if not run.has_device_trace():
        return None
    calls = [(c, s, e) for c, s, e in run.traced_calls() if c.kind == "decode"]
    busy = sum(run.device_seconds(s, e) for _, s, e in calls)
    if not busy:
        return None
    least = sum(work.least_seconds(c.flops, c.bytes, run.peaks)
                for c, _, _ in calls)
    return 100.0 * least / busy

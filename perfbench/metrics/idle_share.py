"""Device: share of the traced window in which no operation ran."""


def read(run):
    if not run.has_device_trace():
        return None
    lo, hi = run.trace.window()
    return 100.0 * (1.0 - run.device_seconds(lo, hi) / ((hi - lo) / 1e9))

"""Whole step: model operations of the traced window's prefills and
decode rounds over their summed host-clock span time times the chip's
peak, in percent."""


def read(run):
    if not run.has_device_trace():
        return None
    calls = run.traced_calls()
    span = sum(e - s for _, s, e in calls) / 1e9
    if not span:
        return None
    return 100.0 * sum(c.flops for c, _, _ in calls) / (span
                                                        * run.peaks["flops"])

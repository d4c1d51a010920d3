"""Model step: device busy time (union of device-operation intervals)
inside each decode_round span, per round, from the profiler trace."""


def read(run):
    if not run.has_device_trace():
        return None
    spans = [(s, e) for c, s, e in run.traced_calls() if c.kind == "decode"]
    if not spans:
        return None
    return 1e3 * sum(run.device_seconds(s, e) for s, e in spans) / len(spans)

"""Model step: device busy time inside each refill span (prefill, fresh
cache and insert), per refilled slot, from the profiler trace."""


def read(run):
    if not run.has_device_trace():
        return None
    calls = [(c, s, e) for c, s, e in run.traced_calls() if c.kind == "refill"]
    n = sum(c.n for c, _, _ in calls)
    if not n:
        return None
    return 1e3 * sum(run.device_seconds(s, e) for _, s, e in calls) / n

"""Engine: host-clock time of a decode_round call."""


def read(run):
    calls = run.calls_in_window("decode")
    return 1e3 * sum(c.t1 - c.t0 for c in calls) / len(calls) if calls else None

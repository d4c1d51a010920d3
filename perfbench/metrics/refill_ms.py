"""Engine: host-clock time of admit_wave calls that refill slots, per
refilled slot (batch-1 prefill, fresh cache, insert, sampling)."""


def read(run):
    calls = run.calls_in_window("refill")
    n = sum(c.n for c in calls)
    return 1e3 * sum(c.t1 - c.t0 for c in calls) / n if n else None

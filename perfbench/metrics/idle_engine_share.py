"""Device: share of the traced window in which no device operation runs
while the host is inside an `engine.*` span and not in `engine.wait`: idle
time that the engine's own host work leaves exposed. idle_share less this
is idle between engine calls or while the host waits."""
import program_spans


def read(run):
    if not program_spans.in_window(run):
        return None
    lo, hi = run.trace.window()
    ivs = program_spans.exposed(run)
    busy = run.busy()
    idle = sum(sum(e - s - b.within(s, e) for s, e in ivs)
               for b in busy) / len(busy)
    return 100.0 * idle / (hi - lo)

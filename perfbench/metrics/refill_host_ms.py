"""Engine: the host's own work per refilled slot, from the program's
spans: the sum over `engine.refill` spans of the span less its
`engine.wait` children, over the slots the harness's traced refill calls
filled (so a refill that fills several slots in one span stays per
slot)."""
import program_spans


def read(run):
    refills = program_spans.in_window(run, "engine.refill")
    if not refills:
        return None
    n = sum(c.n for c, _, _ in run.traced_calls() if c.kind == "refill")
    if not n:
        return None
    return sum(program_spans.host_ms(sp) for sp in refills) / n

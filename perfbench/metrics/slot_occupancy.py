"""Scheduler: mean share of slots live over the window's decode rounds."""


def read(run):
    rounds = run.calls_in_window("decode")
    if not rounds:
        return None
    return 100.0 * sum(c.n for c in rounds) / (len(rounds) * run.B)

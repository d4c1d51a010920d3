"""Sampler: host time in `engine.sample` (key split, row gather, the
sampler's dispatch and its own syncs) inside `engine.decode` spans, per
round, in the traced window."""
import program_spans


def read(run):
    rounds = program_spans.in_window(run, "engine.decode")
    if not rounds:
        return None
    return sum(s.dur for sp in rounds for s in sp.kids("engine.sample")) \
        / len(rounds) / 1e6

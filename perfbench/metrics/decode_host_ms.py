"""Engine: the host's own work per decode round, from the program's spans:
mean over `engine.decode` spans of the span less its `engine.wait`
children, in the traced window."""
import program_spans


def read(run):
    rounds = program_spans.in_window(run, "engine.decode")
    if not rounds:
        return None
    return sum(program_spans.host_ms(sp) for sp in rounds) / len(rounds)

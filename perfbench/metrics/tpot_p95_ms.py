"""95th percentile over requests of each request's time per output token
inside the window."""
import harness
import serving_stats


def read(run):
    s = serving_stats.tpot_samples(run)
    return harness.percentile(s, 95) * 1e3 if s else None

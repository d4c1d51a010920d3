"""Output tokens emitted inside the window, over the window's seconds."""
import serving_stats


def read(run):
    return serving_stats.window_tokens(run) / (run.w1 - run.w0)

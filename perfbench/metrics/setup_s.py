"""Process start to the window's start: weights, warm-up and ramp."""


def read(run):
    return run.setup["setup_s"]

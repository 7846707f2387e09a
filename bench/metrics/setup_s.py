"""Process start to window open: weights, compile or cache load, warm-up
and the traffic's lead-in."""


def read(run):
    return run.setup_s

"""1 - (union of the device's op intervals) / (traced window), in
percent, from the profiler trace."""


def read(run):
    if not run.trace or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])

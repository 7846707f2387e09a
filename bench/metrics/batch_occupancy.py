"""Mean over the window's engine steps of the lanes the step ran over
the engine's lanes, in percent."""


def read(run):
    if not run.steps:
        return None
    return 100.0 * sum(len(s.spans) for s in run.steps) / (
        len(run.steps) * run.lanes)

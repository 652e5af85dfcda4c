"""Model step: device-busy time in the traced window over the scheduler
steps (of every node) the window ran."""


def read(run):
    steps = run.traced(run.steps)
    if run.trace is None or not steps:
        return None
    return run.trace["busy_s"] * 1e3 / len(steps)

"""Process start to the window's start: weights, cluster, compiles (or
cache loads) of every shape the traffic reaches, and the warm-up phase."""


def read(run):
    return run.setup_s

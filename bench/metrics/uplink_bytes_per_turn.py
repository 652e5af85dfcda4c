"""Client uplink bytes (``EdgeCluster.client_bytes_up``, framing included)
sent during the window, per turn sent in it: the paper's request size."""


def read(run):
    return run.counters["uplink_bytes"] / len(run.turns) if run.turns else None

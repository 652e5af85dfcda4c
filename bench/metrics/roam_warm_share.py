"""Migration: share of the window's answered roam turns (a session's first
turn on a node it had not used) whose KV prefix was warmed on arrival,
by shipped pages or by token recompute (``Timing.kv_warm_source``)."""


def read(run):
    roams = [t for t in run.turns if t.roam and t.ok]
    if not roams:
        return None
    warm = sum(t.response.timing.kv_warm_source in ("pages", "tokens") for t in roams)
    return warm / len(roams)

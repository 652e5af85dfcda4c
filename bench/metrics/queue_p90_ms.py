"""Scheduler: ``Timing.queue_ms`` (submit to slot admission, cluster
clock); 90th percentile over the window's turns (a failed turn misses)."""

from bench.stats import MISSED_MS, percentile


def read(run):
    return percentile([t.response.timing.queue_ms if t.ok else MISSED_MS
                       for t in run.turns], 90)

"""The paper's response time: ``Ticket.latency_ms``, from the client's send
to the response delivered at the client; median over every turn sent in
the window (a failed turn misses every limit)."""

from bench.stats import percentile


def read(run):
    return percentile([t.response_ms for t in run.turns], 50)

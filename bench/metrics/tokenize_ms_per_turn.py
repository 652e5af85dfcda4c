"""Context manager: mean ``Timing.tokenize_ms`` of the window's answered
turns (host time to tokenize the new prompt, laid onto the cluster clock)."""


def read(run):
    xs = [t.response.timing.tokenize_ms for t in run.turns if t.ok]
    return sum(xs) / len(xs) if xs else None

"""Scheduler: prompt tokens served from resident KV pages (session entry,
shared prefix or same-wave donor) over all prompt tokens of the window's
answered turns."""


def read(run):
    reused = sum(t.response.timing.kv_reused_tokens for t in run.turns if t.ok)
    fresh = sum(t.response.timing.prefill_tokens for t in run.turns if t.ok)
    return reused / (reused + fresh) if reused + fresh else None

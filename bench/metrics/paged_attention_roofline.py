"""Kernels: the paged decode attention kernel's lower-bound time (per layer
call, the larger of FLOPs over peak and bytes over bandwidth, counted by
the block from each traced step's lanes, summed over its layers; the
shared-prefix pass included) over its device time in the trace, in
percent."""

from bench import flops


def read(run):
    if run.trace is None:
        return None
    spent = run.trace["kernel_s"].get("paged_attention", 0.0)
    d, ps = run.dims, run.page_size
    need = 0.0
    for s in run.traced(run.steps):
        if s.kv_lens:
            calls = run.block.paged_attention_call(d, s.kv_lens, s.shared_pages, ps)
            need += flops.layers_seconds(calls, run.peaks)
    return 100.0 * need / spent if spent > 0 and need > 0 else None

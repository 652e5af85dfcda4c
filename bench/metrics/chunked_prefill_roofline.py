"""Kernels: the chunked-prefill attention kernel's lower-bound time (per
layer call, counted by the block from each traced chunk's length and
offset, summed over its layers) over its device time in the trace, in
percent."""

from bench import flops


def read(run):
    if run.trace is None:
        return None
    spent = run.trace["kernel_s"].get("chunked_prefill", 0.0)
    d, ps = run.dims, run.page_size
    need = sum(flops.layers_seconds(run.block.chunked_prefill_call(d, c.c, c.p0, ps),
                                    run.peaks)
               for c in run.traced(run.chunks))
    return 100.0 * need / spent if spent > 0 and need > 0 else None

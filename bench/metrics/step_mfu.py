"""Model step: model FLOPs of every token processed in the traced window
(prefill chunks and decode lanes, counted by the block from the
configuration's shapes) over the window's length times the chip's bf16
peak, in percent."""


def read(run):
    if run.trace is None:
        return None
    d, block = run.dims, run.block
    total = sum(block.token_flops(d, n, logits=True)
                for s in run.traced(run.steps) for n in s.kv_lens)
    total += sum(block.chunk_flops(d, c.c, c.p0) for c in run.traced(run.chunks))
    if total == 0:
        return None
    return 100.0 * total / (run.trace["window_s"] * run.peaks["bf16_flops_per_s"])

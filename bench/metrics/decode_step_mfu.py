"""Useful FLOPs of the decode steps (every row one token) over their
device time and the chip's bf16 peak: the whole decode step's share,
which bounds what the paged-decode kernel's roofline share can buy."""
from bench.work import step_flops


def read(ctx):
    steps = [s for s in (ctx.tr.steps if ctx.tr else []) if s.decode]
    dev = sum(s.end - s.start for s in steps)
    if dev <= 0:
        return None
    flops = sum(step_flops(ctx.w.spec, s.spans) for s in steps)
    return 100.0 * flops / (dev * ctx.peak["bf16_flops"])

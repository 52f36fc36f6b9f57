"""Paged-decode attention kernel: the least time the chip needs for the
attention of the decode steps' tokens (bench work function: K and V of
each token's context, q and the output) over the kernel's device time."""
from bench.work import attn_bytes, attn_flops, roofline_seconds


def read(ctx):
    steps = [s for s in (ctx.tr.steps if ctx.tr else [])
             if s.decode and s.kernel_s > 0]
    if not steps:
        return None
    spec = ctx.w.spec
    need = sum(roofline_seconds(
        sum(attn_flops(spec, n, p) for n, p, _ in s.spans),
        sum(attn_bytes(spec, n, p) for n, p, _ in s.spans), ctx.peak)
        for s in steps)
    return 100.0 * need / sum(s.kernel_s for s in steps)

"""Chunked-prefill attention kernel: the least time the chip needs for
the causal attention of the mixed steps' rows (bench work function) over
the kernel's device time."""
from bench.work import attn_bytes, attn_flops, roofline_seconds


def read(ctx):
    steps = [s for s in (ctx.tr.steps if ctx.tr else [])
             if not s.decode and s.kernel_s > 0]
    if not steps:
        return None
    spec = ctx.w.spec
    need = sum(roofline_seconds(
        sum(attn_flops(spec, n, p) for n, p, _ in s.spans),
        sum(attn_bytes(spec, n, p) for n, p, _ in s.spans), ctx.peak)
        for s in steps)
    return 100.0 * need / sum(s.kernel_s for s in steps)

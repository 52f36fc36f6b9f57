"""Useful model FLOPs of the steps run in the traced window (the bench's
work function over the prompt and output tokens they served) over the
window's length, the chips and their bf16 peak."""
from bench.work import step_flops


def read(ctx):
    tr = ctx.tr
    if tr is None or not tr.steps or tr.window_s <= 0:
        return None
    flops = sum(step_flops(ctx.w.spec, s.spans) for s in tr.steps)
    return 100.0 * flops / (tr.window_s * ctx.chips * ctx.peak["bf16_flops"])

"""99th percentile of the gaps between consecutive output tokens whose
later token falls in the window (host clock).  Above the knee the few
steps that admit a request into a decode batch set it, so it is read
beside the end-to-end metrics and not bounded."""
from bench.endtoend import window_numbers


def read(ctx):
    return window_numbers(ctx.w)["tbt_p99_ms"]

"""Mean device time of a mixed step (some row longer than one token)."""


def read(ctx):
    steps = [s for s in (ctx.tr.steps if ctx.tr else []) if not s.decode]
    if not steps:
        return None
    return 1e3 * sum(s.end - s.start for s in steps) / len(steps)

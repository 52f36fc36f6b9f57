"""Mean number of rows (requests) in a step dispatched in the window."""


def read(ctx):
    w = ctx.w
    rows = [len(s.spans) for s in w.steps
            if w.open_t <= s.t - w.epoch <= w.close_t]
    return sum(rows) / len(rows) if rows else None

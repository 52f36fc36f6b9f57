"""Share of the traced window with no operation running on the chip."""


def read(ctx):
    tr = ctx.tr
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)

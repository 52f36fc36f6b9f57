"""Share of the token positions the steps dispatched in the window
computed that is padding: 100 x (1 - real tokens / (slots x T)), from
the ``engine.dispatch`` spans' ``tokens``, ``slots`` and ``T``."""
from bench import spans


def read(ctx):
    win = spans.window(ctx)
    steps = win.named("engine.dispatch") if win else []
    positions = sum(s.stats["slots"] * s.stats["T"] for s in steps)
    if not positions:
        return None
    return 100.0 * (1.0 - sum(s.stats["tokens"] for s in steps) / positions)

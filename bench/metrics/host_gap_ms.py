"""Median host time between steps, from the program's own spans: the end
of step k's ``engine.collect`` (its logits on the host) to the end of
step k+1's ``engine.launch`` (the next step enqueued), over consecutive
steps both dispatched in the window, k+1 after k was collected.  Prints
first, unjudged, the window's device idle time by program span."""
import statistics

from bench import spans


def read(ctx):
    win = spans.window(ctx)
    if win is None:
        return None
    print(spans.idle_line(win), flush=True)
    gaps = spans.host_gaps(win)
    return 1e3 * statistics.median(gaps) if gaps else None

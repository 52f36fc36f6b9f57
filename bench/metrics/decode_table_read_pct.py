"""Share of the block tables' width that the decode steps dispatched in
the window read: 100 x sum of ``pages`` / sum of ``slots`` x ``n_pp``.
A decode step's ``engine.tables`` span carries ``pages``, the sum over
the step's rows of ceil(length / page), the pages the paged-decode
kernel streams, and ``n_pp``, the table's width; ``slots`` comes from
the ``engine.dispatch`` span around it.  ``slots`` x ``n_pp`` is what a
kernel that walks every slot's whole table visits."""
import bisect

from bench import spans


def read(ctx):
    win = spans.window(ctx)
    if win is None:
        return None
    disp = win.named("engine.dispatch")
    starts = [d.start for d in disp]
    pages = table = 0
    for t in win.named("engine.tables"):
        i = bisect.bisect_right(starts, t.start) - 1
        if "pages" not in t.stats or i < 0 or disp[i].end < t.end:
            continue
        pages += t.stats["pages"]
        table += disp[i].stats["slots"] * t.stats["n_pp"]
    if not table:
        return None
    return 100.0 * pages / table

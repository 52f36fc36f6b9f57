"""End-to-end metrics of a window, from per-request timestamps on the
session's clock (host wall clock, seconds since the load started).

- TTFT: over every request due in the window, first token - due time; a
  request with no first token by the close enters at close - due.
- TBT: every gap between consecutive output tokens of any request whose
  later token falls in the window.
- Output tokens/s: tokens emitted in the window / the window's length.

The session's own ``SessionMetrics.goodput`` divides by the whole run
with its drain; nothing here reads it.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def window_numbers(w) -> Dict[str, float]:
    lo, hi = w.open_t, w.close_t
    due = [a for a in w.arrivals if lo <= a.due < hi]
    ttft, met, failed = [], 0, 0
    ttft_lim = w.mix["ttft_limit_ms"] / 1e3
    tbt_lim = w.mix["tbt_limit_ms"] / 1e3
    for a in due:
        st = w.states.get(a.rid)
        if st is None or st.rejected or st.cancelled:
            failed += 1
            ttft.append(hi - a.due)
            continue
        times = [t for t in st.token_times if t <= hi]
        first = times[0] if times else hi
        ttft.append(first - a.due)
        worst_gap = max(np.diff(times), default=0.0)
        if times and first - a.due <= ttft_lim and worst_gap <= tbt_lim:
            met += 1
    gaps, n_out = [], 0
    for st in w.states.values():
        t = np.asarray(st.token_times)
        n_out += int(np.sum((t >= lo) & (t <= hi)))
        if len(t) > 1:
            g = np.diff(t)
            gaps.extend(g[(t[1:] >= lo) & (t[1:] <= hi)])
    ttft, gaps = np.asarray(ttft), np.asarray(gaps)
    pct = lambda x, q: float(np.percentile(x, q)) * 1e3 if len(x) else None
    return {
        "attempted": len(due), "failed": failed,
        "ttft_p50_ms": pct(ttft, 50), "ttft_p90_ms": pct(ttft, 90),
        "ttft_n": len(ttft),
        "tbt_p50_ms": pct(gaps, 50), "tbt_p99_ms": pct(gaps, 99),
        "tbt_n": len(gaps),
        "output_tok_s": n_out / (hi - lo),
        "met_both_limits": met / len(due) if due else None,
    }

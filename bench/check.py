"""What decides ``correct``: the served tokens against the plain reference.

After the window closes and the program's state is dropped, a sample of
requests drawn from the seed (always with the one of longest context,
and finished requests before unfinished ones) is run once through the
float32 reference over prompt + served tokens.  Every served token was
greedy, so each should be the reference's best token up to rounding;
the number compared is the widest gap, over all sampled tokens, by which
a served token's reference logit lies below the reference's best logit
at its position.  The limit comes from ``bench/limits/<cell>.json``.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List, Optional

import numpy as np

from bench import reference

LIMITS = pathlib.Path(__file__).resolve().parent / "limits"
TARGET_TOKENS = 300      # served tokens compared per run
MAX_REQUESTS = 12
MAX_CONTEXT = 65536      # prompt + served tokens the reference runs over


def load_limit(cell: str) -> float:
    path = LIMITS / f"{cell}.json"
    if not path.is_file():
        raise SystemExit(f"bench: no limits file {path}")
    return float(json.loads(path.read_text())["max_logit_gap"])


def sample(w, seed: int) -> List[str]:
    """Request ids to compare: the longest-context served request, then
    others in an order drawn from the seed, finished ones first, until
    ``TARGET_TOKENS`` served tokens or ``MAX_REQUESTS`` requests; one
    that would take the reference past ``MAX_CONTEXT`` tokens is passed
    over, so the check stays shorter than the window."""
    prompt = {a.rid: len(a.prompt) for a in w.arrivals}
    served = [rid for rid, t in w.served.items() if len(t) > 0]
    if not served:
        return []
    longest = max(served, key=lambda r: (prompt[r] + len(w.served[r]), r))
    rng = np.random.default_rng([seed, 1])
    rest = [served[i] for i in rng.permutation(len(served))
            if served[i] != longest]
    rest.sort(key=lambda r: not w.finished.get(r, False))  # stable
    ctx = lambda r: prompt[r] + len(w.served[r])
    out, n, total = [longest], len(w.served[longest]), ctx(longest)
    for rid in rest:
        if n >= TARGET_TOKENS or len(out) >= MAX_REQUESTS:
            break
        if total + ctx(rid) > MAX_CONTEXT:
            continue
        out.append(rid)
        n += len(w.served[rid])
        total += ctx(rid)
    return out


def compare(spec, seed: int, prompts: List[np.ndarray],
            served: List[np.ndarray],
            quant: Optional[str] = None) -> Dict[str, float]:
    """The widest reference-logit gap of the ``served`` tokens after
    their ``prompts``.  With ``quant``, also the widest gap of the tokens
    that precision would put first at the same positions (the control's
    reading)."""
    hid = reference.final_hidden(spec, seed, prompts, served)
    gaps, _ = reference.logit_gaps(spec, seed, hid, served)
    out = {"max_logit_gap": float(max(g.max() for g in gaps)),
           "tokens": int(sum(len(o) for o in served)),
           "requests": len(served),
           "longest_context": int(max(len(p) + len(o)
                                      for p, o in zip(prompts, served)))}
    if quant is not None:
        hq = reference.final_hidden(spec, seed, prompts, served, quant)
        _, firsts = reference.logit_gaps(spec, seed, hq, served, quant)
        cg, _ = reference.logit_gaps(spec, seed, hid, firsts)
        out[f"max_logit_gap_{quant}"] = float(max(g.max() for g in cg))
    return out

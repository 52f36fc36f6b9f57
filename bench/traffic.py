"""The one traffic generator: reads ``bench/traffic/<mix>.json``.

A mix file gives request classes (a weight and lognormal prompt and
output lengths, each by its median and sigma, clipped), Poisson arrivals
at a fixed rate, and how long load runs before the window opens
(``preload_s``).  ``initial_population`` (optional) puts that many
requests in at time 0 that stand for requests already part-way through
their outputs, as in steady state above the knee: request i of n is
(i + 0.5) / n of the way through its output, and the tokens it would
already have generated join its prompt.  So the window opens on full
slots with contexts spread as the steady state spreads them, after a
preload of prefill instead of minutes of decoding.

The generator follows the shapes of the program's
``repro.data.workloads`` (lognormal around the paper's modes, Poisson
arrivals), copied here so that the yardstick cannot move with the
program.

Every seed gets the same work in the same order: the lengths are the
lognormal's quantiles at (i + 0.5) / n and the inter-arrival gaps the
exponential's, shuffled by one fixed draw, and the seed draws only the
prompt token ids (and, with the weights, what each token is).  Runs
with different seeds then do the same work on different data, so their
timings differ by the machine's noise, not by which long requests
happen to share the slots.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from statistics import NormalDist
from typing import List

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: str
    due: float               # seconds after the load starts
    prompt: np.ndarray       # token ids
    output_len: int


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"bench: no traffic file {path}")
    return json.loads(path.read_text())


def _quantiles(n: int, median: float, sigma: float, lo: int, hi: int):
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def _draw(mix: dict, n: int, rng) -> list:
    """n (prompt, output) lengths: the classes in proportion to their
    weights, each a grid of lognormal quantiles, in an order drawn from
    ``rng``."""
    weights = np.array([c["weight"] for c in mix["classes"]], float)
    counts = np.floor(n * weights / weights.sum()).astype(int)
    counts[: n - counts.sum()] += 1
    p_lo, p_hi = mix["prompt_clip"]
    d_lo, d_hi = mix["output_clip"]
    lengths = []
    for c, k in zip(mix["classes"], counts):
        if k == 0:
            continue
        p = _quantiles(k, c["prompt"]["median"], c["prompt"]["sigma"],
                       p_lo, p_hi)
        d = _quantiles(k, c["output"]["median"], c["output"]["sigma"],
                       d_lo, d_hi)
        lengths += list(zip(rng.permutation(p), rng.permutation(d)))
    return [lengths[j] for j in rng.permutation(len(lengths))]


def generate(mix: dict, seed: int, horizon: float, vocab: int,
             max_context: int) -> List[Arrival]:
    """The initial population due at 0, then ``rate * horizon`` requests
    due in [0, horizon)."""
    order = np.random.default_rng(0)     # the same for every seed
    rate = float(mix["rate_per_s"])
    n_init = int(mix.get("initial_population", 0))
    n = max(1, int(math.ceil(rate * horizon)))
    init = _draw(mix, n_init, order)
    sized = [(0.0, p + int(d * (i + 0.5) / n_init),
              d - int(d * (i + 0.5) / n_init))
             for i, (p, d) in enumerate(init)]
    u = (np.arange(n) + 0.5) / n
    gaps = order.permutation(-np.log1p(-u) / rate)
    # scale so the last request is due just inside the horizon
    due = np.cumsum(gaps) * (horizon * (1 - 0.5 / n) / gaps.sum())
    sized += [(float(t), p, d)
              for t, (p, d) in zip(due, _draw(mix, n, order))]
    rng = np.random.default_rng(seed)
    out = []
    for i, (t, p, d) in enumerate(sized):
        # the deployment's context bound: a prompt leaves room for its
        # output
        p = int(min(p, max_context - d))
        out.append(Arrival(f"r{i}", t,
                           rng.integers(0, vocab, p).astype(np.int32),
                           int(d)))
    return out

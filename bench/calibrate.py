"""Readings that the check's limits are set from (not run by the benchmark).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 30

In one process, for each seed: one run of the cell as the benchmark runs
it (``run.measure``: the same window, traffic and load), with the check's
control in the program's place: the reference computed with fp8 (e4m3)
operands, the tokens it puts first judged by the same rule and limit
against the float32 reference.  Each run prints the program's own
reading (the lower reading) on an earlier line and its result line, whose
``checked`` holds the control's reading (the upper reading) and whose
``correct`` must be false.  The limits in ``bench/limits/<cell>.json``
and their readings are in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU found", file=sys.stderr)
        return 1
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run
    run.use_compile_cache(jax)
    from bench.check import load_limit
    from bench.model import load_spec
    from bench.traffic import load_mix

    bench, cell = run._cell(args.workload)
    spec, mix = load_spec(cell["config"]), load_mix(cell["traffic"])
    limit = load_limit(cell["name"])
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.measure(bench, cell, seed, args.seconds, 0, spec, mix,
                          limit, control="fp8", t_start=time.monotonic())
        print("READING", seed, json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

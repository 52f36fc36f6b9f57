"""Benchmark entry: one run of one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuses to run without a TPU (no CPU fallback).  Sets up the cell's
served path (``bench.harness``), measures a window of ``--seconds``,
checks the served tokens against the plain reference (``bench.check``)
and prints one JSON line last on standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checked``: each number
compared beside its limit.  Earlier lines carry what is printed but not
judged.

JAX's persistent compilation cache lives in ``JAX_COMPILATION_CACHE_DIR``
when that is set, else in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _cell(name: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        if w["name"] == name:
            return bench, w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def _wanted(entries, cell: str):
    return [m for m in entries if cell in m.get("workloads", [cell])]


def _reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def use_compile_cache(jax) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``<checkout>/.jax_cache`` (made here: JAX
    does not make it, and fails every write without it).  Every program
    is kept, however quick its compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell = _cell(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU found (JAX platform {devs[0].platform!r}); "
              f"this benchmark runs on the chip only", file=sys.stderr)
        return 1
    if len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
              f"sees {len(devs)}", file=sys.stderr)
        return 1
    print(f"device_kind {devs[0].device_kind!r}, {len(devs)} device(s)",
          flush=True)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    use_compile_cache(jax)

    from bench.check import load_limit
    from bench.model import load_spec
    from bench.traffic import load_mix
    spec = load_spec(cell["config"])
    if spec.chips != cell["chips"]:
        print(f"bench: {args.workload} asks for {cell['chips']} chips, its "
              f"configuration deploys on {spec.chips}", file=sys.stderr)
        return 1
    out = measure(bench, cell, args.seed, args.seconds, args.trace, spec,
                  load_mix(cell["traffic"]), load_limit(cell["name"]))
    print(json.dumps(out), flush=True)
    return 0


def measure(bench: dict, cell: dict, seed: int, seconds: float, traced: int,
            spec, mix: dict, limit: float, fault=None, control=None,
            t_start: float = T_START) -> dict:
    """Everything of a run after the device check: the window, the
    cell's metrics, the check; returns the result line as a dict.
    ``fault`` (tests only) is passed to ``harness.run_cell``.  With
    ``control`` ("fp8"; ``bench/calibrate.py`` and tests only) the check
    judges, in the served tokens' place, the tokens that the reference
    computed in that precision puts first at the same positions."""
    import jax
    from bench import check, endtoend, harness, trace
    from bench.work import peaks

    devs = jax.devices()
    dev = devs[0]
    trace_dir = None
    if traced:
        trace_dir = ROOT / ".bench_trace" / f"{cell['name']}-{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir = str(trace_dir)

    w = harness.run_cell(spec, mix, seed, seconds, t_start,
                         trace_dir=trace_dir, fault=fault)
    e2e = endtoend.window_numbers(w)
    print(f"window {seconds:g} s after {mix['preload_s']:g} s of load: "
          f"{e2e['attempted']} requests due, {e2e['failed']} failed; "
          f"ttft_p90_ms {e2e['ttft_p90_ms']} over {e2e['ttft_n']}; "
          f"tbt over {e2e['tbt_n']} gaps; share meeting both limits "
          f"{e2e['met_both_limits']}; compiles in window "
          f"{w.compiles_in_window}; alpha->beta handoff bytes "
          f"{w.handoff_bytes}; steps {len(w.steps)}", flush=True)
    late = sorted(w.admitted[a.rid] - a.due for a in w.arrivals
                  if a.rid in w.admitted and w.open_t <= a.due < w.close_t)
    if late:
        print(f"generator lateness (arrival taken in - due): median "
              f"{late[len(late) // 2] * 1e3:.1f} ms, max "
              f"{late[-1] * 1e3:.1f} ms", flush=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": w.memory_peak_bytes}
    metrics, breakdown, ctx = {}, None, None
    if traced:
        peak = peaks(dev.device_kind)
        tr = trace.reduce(w)
        device["busy_s"], device["window_s"] = tr.busy_s, tr.window_s
        breakdown = tr.breakdown
        ctx = trace.Context(w=w, tr=tr, peak=peak, chips=spec.chips)
        for m in _wanted(bench["per_layer"], cell["name"]):
            v = _reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        e2e["setup_s"] = w.setup_s
        for m in _wanted(bench["end_to_end"], cell["name"]):
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    rids = check.sample(w, seed)
    prompts = {a.rid: a.prompt for a in w.arrivals}
    ps, os_ = [prompts[r] for r in rids], [w.served[r] for r in rids]
    del w, ctx
    gc.collect()                 # the program's state goes before the check
    reading = check.compare(spec, seed, ps, os_, control) if rids else None
    gap = None
    if reading:
        print(f"checked {reading['tokens']} served tokens of "
              f"{reading['requests']} requests (longest context "
              f"{reading['longest_context']}) against the float32 "
              f"reference", flush=True)
        gap = reading["max_logit_gap"]
        if control:
            print(f"control {control}: the program's max_logit_gap {gap}; "
                  f"the control's judged in its place", flush=True)
            gap = reading[f"max_logit_gap_{control}"]
    print(f"max_logit_gap {gap} limit {limit}", file=sys.stderr, flush=True)
    out = {"correct": gap is not None and gap <= limit,
           "attempted": e2e["attempted"], "failed": e2e["failed"],
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checked"] = {"max_logit_gap": {"value": gap, "limit": limit}}
    return out


if __name__ == "__main__":
    sys.exit(main())

"""The window: set up the served path, drive it open-loop, read it back.

``run_cell`` is the whole of one run after the device check: it builds
the configuration's model with weights from the seed, the program's
``EngineBackend`` + ``ServeSession`` (one instance, the DynaServe
policy, the cell's TBT limit), warms the step programs the traffic
reaches, runs the traffic through ``ServeSession.run`` with the window
opening ``preload_s`` after the load starts, and returns what the window
saw.  The correctness check (``bench.check``) runs after it, once the
program's state is dropped.

Around each call into ``InstanceEngine.dispatch_batch`` the harness
records the step's spans (tokens, positions, sampled or not) and, in a
traced run, a host annotation ``bench_step`` with the step's sequence
number on the profiler's clock.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional

import numpy as np

import jax

from bench.compile_clock import CompileClock
from bench.model import ModelSpec, program_config
from bench.traffic import Arrival, generate
from bench.weights import check_layout, make_params

FAULTS = ("state_unchanged", "half_batch", "token_altered")
TRACE_LEAD_S = 2.0       # the profiler starts this long before the window


@dataclasses.dataclass
class StepRecord:
    t: float                 # host clock (time.monotonic) at dispatch
    T: int                   # longest row of the step, in tokens
    spans: list              # (n, start, sampled) per batch row


@dataclasses.dataclass
class WindowResult:
    spec: ModelSpec
    mix: dict
    seconds: float
    open_t: float            # window open, session clock
    close_t: float
    epoch: float             # session clock 0 on the host clock
    arrivals: List[Arrival]
    states: Dict[str, object]        # rid -> the session's ReqState
    served: Dict[str, np.ndarray]    # rid -> tokens the program served
    finished: Dict[str, bool]
    admitted: Dict[str, float]       # rid -> session took the arrival in
    steps: List[StepRecord]
    setup_s: float
    compiles_in_window: int
    memory_peak_bytes: int
    handoff_bytes: int
    trace_dir: Optional[str] = None
    trace_t0: float = 0.0    # the window on the host clock
    trace_t1: float = 0.0


def warm_shapes(eng, arrivals: List[Arrival]):
    """The (T, n_pp) step programs the window can reach: every chunk
    bucket T up to the longest prompt chunk, times the page-table buckets
    n_pp up to the longest context the traffic holds, where a chunk of
    bucket T fits the table.  With an initial population (requests due at
    0) the table, as wide as the longest context held, stays at least as
    wide as the shortest of theirs while any of them is held, which is
    the whole window (their outputs outlast it); narrower tables are left
    to the preload, which compiles what it meets on its way there."""
    from repro.engine.runner import bucket_of
    longest_chunk = max(min(len(a.prompt), eng.max_chunk) for a in arrivals)
    longest_ctx = max(len(a.prompt) + a.output_len for a in arrivals)
    t_max = bucket_of(longest_chunk, eng.buckets)
    p_max = bucket_of(math.ceil(longest_ctx / eng.page_size),
                      eng.page_buckets)
    initial = [len(a.prompt) for a in arrivals if a.due == 0.0]
    p_min = bucket_of(math.ceil(min(initial) / eng.page_size),
                      eng.page_buckets) if initial else 1
    return [(T, n) for n in eng.page_buckets if p_min <= n <= p_max
            for T in eng.buckets
            if T <= t_max and n * eng.page_size > T // 2]


def warm(eng, shapes) -> None:
    """Run each step program once through the engine's own entry: one
    row whose chunk falls in bucket T and ends where the block table is
    n_pp pages wide, on a scratch slot freed afterwards."""
    from repro.engine.runner import BatchItem
    for T, n_pp in shapes:
        end = n_pp * eng.page_size
        n = min(T, end)
        slot = eng.alloc("bench-warm")
        eng.run_batch([BatchItem(slot, np.zeros(n, np.int32), end - n,
                                 True)])
        eng.free(slot)


class _Observer:
    """When the session took in each arrival (its first transition)."""

    def __init__(self):
        self.admitted: Dict[str, float] = {}

    def on_transition(self, req, old, new, now):
        if old == "queued" and req.rid not in self.admitted:
            self.admitted[req.rid] = now


def _instrument(eng, steps: List[StepRecord], at_dispatch, fault):
    """Wrap the engine's dispatch/collect: record each step, annotate it
    for the profiler when tracing, and plant a fault when asked."""
    dispatch, collect = eng.dispatch_batch, eng.collect_batch

    def spans_of(items):
        return [(len(it.tokens), int(it.pos_offset), bool(it.want_logits))
                for it in items]

    def wrapped_dispatch(items):
        if not items:
            return dispatch(items)
        k = len(steps)
        T = max(len(it.tokens) for it in items)
        steps.append(StepRecord(time.monotonic(), T, spans_of(items)))
        before = eng.cache
        if at_dispatch():
            with jax.profiler.TraceAnnotation("bench_step", seq=k):
                h = dispatch(items)
        else:
            h = dispatch(items)
        if fault == "state_unchanged":
            eng.cache = before
        return h

    def wrapped_collect(handle):
        if at_dispatch():
            with jax.profiler.TraceAnnotation("bench_collect"):
                out = collect(handle)
        else:
            out = collect(handle)
        if fault == "half_batch" and len(out) > 1:
            slots = sorted(out)
            for a, b in zip(slots[0::2], slots[1::2]):
                out[b] = out[a]
        elif fault == "token_altered":
            for s in out:
                lg = np.array(out[s])
                lg[(int(np.argmax(lg)) + 1) % lg.shape[0]] = lg.max() + 1.0
                out[s] = lg
        return out

    eng.dispatch_batch = wrapped_dispatch
    eng.collect_batch = wrapped_collect


def run_cell(spec: ModelSpec, mix: dict, seed: int, seconds: float,
             t_start: float, trace_dir: Optional[str] = None,
             fault: Optional[str] = None) -> WindowResult:
    """One run of a cell up to the window's close (see the module doc).
    ``t_start`` is the host clock at process start; ``fault`` (tests
    only) breaks the timed path underneath, one of ``FAULTS``."""
    from repro.core.request import Request
    from repro.core.session import ServeSession, SessionConfig
    from repro.engine.backend import EngineBackend
    from repro.sim.policies import DynaServePolicy

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    if spec.instances != 1 or spec.chips != 1:
        raise SystemExit(f"bench: {spec.name} deploys {spec.instances} "
                         f"instance(s) on {spec.chips} chip(s); the harness "
                         f"runs one instance on one chip")
    clock = CompileClock()
    dev = jax.devices()[0]
    cfg = program_config(spec)
    params = make_params(spec, seed)
    check_layout(params, cfg)
    jax.block_until_ready(params)
    t_weights = time.monotonic()

    preload = float(mix["preload_s"])
    arrivals = generate(mix, seed, preload + seconds, spec.vocab,
                        spec.max_context)
    slo = mix["tbt_limit_ms"] / 1e3
    backend = EngineBackend(cfg, params, n_slots=spec.n_slots,
                            max_len=spec.max_context, n_pages=spec.n_pages)
    del params
    session = ServeSession(backend, DynaServePolicy(backend.cost, slo),
                           SessionConfig(n_instances=1, slo=slo,
                                         open_loop=True,
                                         max_sim_time=preload + seconds))
    eng = backend.engines[0]
    shapes = warm_shapes(eng, arrivals)
    warm(eng, shapes)
    jax.block_until_ready(eng.cache)
    print(f"set-up: weights ready {t_weights - t_start:.1f} s after start; "
          f"{len(shapes)} step programs warmed in "
          f"{time.monotonic() - t_weights:.1f} s ({clock.compiles} "
          f"compiles, {clock.hits} persistent-cache hits, "
          f"{clock.seconds:.1f} s in compile events); {len(arrivals)} "
          f"requests over {preload:g} s of load and the window", flush=True)

    obs = _Observer()
    session.observers.append(obs)
    steps: List[StepRecord] = []
    win = {"epoch": 0.0, "open": False, "c_open": 0, "trace": False}

    def at_dispatch():
        """Called before each step: opens the window at the first step
        due after it, and starts the trace TRACE_LEAD_S before that, so
        the profiler's own start-up stall falls outside the window; True
        while tracing."""
        now = time.monotonic() - win["epoch"]
        if (trace_dir is not None and not win["trace"]
                and now >= preload - TRACE_LEAD_S):
            # no Python tracer: it would slow every host call; the
            # harness's annotations need only the host tracer's level 1
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level, opts.host_tracer_level = 0, 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            win["trace"] = True
        if not win["open"] and now >= preload:
            win["open"], win["c_open"] = True, clock.compiles
        return win["trace"]

    _instrument(eng, steps, at_dispatch, fault)
    reqs = []
    for a in arrivals:
        r = Request(a.rid, a.due, len(a.prompt), a.output_len)
        r.prompt_tokens = a.prompt
        reqs.append(r)
    win["epoch"] = time.monotonic()
    session.run(reqs)
    jax.block_until_ready(eng.cache)
    close_host = time.monotonic()
    if win["trace"]:
        jax.profiler.stop_trace()
    in_window = clock.compiles - win["c_open"] if win["open"] else 0
    stats = dev.memory_stats() or {}
    states = dict(session.req_states)
    return WindowResult(
        spec=spec, mix=mix, seconds=seconds, open_t=preload,
        close_t=preload + seconds, epoch=win["epoch"], arrivals=arrivals,
        states=states,
        served={rid: np.asarray(rec.generated, np.int32)
                for rid, rec in backend.records.items()},
        finished={rid: st.done_at is not None for rid, st in states.items()},
        admitted=obs.admitted, steps=steps,
        setup_s=win["epoch"] + preload - t_start,
        compiles_in_window=int(in_window),
        memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)),
        handoff_bytes=int(backend.kv_bytes_moved),
        trace_dir=trace_dir if win["trace"] else None,
        # the traced window is the measured one: the session runs on past
        # the close through the events already queued before it
        trace_t0=win["epoch"] + preload,
        trace_t1=min(close_host, win["epoch"] + preload + seconds))

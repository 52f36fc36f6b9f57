"""The program's profiler spans and the readers of ``bench/spans.py``:
real traces of the engine and of a served session on the CPU at the
smoke size, a synthetic trace for the three per-layer metrics and the
device idle put down to spans, and the spans' cost with no trace."""
import contextlib
import sys

import numpy as np
import pytest

import jax

import bench_smoke
from bench import spans
from bench import trace as bench_trace
from bench.harness import _instrument
from bench.trace import Context, Event

from repro.configs import get_smoke_config
from repro.core.request import Request
from repro.core.session import ServeSession, SessionConfig
from repro.engine import BatchItem, InstanceEngine
from repro.engine.backend import EngineBackend
from repro.engine.runner import bucket_of
from repro.models.model import init_params
from repro.sim.policies import DisaggregationPolicy
from repro.utils import spans as program_spans

MS = 1_000_000                      # ns
DEV = "/device:TPU:0"
SESSION_NAMES = {"session.arrival", "session.start", "session.compose",
                 "session.batch_done", "session.sleep", "backend.build",
                 "backend.sample", "engine.dispatch", "engine.tables",
                 "engine.launch", "engine.collect", "engine.wait",
                 "engine.fetch", "engine.export", "engine.import"}


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_smoke_config("qwen2.5-14b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _traced(path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    return jax.profiler.trace(str(path), profiler_options=opts)


def _inside(child, parent):
    return parent.start <= child.start and child.end <= parent.end


def test_engine_steps_in_a_real_trace(smoke_model, tmp_path):
    """Two steps through the harness's wrappers: the engine's spans nest,
    number the steps in the order of the harness's ``bench_step`` and
    describe each batch."""
    cfg, params = smoke_model
    eng = InstanceEngine(cfg, params, n_slots=4, max_len=64)
    assert eng.paged
    a, b = eng.alloc("a"), eng.alloc("b")
    rng = np.random.default_rng(0)
    pa, pb = (rng.integers(0, cfg.vocab_size, n).astype(np.int32)
              for n in (10, 6))
    eng.run_batch([BatchItem(a, pa[:1], 0)])          # a step before tracing
    steps = []
    _instrument(eng, steps, lambda: True, None)
    with _traced(tmp_path):
        eng.run_batch([BatchItem(a, pa[1:], 1, True),
                       BatchItem(b, pb, 0, True)])
        eng.run_batch([BatchItem(a, pa[:1], 10, True),
                       BatchItem(b, pb[:1], 6, True)])
    got = spans.load(str(tmp_path)).spans
    by = {}
    for s in got:
        by.setdefault(s.name, []).append(s)
    assert {n: len(v) for n, v in by.items()} == {
        "engine.dispatch": 2, "engine.tables": 2, "engine.launch": 2,
        "engine.collect": 2, "engine.wait": 2, "engine.fetch": 2}
    disp = by["engine.dispatch"]
    for d, t, l, c, wt, f in zip(disp, by["engine.tables"],
                                 by["engine.launch"], by["engine.collect"],
                                 by["engine.wait"], by["engine.fetch"]):
        assert _inside(t, d) and _inside(l, d) and t.end <= l.start
        assert _inside(wt, c) and _inside(f, c) and c.start >= d.end
        assert c.stats["seq"] == d.stats["seq"]
        assert l.stats["new_program"] in (0, 1)
    assert [d.stats["seq"] for d in disp] == [1, 2]
    # the harness's annotation around each dispatch, in the same order
    marks = sorted((e for e in bench_trace.load(str(tmp_path))
                    if e.name == "bench_step"), key=lambda e: e.start_ns)
    assert [dict(e.stats)["seq"] for e in marks] == [0, 1]
    for e, d in zip(marks, disp):
        assert e.start_ns * 1e-9 <= d.start and \
            d.end <= (e.start_ns + e.duration_ns) * 1e-9
    first, second = (d.stats for d in disp)
    assert first == {"seq": 1, "T": bucket_of(9, eng.buckets), "slots": 4,
                     "tokens": 15}
    assert second == {"seq": 2, "T": 1, "slots": 4, "tokens": 2}
    assert [s.T for s in steps] == [9, 1]
    assert [sum(n for n, _, _ in s.spans) for s in steps] == [15, 2]


def test_served_session_in_a_real_trace(smoke_model, tmp_path):
    """A served session with a prefill/decode split over two instances:
    every program span name is a documented one, each request starts
    once, the handoff's export and import carry its bytes, and the
    session clock on the session spans places a window on the trace."""
    cfg, params = smoke_model
    be = EngineBackend(cfg, params, n_slots=4, max_len=64)
    sess = ServeSession(be, DisaggregationPolicy(), SessionConfig(
        n_instances=2, slo=0.1, open_loop=True, overlap=False))
    rng = np.random.default_rng(1)
    reqs = []
    for i in range(3):
        r = Request(f"r{i}", 0.05 * i, 12, 4)
        r.prompt_tokens = rng.integers(0, cfg.vocab_size, 12)
        reqs.append(r)
    with _traced(tmp_path):
        sess.run(reqs)
    got = spans.load(str(tmp_path)).spans
    names = {s.name for s in got}
    assert names <= SESSION_NAMES
    assert {"session.arrival", "session.start", "session.compose",
            "session.batch_done", "backend.build", "backend.sample",
            "engine.export", "engine.import"} <= names
    starts = [s for s in got if s.name == "session.start"]
    assert sorted(s.stats["rid"] for s in starts) == ["r0", "r1", "r2"]
    assert all(s.stats["wait_us"] >= 0 and s.end == pytest.approx(
        s.start, abs=1e-3) for s in starts)
    arrivals = [s for s in got if s.name == "session.arrival"]
    assert [s.stats["rid"] for s in arrivals] == ["r0", "r1", "r2"]
    assert all(s.stats["late_us"] >= 0 for s in arrivals)
    for name in ("engine.export", "engine.import"):
        assert all(s.stats["bytes"] > 0 and s.stats["rid"]
                   for s in got if s.name == name)
    # the session clock on session spans: one offset to the trace clock
    zero = [s.start - s.stats["now_us"] * 1e-6 for s in got
            if "now_us" in s.stats]
    assert max(zero) - min(zero) < 0.5

    class W:
        epoch, trace_t0, trace_t1 = 0.0, 0.0, 60.0
    win = spans.place(W(), spans.load(str(tmp_path)))
    disp = [s for s in got if s.name == "engine.dispatch"]
    assert win.named("engine.dispatch") == disp


def test_collect_waits_apart_only_while_tracing(smoke_model, tmp_path):
    """Untraced, ``collect_batch`` copies the logits and nothing more;
    traced, it waits for the step under its own span first."""
    cfg, params = smoke_model
    eng = InstanceEngine(cfg, params, n_slots=4, max_len=64)
    a = eng.alloc("a")
    waits = []

    class Logits:
        def __init__(self, x):
            self.x = np.asarray(x)

        def block_until_ready(self):
            waits.append(1)
            return self

        def __array__(self, dtype=None, copy=None):
            return self.x

    prompt = np.arange(1, 5, dtype=np.int32)
    h = eng.dispatch_batch([BatchItem(a, prompt[:3], 0, True)])
    h.logits = Logits(h.logits)
    assert set(eng.collect_batch(h)) == {a} and waits == []
    h = eng.dispatch_batch([BatchItem(a, prompt[3:], 3, True)])
    h.logits = Logits(h.logits)
    with _traced(tmp_path):
        assert set(eng.collect_batch(h)) == {a}
    assert waits == [1]
    names = [s.name for s in spans.load(str(tmp_path)).spans]
    assert names == ["engine.collect", "engine.wait", "engine.fetch"]


def test_no_span_stats_without_a_trace(smoke_model, monkeypatch):
    """With no profiler running no site builds a stat: every span is the
    shared no-op, given a name alone, and the zero-length markers that
    build theirs up front are never reached."""
    cfg, params = smoke_model
    calls = []

    def span(name, **stats):
        calls.append((name, stats))
        return contextlib.nullcontext()

    monkeypatch.setattr(program_spans, "span", span)
    assert not program_spans.active()
    be = EngineBackend(cfg, params, n_slots=4, max_len=64)
    sess = ServeSession(be, DisaggregationPolicy(), SessionConfig(
        n_instances=2, slo=0.1, open_loop=True, overlap=False))
    reqs = [Request(f"r{i}", 0.02 * i, 10, 3) for i in range(2)]
    sess.run(reqs)
    assert all(not stats for _, stats in calls)
    names = {n for n, _ in calls}
    assert {"session.arrival", "session.compose", "session.batch_done",
            "backend.build", "backend.sample", "engine.dispatch",
            "engine.launch", "engine.collect", "engine.export",
            "engine.import"} <= names
    assert "session.start" not in names


# ---------------------------------------------------------------------------
# a synthetic window: spans on the session thread, ops on the chip
# ---------------------------------------------------------------------------
def ev(name, start_ms, dur_ms, line="python", **stats):
    plane = DEV if line.startswith("XLA") else "/host:CPU"
    return Event(plane, line, name, start_ms * MS + 500e9, dur_ms * MS,
                 tuple(stats.items()))


def step(seq, t, T, tokens, work_ms, slots=4):
    """A step's host spans from t (ms): build, dispatch (tables, launch),
    then collect (wait until the device is done, fetch), sample; the
    device runs from the launch's end for work_ms."""
    dev0 = t + 2.0
    return [
        ev("backend.build", t - 1.0, 1.0, seq=seq),
        ev("engine.dispatch", t, 2.0, seq=seq, T=T, slots=slots,
           tokens=tokens),
        ev("engine.tables", t + 0.2, 0.8),
        ev("engine.launch", t + 1.0, 1.0, new_program=0),
        ev("engine.collect", t + 2.0, work_ms + 1.0, seq=seq),
        ev("engine.wait", t + 2.0, work_ms),
        ev("engine.fetch", t + 2.0 + work_ms, 1.0),
        ev("backend.sample", t + 3.0 + work_ms, 0.5, seq=seq),
        ev("%fusion.1 = f(%x)", dev0, work_ms, line="XLA Ops"),
    ]


def synthetic():
    # session clock 0 is trace 500 s; the window is session 100-200 ms
    evs = [ev("session.compose", 98.0, 1.0, seq=7, now_us=98_000),
           ev("session.arrival", 99.0, 0.5, rid="r1", now_us=99_000,
              late_us=4_000),
           ev("session.start", 99.2, 0.0, rid="r1", seq=7, wait_us=4_200),
           ev("session.start", 99.3, 0.0, rid="r0", seq=7, wait_us=90_000)]
    evs += step(7, 101.0, 512, 513, 20.0)       # launch ends 103
    # collect 103-124; the host is idle under no span 124.5-130
    evs += [ev("session.batch_done", 124.5 + 5.5, 2.0, seq=7,
               now_us=130_000)]
    evs += step(8, 133.0, 1, 3, 10.0)           # launch ends 135
    evs += step(9, 150.0, 1, 3, 10.0)           # launch ends 152
    evs += [ev("session.start", 160.0, 0.0, rid="r2", seq=10,
               wait_us=12_000)]
    return evs


class W:
    epoch = 0.0
    trace_t0, trace_t1 = 0.100, 0.200
    open_t, close_t = 0.100, 0.200
    trace_dir = "synthetic-spans"
    arrivals = [type("A", (), {"rid": r, "due": d})()
                for r, d in (("r0", 0.05), ("r1", 0.105), ("r2", 0.150))]


def _ctx(w=None):
    spans._CACHE[W.trace_dir] = spans.from_events(synthetic())
    return Context(w=w or W(), tr=object(), peak={}, chips=1)


def _read(name, w=None):
    sys.path.insert(0, str(bench_smoke.ROOT / "bench"))
    import run
    return run._reader(name)(_ctx(w))


def test_window_placed_by_the_session_clock():
    win = spans.window(_ctx())
    assert win.t0 == pytest.approx(500.100) and \
        win.t1 == pytest.approx(500.200)
    assert [s.stats["seq"] for s in win.named("engine.dispatch")] == \
        [7, 8, 9]


def test_host_gap_ms_reads_collect_to_next_launch(capsys):
    # step 7 collected at 124, step 8 enqueued at 135: 11 ms; step 8
    # collected at 146, step 9 enqueued at 152: 6 ms
    assert _read("host_gap_ms") == pytest.approx(8.5)
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("idle_by_program_span ")]
    assert len(line) == 1


def test_step_pad_pct_counts_padded_positions():
    # 513 + 3 + 3 real of 4 x 512 + 4 + 4 computed
    assert _read("step_pad_pct") == pytest.approx(
        100 * (1 - 519 / 2056))


def test_queue_wait_ms_over_requests_due_in_the_window():
    # r0 was due before the window and is left out
    assert _read("queue_wait_ms") == pytest.approx(8.1)


def test_queue_wait_ms_counts_a_request_not_started_at_the_close():
    # r3, due at 190 ms, has no session.start: it enters at the close
    # (10 ms), so a longer queue cannot lower the reading by leaving its
    # slowest requests out
    class Late(W):
        arrivals = W.arrivals + [type("A", (), {"rid": "r3",
                                                "due": 0.190})()]
    assert _read("queue_wait_ms", Late()) == pytest.approx(10.0)

    # a start recorded after the close counts at the close too
    class Early(W):
        close_t = 0.155
    assert _read("queue_wait_ms", Early()) == pytest.approx(
        (4.2 + 5.0) / 2)


def test_queue_wait_ms_is_absent_with_no_request_due_in_the_window():
    class Quiet(W):
        arrivals = W.arrivals[:1]           # r0, due before the window
    assert _read("queue_wait_ms", Quiet()) is None


def test_readers_find_nothing_in_a_program_without_spans(capsys):
    """A traced run of a program that writes no spans (an older commit):
    each reader returns nothing, raises nothing and prints no idle
    line."""
    class Bare(W):
        trace_dir = "synthetic-no-spans"
    spans._CACHE[Bare.trace_dir] = spans.from_events(
        [e for e in synthetic() if e.line == "XLA Ops"])
    sys.path.insert(0, str(bench_smoke.ROOT / "bench"))
    import run
    ctx = Context(w=Bare(), tr=object(), peak={}, chips=1)
    for name in ("host_gap_ms", "step_pad_pct", "queue_wait_ms"):
        assert run._reader(name)(ctx) is None
    assert "idle_by_program_span" not in capsys.readouterr().out


def test_idle_put_down_to_innermost_span():
    idle = spans.idle_by_span(spans.window(_ctx()))
    by = idle["by_span_s"]
    # device busy 103-123, 135-145, 152-162 ms of the 100-200 ms window
    assert idle["idle_s"] == pytest.approx(0.060)
    assert idle["steps"] == 3
    ms = {k: round(v * 1e3, 6) for k, v in by.items()}
    # each step: build 1, dispatch's own 0.2, tables 0.8, launch 1 before
    # the device starts; fetch 1 and sample 0.5 after it ends
    assert ms == {"backend.build": 3.0, "engine.dispatch": 0.6,
                  "engine.tables": 2.4, "engine.launch": 3.0,
                  "engine.fetch": 3.0, "backend.sample": 1.5,
                  "session.batch_done": 2.0}
    # 124.5-130, 146.5-149 and 163.5-200 ms: the host under no span
    assert idle["uncovered_s"] == pytest.approx(0.0445)
    assert idle["covered_pct"] == pytest.approx(100 * 15.5 / 60)

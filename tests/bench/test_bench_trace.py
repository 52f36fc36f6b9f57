"""The trace reduction on a small synthetic trace: device busy time as a
union, steps matched to the harness's annotations, kernel time per step,
and idle gaps named by what the host was doing."""
import sys

import pytest

import bench_smoke  # noqa: F401
from bench.harness import StepRecord
from bench.trace import Event, reduce

DEV = "/device:TPU:0"
MS = 1_000_000                      # ns


class W:
    """The parts of a WindowResult the reduction reads."""
    def __init__(self):
        # host clock = trace clock - 1000 s
        self.steps = [StepRecord(1000.000 - 1000, 1, [(1, 40, True)]),
                      StepRecord(1000.010 - 1000, 512,
                                 [(512, 0, True), (1, 41, True)])]
        self.trace_t0, self.trace_t1 = 0.0, 0.050


def ev(line, name, start_ms, dur_ms, **stats):
    plane = DEV if line.startswith("XLA") else "/host:CPU"
    return Event(plane, line, name, start_ms * MS + 1000e9, dur_ms * MS,
                 tuple(stats.items()))


def events():
    return [
        ev("python", "bench_step", 0.0, 1.0, seq=0),
        ev("python", "bench_collect", 1.5, 5.5),
        ev("python", "bench_step", 10.0, 1.0, seq=1),
        ev("python", "bench_collect", 11.0, 20.0),
        # decode step on the chip 2-6 ms, prefill step 12-30 ms
        ev("XLA Modules", "jit_step_body(7)", 2.0, 4.0),
        ev("XLA Modules", "jit_step_body(9)", 12.0, 18.0),
        # the scan over layers holds the step's ops
        ev("XLA Ops", "%while.1 = (s32[]) while(%tuple), body=%b", 2.0, 4.0),
        ev("XLA Ops", "%fusion.1 = bf16[4] fusion(%x)", 2.0, 1.0),
        ev("XLA Ops", "%paged_decode_attention_op.3 = bf16[4] custom-call("
           '%a), custom_call_target="tpu_custom_call"', 3.0, 2.0),
        ev("XLA Ops", "%fusion.1 = bf16[4] fusion(%x)", 4.5, 1.5),
        ev("XLA Ops", "%custom-call.9 = bf16[9] custom-call(), "
           'custom_call_target="AllocateBuffer"', 12.0, 0.5),
        ev("XLA Ops", "%fusion.2 = bf16[4] fusion(%y)", 12.0, 8.0),
        ev("XLA Ops", "%chunked_prefill_attention_op.5 = bf16[4] "
           "custom-call(%q)", 20.0, 10.0),
        ev("XLA Ops", "%copy.4 = bf16[4] copy(%z)", 40.0, 5.0),
    ]


def test_busy_steps_kernels_gaps():
    r = reduce(W(), events())
    assert r.window_s == pytest.approx(0.050)
    # union: 2-6, 12-30, 40-45 ms
    assert r.busy_s == pytest.approx(0.004 + 0.018 + 0.005)
    assert [(s.seq, s.decode) for s in r.steps] == [(0, True), (1, False)]
    assert r.steps[0].end - r.steps[0].start == pytest.approx(0.004)
    assert r.steps[0].kernel_s == pytest.approx(0.002)
    assert r.steps[1].kernel_s == pytest.approx(0.010)
    gaps = dict((round(s * 1e3, 6), n) for n, s in r.breakdown["idle_gaps"])
    # 0-2 ms half under a dispatch; 6-12 ms only a third under collects
    assert gaps == {2.0: "host:build_and_dispatch_step",
                    6.0: "host:session_loop", 10.0: "host:session_loop",
                    5.0: "host:session_loop"}
    ops = dict(r.breakdown["device_ops"])
    assert "while" not in ops
    assert ops["paged_decode_attention_op"] == pytest.approx(0.002)
    assert ops["chunked_prefill_attention_op"] == pytest.approx(0.010)
    assert ops["fusion"] == pytest.approx(0.0105)
    assert ops["copy"] == pytest.approx(0.005)


def test_step_outside_window_is_left_out():
    w = W()
    w.trace_t1 = 0.020                 # the prefill step ends after it
    r = reduce(w, events())
    assert [s.seq for s in r.steps] == [0]


def _per_layer():
    import json
    return [m["name"] for m in json.loads(
        (bench_smoke.ROOT / "BENCHMARK.json").read_text())["per_layer"]]


@pytest.mark.parametrize("name", _per_layer())
def test_every_per_layer_metric_has_a_reader(name):
    """The harness finds each per-layer metric's reader by its name."""
    sys.path.insert(0, str(bench_smoke.ROOT / "bench"))
    import run
    assert callable(run._reader(name))

"""Unit tests for DynaServe's core: micro-requests, Algorithm 1 binary
search, Algorithm 2 budgets, the execution predictor, and chunked KV
transfer."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (
    A100, BatchCostModel, ExecutionPredictor, GlobalScheduler, LocalScheduler,
    QueuedWork, Request, plan_chunked_transfer, split_request,
)
from repro.core.costmodel import WorkItem
from repro.core.global_scheduler import InstanceView
from repro.core.kv_transfer import monolithic_exposed
from repro.core.local_scheduler import DecodeWork, PrefillWork


@pytest.fixture(scope="module")
def cost():
    return BatchCostModel(get_config("qwen2.5-14b"), A100)


# ---------------- hardware table ----------------
@pytest.mark.parametrize("kind,spec", [("TPU v5 lite", "TPU-v5e"),
                                       ("cpu", "A100-80G")])
def test_hardware_for_known_device_kinds(kind, spec):
    from repro.core.costmodel import hardware_for
    assert hardware_for(kind).name == spec


def test_hardware_for_unknown_device_kind_raises():
    from repro.core.costmodel import hardware_for
    with pytest.raises(ValueError, match="no hardware spec"):
        hardware_for("TPU v4")


# ---------------- micro-requests ----------------
def test_split_special_cases():
    r = Request("r", 0.0, 100, 100)
    a, b = split_request(r, 0.0)
    assert a is None and b.n_tokens == 200            # pure colocation on beta
    a, b = split_request(r, 1.0)
    assert b is None and a.n_tokens == 200
    a, b = split_request(r, 0.5)                       # PD-disagg boundary
    assert a.prefill_tokens == 100 and a.decode_tokens == 0
    assert b.prefill_tokens == 0 and b.decode_tokens == 100


def test_split_mixed_segments():
    r = Request("r", 0.0, 100, 300)
    a, b = split_request(r, 0.75)        # s=300 > P: alpha carries decode
    assert a.prefill_tokens == 100 and a.decode_tokens == 200
    assert b.prefill_tokens == 0 and b.decode_tokens == 100
    a, b = split_request(r, 0.125)       # s=50 < P: beta finishes prefill
    assert a.prefill_tokens == 50 and a.decode_tokens == 0
    assert b.prefill_tokens == 50 and b.decode_tokens == 300
    assert b.needs_kv_handoff and b.handoff_tokens == 50


# ---------------- cost model ----------------
def test_cost_model_roofline_regimes(cost):
    # decode-only batches are memory-bound; prefill chunks compute-bound
    dec = [WorkItem("decode", 1, 2048)] * 16
    pre = [WorkItem("prefill", 2048, 0)]
    t_dec_c = cost.flops(dec) / (cost.hw.peak_flops * cost.hw.mfu_cap)
    t_dec_m = cost.bytes_moved(dec) / (cost.hw.hbm_bw * cost.hw.bw_eff)
    assert t_dec_m > t_dec_c
    t_pre_c = cost.flops(pre) / (cost.hw.peak_flops * cost.hw.mfu_cap)
    t_pre_m = cost.bytes_moved(pre) / (cost.hw.hbm_bw * cost.hw.bw_eff)
    assert t_pre_c > t_pre_m
    # paper Table 1: 2048-token chunk of a 14B model costs ~350ms on A100
    assert 0.2 < cost.latency(pre) < 0.6


def test_max_prefill_inversion_is_tight(cost):
    for dnum, ctx in [(0, 0), (8, 1024), (32, 4096), (64, 8192)]:
        m = cost.max_prefill_tokens(0.1, dnum, ctx)
        if m > 0:
            assert cost.mixed_batch_latency(m, 0, dnum, ctx) <= 0.105
            assert cost.mixed_batch_latency(int(m * 1.3) + 64, 0, dnum, ctx) > 0.1


# ---------------- predictor ----------------
def test_predictor_monotone_in_load(cost):
    pred = ExecutionPredictor(cost)
    base = [QueuedWork("a", 1000, 200, 1000)]
    t1 = pred.drain_time(base)
    t2 = pred.drain_time(base + [QueuedWork("b", 2000, 300, 1500)])
    assert t2 > t1 > 0


def test_predictor_decode_dominates_when_long(cost):
    pred = ExecutionPredictor(cost)
    short = pred.drain_time([QueuedWork("a", 0, 50, 512)])
    long_ = pred.drain_time([QueuedWork("a", 0, 500, 512)])
    assert long_ > short * 5


# ---------------- Algorithm 1 ----------------
def test_global_scheduler_balances(cost):
    gs = GlobalScheduler(cost, margin_tokens=0)
    # instance 0 heavily loaded -> alpha should shrink (phi below P/L)
    q0 = [QueuedWork("x", 8000, 100, 4000)]
    q1 = []
    r = Request("r", 0.0, 2048, 512)
    pl = gs.schedule(r, [InstanceView(0, q0), InstanceView(1, q1)])
    # pair picking routes alpha to the idle instance
    assert pl.alpha_instance == 1
    rel_gap = abs(pl.predicted_t1 - pl.predicted_t2) / max(
        pl.predicted_t1, pl.predicted_t2)
    assert rel_gap < 0.25
    assert pl.probes <= 6


def test_global_scheduler_cold_start_is_pd_split(cost):
    gs = GlobalScheduler(cost, margin_tokens=0)
    r = Request("r", 0.0, 1000, 1000)
    pl = gs.schedule(r, [InstanceView(0, []), InstanceView(1, [])])
    assert abs(pl.phi - 0.5) < 1e-6
    assert pl.probes == 0


def test_scheduling_overhead_under_20ms(cost):
    gs = GlobalScheduler(cost)
    q0 = [QueuedWork(f"a{i}", 500, 100, 1000) for i in range(64)]
    q1 = [QueuedWork(f"b{i}", 0, 300, 2000) for i in range(64)]
    r = Request("r", 0.0, 2048, 512)
    # best-of-3: wall time, robust to CI-box CPU contention
    best = min(gs.schedule(r, [InstanceView(0, q0),
                               InstanceView(1, q1)]).overhead_s
               for _ in range(3))
    # paper Table 3 budget is <20 ms (their C++ impl, idle box); this
    # single-core CI container runs tests under heavy contention, so
    # assert a loose 50 ms here — benchmarks/tab3 reports the real means
    assert best < 0.050


# ---------------- Algorithm 2 ----------------
def test_local_scheduler_respects_budget(cost):
    ls = LocalScheduler(cost, slo=0.1)
    pq = [PrefillWork(f"p{i}", 700, 0) for i in range(8)]
    dq = [DecodeWork(f"d{i}", 2048) for i in range(16)]
    plan = ls.next_batch(pq, dq)
    assert plan.dnum == 16                       # all decodes admitted
    assert plan.predicted_latency <= 0.1 * 1.02
    m = ls.max_prefill_allowed(2048, 16)
    assert plan.prefill_tokens <= m


def test_local_scheduler_profile_feedback(cost):
    ls = LocalScheduler(cost, slo=0.1)
    pq = [PrefillWork("p", 4000, 0)]
    dq = [DecodeWork("d", 1024)] * 8
    plan = ls.next_batch(pq, dq)
    ls.record(plan, measured=plan.predicted_latency * 1.1)
    assert ls.profile.records == 1
    assert ls.profile.lookup(plan.prefill_tokens, 1024, 8) is not None


def test_static_chunk_mode_ignores_slo(cost):
    ls = LocalScheduler(cost, slo=0.1, slo_aware=False, static_chunk=2048)
    assert ls.max_prefill_allowed(8192, 64) == 2048


# ---------------- chunked KV transfer ----------------
def test_chunked_transfer_overlaps(cost):
    plan = plan_chunked_transfer(cost, 8192, 512)
    mono = monolithic_exposed(cost, 8192)
    assert plan.exposed < 0.15 * mono       # paper §6.6: ~94% hidden
    assert plan.n_chunks == 16
    # chunks are sent in order and cover all bytes
    assert plan.total_bytes >= cost.kv_bytes_per_tok * 8192
    for (s1, e1), (s2, e2) in zip(plan.timeline, plan.timeline[1:]):
        assert s2 >= s1 and e2 >= e1


def test_transfer_zero_tokens(cost):
    plan = plan_chunked_transfer(cost, 0)
    assert plan.exposed == 0.0 and plan.n_chunks == 0

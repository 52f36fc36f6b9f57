"""Sharded multi-device instances: TP/EP correctness, shard-aware
handoff, width-aware cost model / controller / placement identity.

Single-device cases always run.  Multi-device cases need >= 2 XLA
devices — the CI ``shard-tests`` job provides them with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``; under the
default one-device tier-1 run they skip.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.costmodel import A100, BatchCostModel
from repro.engine import BatchItem, InstanceEngine
from repro.models.model import init_params

MOE = "qwen3-moe-30b-a3b"
DENSE = "qwen2.5-14b"

multi = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 XLA devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def _greedy(eng, slot, prompt, n):
    out = eng.run_batch([BatchItem(slot, prompt, 0, want_logits=True)])
    first_logits = np.asarray(out[slot])
    toks = [int(first_logits.argmax())]
    pos = len(prompt)
    for _ in range(n - 1):
        out = eng.run_batch([BatchItem(slot, np.array([toks[-1]], np.int32),
                                       pos, want_logits=True)])
        toks.append(int(out[slot].argmax()))
        pos += 1
    return toks, first_logits


# ---------------------------------------------------------------------------
# sharded execution correctness (multi-device)
# ---------------------------------------------------------------------------
@multi
@pytest.mark.parametrize("name", [MOE, DENSE])
def test_tp_logits_match_single_device(name):
    """A TP=2 (EP=2 on the MoE arch) instance must produce the same
    logits and greedy tokens as the unsharded reference."""
    cfg = get_smoke_config(name)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, 24).astype(np.int32)

    ref = InstanceEngine(cfg, params, n_slots=2, max_len=96)
    ref_toks, ref_logits = _greedy(ref, ref.alloc("r"), prompt, 6)

    tp = InstanceEngine(cfg, params, n_slots=2, max_len=96,
                        devices=jax.devices()[:2])
    assert tp.tp == 2
    toks, logits = _greedy(tp, tp.alloc("r"), prompt, 6)
    np.testing.assert_allclose(logits, ref_logits, atol=2e-4, rtol=2e-4)
    assert toks == ref_toks


@multi
@pytest.mark.parametrize("src_tp,dst_tp", [(2, 1), (1, 2)])
def test_handoff_across_shard_widths(src_tp, dst_tp):
    """export_state gathers shards into the portable piece format, so a
    handoff crosses widths (TP=2 -> TP=1 and back) without drift."""
    cfg = get_smoke_config(MOE)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(1).integers(
        0, cfg.vocab_size, 24).astype(np.int32)

    ref = InstanceEngine(cfg, params, n_slots=2, max_len=96)
    ref_toks, _ = _greedy(ref, ref.alloc("r"), prompt, 7)

    def make(tp):
        devs = jax.devices()[:tp] if tp > 1 else None
        return InstanceEngine(cfg, params, n_slots=2, max_len=96,
                              devices=devs)

    A, B = make(src_tp), make(dst_tp)
    sa = A.alloc("r")
    A.run_batch([BatchItem(sa, prompt[:16], 0)])
    pieces = A.export_state(sa, upto=16, chunk=8)
    sb = B.alloc("r")
    B.import_state(sb, pieces)
    out = B.run_batch([BatchItem(sb, prompt[16:], 16, want_logits=True)])
    toks = [int(out[sb].argmax())]
    pos = len(prompt)
    for _ in range(6):
        out = B.run_batch([BatchItem(sb, np.array([toks[-1]], np.int32),
                                     pos, want_logits=True)])
        toks.append(int(out[sb].argmax()))
        pos += 1
    assert toks == ref_toks


@multi
def test_moe_ep_routing_equivalence():
    """moe_fwd under an expert-sharded shard_map (each shard owning a
    contiguous expert slice) must reproduce the full-expert output: the
    replicated router/capacity ranking means all shards agree on the
    dispatch, and the combine psum sums each token exactly once."""
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.models.layers import moe_fwd
    from repro.models.tp import tp_context

    cfg = get_smoke_config(MOE)
    E, dm, ff = cfg.moe_experts, cfg.d_model, cfg.moe_d_ff
    assert E % 2 == 0
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    p = {"router": jax.random.normal(k[0], (dm, E), jnp.float32) * 0.02,
         "wi": jax.random.normal(k[1], (E, dm, ff), jnp.float32) * 0.02,
         "wg": jax.random.normal(k[2], (E, dm, ff), jnp.float32) * 0.02,
         "wo": jax.random.normal(k[3], (E, ff, dm), jnp.float32) * 0.02}
    x = jax.random.normal(k[4], (2, 8, dm), jnp.float32)

    y_ref, aux_ref = moe_fwd(p, x, cfg)

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    p_specs = {"router": P(), "wi": P("model"), "wg": P("model"),
               "wo": P("model")}

    def body(p, x):
        with tp_context("model"):
            return moe_fwd(p, x, cfg)

    y, aux = jax.shard_map(body, mesh=mesh, in_specs=(p_specs, P()),
                           out_specs=(P(), P()), check_vma=False)(p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)


@multi
def test_engine_backend_sharded_session_end_to_end():
    """A qwen3-MoE-shaped pool of TP=2/EP=2 instances serves a small
    trace end-to-end through the full session stack, and the backend
    reports the shard width via describe()/gauges()."""
    from repro.core.request import Request
    from repro.core.session import ServeSession, SessionConfig
    from repro.engine.backend import EngineBackend
    from repro.sim.policies import DynaServePolicy

    cfg = get_smoke_config(MOE)
    params = init_params(cfg, jax.random.PRNGKey(0))
    backend = EngineBackend(cfg, params, n_slots=8, max_len=96,
                            devices_per_instance=2)
    rng = np.random.default_rng(0)
    reqs, t = [], 0.0
    for i in range(4):
        t += rng.exponential(0.05)
        reqs.append(Request(f"r{i}", t, int(rng.integers(8, 24)), 6,
                            predicted_decode=6))
    policy = DynaServePolicy(backend.cost, 0.1)
    session = ServeSession(backend, policy,
                           SessionConfig(n_instances=2, slo=0.1))
    m = session.run(reqs)
    assert m.completed == m.offered == 4
    assert backend.describe()["devices_per_instance"] == 2
    for iid, eng in backend.engines.items():
        assert eng.tp == 2
        assert backend.gauges(iid)["devices"] == 2.0


# ---------------------------------------------------------------------------
# validation / cost model / controller / placement (single-device)
# ---------------------------------------------------------------------------
def test_validate_tp_rejections():
    dev = jax.devices()[0]
    cfg = get_smoke_config(DENSE)
    params = init_params(cfg, jax.random.PRNGKey(0))
    # n_heads=8 but n_kv_heads=2: 3 divides neither
    with pytest.raises(ValueError, match="% 3 != 0"):
        InstanceEngine(cfg, params, devices=[dev] * 3)
    # quantized pages have no shardable scale planes
    with pytest.raises(ValueError, match="quantized|fp8"):
        InstanceEngine(cfg, params, devices=[dev] * 2, kv_precision="fp8")
    # GQA cap: kv_heads=2 forbids TP=4 even though n_heads=8 divides
    with pytest.raises(ValueError, match="n_kv_heads"):
        InstanceEngine(cfg, params, devices=[dev] * 4)


def test_achieved_parallelism_records_replication():
    import warnings as _w
    from repro.utils.sharding import achieved_parallelism, _warned
    cfg = get_smoke_config(DENSE)          # heads=8, kv_heads=2
    _warned.clear()
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        ap = achieved_parallelism(cfg, 4)
        assert ap.heads == 4 and ap.kv_heads == 1    # kv replicated
        assert any("n_kv_heads" in str(w.message) for w in rec)
    with _w.catch_warnings(record=True) as rec:      # one-time only
        _w.simplefilter("always")
        achieved_parallelism(cfg, 4)
        assert not rec


def test_cost_model_tp_pricing():
    from repro.configs import get_config
    cfg = get_config(DENSE)       # full-size: compute dominates overhead
    base = BatchCostModel(cfg, A100)
    tp1 = BatchCostModel(cfg, A100, tp_degree=1)
    tp2 = BatchCostModel(cfg, A100, tp_degree=2)
    probes = [(256, 0, 0, 0), (128, 64, 4, 96), (0, 0, 8, 128)]
    for M, ctx, dnum, dctx in probes:
        a = base.mixed_batch_latency(M, ctx, dnum, dctx)
        # tp_degree=1 is byte-exact legacy behaviour
        assert tp1.mixed_batch_latency(M, ctx, dnum, dctx) == a
        b = tp2.mixed_batch_latency(M, ctx, dnum, dctx)
        # faster than 1-device, slower than the ideal 2x (collectives
        # and unsharded work keep it sub-linear)
        assert b < a
        assert b > a / 2
    # GQA cap: width 5 divides n_heads=40 but not n_kv_heads=8, so the
    # KV cache is replicated (no KV-read speedup) while attention FLOPs
    # still shard
    tp5 = BatchCostModel(cfg, A100, tp_degree=5)
    assert tp5.kv_tp == 1 and tp5.attn_tp == 5
    assert tp5.coll_s_per_tok > tp2.coll_s_per_tok > 0.0


def test_pool_controller_width_trades():
    from repro.core.elastic import (ElasticConfig, InstanceStat,
                                    MergeInstances, PoolController,
                                    SplitInstance)
    cfg = ElasticConfig(min_instances=1, max_instances=2,
                        max_devices_per_instance=2, widen_cooldown=0.0,
                        load_ewma_alpha=1.0)
    pc = PoolController(cfg)
    loaded = [InstanceStat(iid=i, drain_time=5.0,
                           queued_prefill_tokens=4000,
                           queued_decode_tokens=400, n_queued=10,
                           draining=False, role_bias=0.0, devices=1)
              for i in range(2)]
    acts = pc.decide(loaded, now=10.0)
    merges = [a for a in acts if isinstance(a, MergeInstances)]
    assert len(merges) == 1
    assert sorted(merges[0].donors) == [0, 1] and merges[0].devices == 2

    pc2 = PoolController(cfg)
    quiet = [InstanceStat(iid=0, drain_time=0.05, queued_prefill_tokens=0,
                          queued_decode_tokens=0, n_queued=0,
                          draining=False, role_bias=0.0, devices=2)]
    acts2 = pc2.decide(quiet, now=20.0)
    splits = [a for a in acts2 if isinstance(a, SplitInstance)]
    assert len(splits) == 1
    assert splits[0].iid == 0 and splits[0].devices == 1

    # default config (max_devices_per_instance=1) never trades width
    pc3 = PoolController(ElasticConfig(max_instances=2,
                                       load_ewma_alpha=1.0))
    acts3 = pc3.decide(loaded, now=10.0)
    assert not [a for a in acts3
                if isinstance(a, (MergeInstances, SplitInstance))]


def test_elastic_sim_executes_width_trade():
    """End-to-end in the simulator: a loaded 2-member pool capped at 2
    members merges into a TP=2 instance (the width <-> count trade)."""
    from repro.configs import get_config
    from repro.core.elastic import ElasticConfig
    from repro.core.session import ServeSession, SessionConfig
    from repro.data.workloads import generate_trace
    from repro.sim.policies import ElasticDynaServePolicy
    from repro.sim.simulator import SimBackend

    cost = BatchCostModel(get_config(DENSE), A100)
    policy = ElasticDynaServePolicy(cost, 0.1, elastic=ElasticConfig(
        min_instances=1, max_instances=2, max_devices_per_instance=2,
        widen_cooldown=0.5))
    backend = SimBackend(cost, devices_per_instance=1)
    reqs = generate_trace("burstgpt", 6.0, 20.0, seed=0)
    sess = ServeSession(backend, policy,
                        SessionConfig(n_instances=2, slo=0.1))
    m = sess.run(reqs)
    assert m.completed == m.offered
    widths = {i.iid: backend.devices_for(i.iid) for i in sess.instances}
    assert max(widths.values()) == 2, widths


def test_sim_engine_placement_identity_mixed_widths():
    """Both backends build the same per-width cost models, so Algorithm
    1 makes byte-identical placement decisions over a mixed
    devices_per_instance pool."""
    from repro.core.global_scheduler import GlobalScheduler, InstanceView
    from repro.core.predictor import QueuedWork
    from repro.core.request import Request
    from repro.engine.backend import EngineBackend
    from repro.sim.simulator import SimBackend

    cfg = get_smoke_config(DENSE)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = EngineBackend(cfg, params, devices_per_instance=[1, 2])
    sim = SimBackend(BatchCostModel(cfg, A100),
                     devices_per_instance=[1, 2])

    probes = [(256, 0, 0, 0), (128, 64, 4, 96), (0, 0, 8, 128)]
    for iid in (0, 1):
        ce, cs = eng.cost_for(iid), sim.cost_for(iid)
        for M, ctx, dnum, dctx in probes:
            assert ce.mixed_batch_latency(M, ctx, dnum, dctx) == \
                cs.mixed_batch_latency(M, ctx, dnum, dctx)

    def views(backend):
        return [InstanceView(0, [QueuedWork("a", 300, 40, 0),
                                 QueuedWork("b", 100, 20, 0)],
                             cost=backend.cost_for(0)),
                InstanceView(1, [QueuedWork("c", 500, 10, 0)],
                             cost=backend.cost_for(1))]

    gs_e = GlobalScheduler(eng.cost, 0.1)
    gs_s = GlobalScheduler(sim.cost, 0.1)
    for i, (P_, D) in enumerate([(400, 60), (900, 30), (64, 128)]):
        r = Request(f"r{i}", 0.0, P_, D, predicted_decode=D)
        pe = gs_e.schedule(r, views(eng))
        ps = gs_s.schedule(r, views(sim))
        assert (pe.phi, pe.alpha_instance, pe.beta_instance, pe.probes) \
            == (ps.phi, ps.alpha_instance, ps.beta_instance, ps.probes)
        assert pe.predicted_t1 == ps.predicted_t1
        assert pe.predicted_t2 == ps.predicted_t2


def test_devices_spec_forms():
    """The per-instance width spec mirrors kv_precision: scalar, list
    (modulo), dict with default; set_devices rewrites any form."""
    from repro.sim.simulator import SimBackend
    cost = BatchCostModel(get_smoke_config(DENSE), A100)
    sim = SimBackend(cost, devices_per_instance=[1, 2])
    assert [sim.devices_for(i) for i in range(4)] == [1, 2, 1, 2]
    sim.set_devices(0, 4)
    assert sim.devices_for(0) == 4 and sim.devices_for(2) == 1
    sim2 = SimBackend(cost, devices_per_instance={"default": 2, 3: 1})
    assert sim2.devices_for(0) == 2 and sim2.devices_for(3) == 1
    assert sim2.cost_for(3) is sim2.cost_for(3)   # cached per width
    assert sim2.describe()["devices_per_instance"] == "mixed"


def test_engine_device_shortage_hint():
    """Asking for a wider instance than the host has devices raises
    with the XLA_FLAGS hint (don't spawn — fail at assignment)."""
    from repro.engine.backend import EngineBackend
    cfg = get_smoke_config(DENSE)
    params = init_params(cfg, jax.random.PRNGKey(0))
    n = jax.device_count() + 2
    backend = EngineBackend(cfg, params, devices_per_instance=n)
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        backend.spawn(0)


@multi
def test_one_device_instances_round_robin_share_weights():
    """One-device instances spread over the visible devices (instance
    iid on device iid % n), and instances on one device share one placed
    copy of the weights."""
    from repro.engine.backend import EngineBackend
    cfg = get_smoke_config(DENSE)
    params = init_params(cfg, jax.random.PRNGKey(0))
    n = jax.device_count()
    backend = EngineBackend(cfg, params, n_slots=2, max_len=32)
    for iid in range(n + 1):
        backend.spawn(iid)
    engines = [backend.engines[i] for i in range(n + 1)]
    for iid, eng in enumerate(engines):
        want = {jax.devices()[iid % n]}
        assert eng.params["embed"].devices() == want
        assert eng.cache["blocks"][0]["k_pages"].devices() == want
    ptr = lambda e: e.params["embed"].unsafe_buffer_pointer()
    assert ptr(engines[n]) == ptr(engines[0])
    assert ptr(engines[1]) != ptr(engines[0])

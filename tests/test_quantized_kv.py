"""Quantized KV pages end-to-end: the precision registry and SLO policy,
per-page allocator tags, quantize-on-write engine generation, quantized
and cross-precision handoff streams, frames-denominated admission that is
bit-identical between sim and engine over heterogeneous pools, and the
transfer-byte accounting the policies budget with."""
import numpy as np
import pytest

from repro.core.costmodel import A100, BatchCostModel
from repro.core.precision import (
    BF16, FP8, INT8, FRAMES_PER_BF16_PAGE, PrecisionPolicy, frames_for,
    get_precision,
)
from repro.core.request import INTERACTIVE, STANDARD, RequestState
from repro.core.session import ServeSession, SessionConfig
from repro.engine.block_allocator import BlockAllocator
from repro.engine.prefix_cache import PrefixCache
from repro.sim.policies import ColocationPolicy, DynaServePolicy
from repro.sim.simulator import SimBackend


@pytest.fixture(scope="module")
def cost():
    from repro.configs import get_config
    return BatchCostModel(get_config("qwen2.5-14b"), A100)


# ---------------------------------------------------------------------------
# Precision registry + SLO-class policy
# ---------------------------------------------------------------------------
def test_precision_registry():
    assert get_precision("bf16") is BF16 and BF16.itemsize == 2
    assert get_precision(FP8) is FP8 and FP8.qmax == 448.0
    assert INT8.qmax == 127.0 and INT8.itemsize == 1
    assert BF16.frames == FRAMES_PER_BF16_PAGE == 2
    assert FP8.frames == INT8.frames == 1
    assert not BF16.quantized and FP8.quantized and INT8.quantized
    assert frames_for(17, 16, BF16) == 4    # 2 pages x 2 frames
    assert frames_for(17, 16, INT8) == 2
    with pytest.raises(ValueError):
        get_precision("fp4")


def test_precision_policy_parse_and_for_slo():
    uni = PrecisionPolicy.parse("fp8")
    assert uni.uniform is FP8
    assert uni.for_slo("interactive") is FP8 and uni.for_slo(None) is FP8

    mixed = PrecisionPolicy.parse("mixed")
    assert mixed.uniform is None
    assert mixed.for_slo("batch") is FP8
    assert mixed.for_slo("interactive") is BF16
    assert mixed.for_slo(None) is BF16

    custom = PrecisionPolicy.parse("batch=int8,standard=fp8")
    assert custom.for_slo("batch") is INT8
    assert custom.for_slo("standard") is FP8
    assert custom.for_slo("interactive") is BF16


# ---------------------------------------------------------------------------
# Allocator: per-page precision tags
# ---------------------------------------------------------------------------
def test_allocator_precision_tags_and_check():
    a = BlockAllocator(n_pages=8, page_size=4, n_slots=2, precision="fp8")
    assert a.precision is FP8
    a.ensure(0, 10)                       # 3 pages
    for p in a.pages_of(0):
        assert a.precision_of(p) == "fp8"
    assert a.used_by_precision() == {"fp8": 3}
    a.check()                             # tag/pool cross-check holds
    a.free_slot(0)
    assert a.used_by_precision() == {}
    a.check()


# ---------------------------------------------------------------------------
# Engine: quantize-on-write pools, quantized + cross-precision handoff
# ---------------------------------------------------------------------------
def _engine(cfg, params, prec, **kw):
    from repro.engine import InstanceEngine
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", 96)
    return InstanceEngine(cfg, params, kv_precision=prec, **kw)


def _gen(eng, slot, prompt, n, pos0=0):
    from repro.engine import BatchItem
    out = eng.run_batch([BatchItem(slot, prompt, pos0, want_logits=True)])
    toks = [int(out[slot].argmax())]
    pos = pos0 + len(prompt)
    for _ in range(n - 1):
        out = eng.run_batch([BatchItem(slot, np.array([toks[-1]], np.int32),
                                       pos, want_logits=True)])
        toks.append(int(out[slot].argmax()))
        pos += 1
    return toks


def test_quantized_requires_paged_mode():
    jax = pytest.importorskip("jax")
    from repro.configs import get_smoke_config
    from repro.models.model import init_params
    cfg = get_smoke_config("qwen2.5-14b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        _engine(cfg, params, "fp8", kv_mode="dense")


@pytest.mark.parametrize("prec", ["fp8", "int8"])
def test_engine_quantized_generation(prec):
    """Quantize-on-write pools: generation runs through the quantized
    Pallas kernels; the pool stores 1-byte codes + f32 scale planes and
    prices KV state at roughly half the bf16 bytes."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.engine import BatchItem
    from repro.kernels.ops import kv_storage_dtype
    from repro.models.model import init_params

    cfg = get_smoke_config("qwen2.5-14b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, 24).astype(np.int32)
    eng = _engine(cfg, params, prec)
    toks = _gen(eng, eng.alloc("r"), prompt, 6)
    assert len(toks) == 6 and all(0 <= t < cfg.vocab_size for t in toks)
    blk = eng.cache["blocks"][0]
    assert blk["k_pages"].dtype == kv_storage_dtype(prec)
    assert blk["k_scales"].dtype == jnp.float32
    # head-major pages (G, n, KV, page, hd); one scale per token (G, n, 1, page)
    G, n, _, page, _ = blk["v_pages"].shape
    assert blk["v_scales"].shape == (G, n, 1, page)

    bf16 = _engine(cfg, params, "bf16")
    bf16.run_batch([BatchItem(bf16.alloc("r"), prompt, 0)])
    # codes are half the bytes; the f32 scale planes add a small tax
    assert eng.state_bytes(24) < bf16.state_bytes(24)
    assert eng.state_bytes(24, as_precision="bf16") == bf16.state_bytes(24)


def test_quantized_handoff_is_exact():
    """fp8 pool -> fp8 pool handoff ships codes + scale planes verbatim:
    the destination continues the token stream bit-identically."""
    jax = pytest.importorskip("jax")
    from repro.configs import get_smoke_config
    from repro.models.model import init_params

    cfg = get_smoke_config("qwen2.5-14b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(1).integers(
        0, cfg.vocab_size, 24).astype(np.int32)
    one = _engine(cfg, params, "fp8")
    ref = _gen(one, one.alloc("r"), prompt, 6)

    from repro.engine import BatchItem
    A = _engine(cfg, params, "fp8")
    B = _engine(cfg, params, "fp8")
    sa = A.alloc("r")
    A.run_batch([BatchItem(sa, prompt[:16], 0)])
    pieces = A.export_state(sa, upto=16, chunk=8)
    assert all(p.get("precision") == "fp8" for p in pieces
               if "precision" in p)
    sb = B.alloc("r")
    B.import_state(sb, pieces)
    toks = _gen(B, sb, prompt[16:], 6, pos0=16)
    assert toks == ref


@pytest.mark.parametrize("src,dst", [("bf16", "fp8"), ("fp8", "bf16"),
                                     ("int8", "fp8")])
def test_cross_precision_import_converts(src, dst):
    """Handoff across pool formats: the importer requantizes (or
    dequantizes) into ITS pool format and decoding continues."""
    jax = pytest.importorskip("jax")
    from repro.configs import get_smoke_config
    from repro.engine import BatchItem
    from repro.models.model import init_params

    cfg = get_smoke_config("qwen2.5-14b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(2).integers(
        0, cfg.vocab_size, 24).astype(np.int32)
    A = _engine(cfg, params, src)
    B = _engine(cfg, params, dst)
    sa = A.alloc("r")
    A.run_batch([BatchItem(sa, prompt[:16], 0)])
    sb = B.alloc("r")
    B.import_state(sb, A.export_state(sa, upto=16, chunk=8))
    toks = _gen(B, sb, prompt[16:], 4, pos0=16)
    assert len(toks) == 4 and all(0 <= t < cfg.vocab_size for t in toks)


# ---------------------------------------------------------------------------
# Session: frames-denominated admission, identical on sim and engine
# ---------------------------------------------------------------------------
def test_sim_and_engine_admit_identically_on_heterogeneous_pools(cost):
    """Instance 0 stores bf16 (2 frames/page), instance 1 stores fp8
    (1 frame/page): the commitment-based admission decision — now
    denominated in frames — must shed the SAME requests on both
    substrates.  On an engine instance the pool precision scales a
    request's cost and the pool total by the same factor (its pages are
    physically uniform), so with equal page counts the quantized
    instance sheds like the bf16 one — capacity doubles when the same
    HBM bytes buy 2x the pages (the benchmark configures that)."""
    jax = pytest.importorskip("jax")
    from repro.configs import get_smoke_config
    from repro.engine.backend import EngineBackend
    from repro.models.model import init_params

    prec = ["bf16", "fp8"]
    cfg = get_smoke_config("qwen2.5-14b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    ebackend = EngineBackend(cfg, params, n_slots=8, max_len=128,
                             page_size=16, n_pages=8, kv_precision=prec)
    esess = ServeSession(ebackend,
                         ColocationPolicy(chunk=64, slo_aware=False),
                         SessionConfig(n_instances=2, admission=True,
                                       debug_kv_invariants=True))
    sbackend = SimBackend(cost, page_size=16, pages_per_instance=8,
                          kv_precision=prec)
    ssess = ServeSession(sbackend,
                         ColocationPolicy(chunk=64, slo_aware=False),
                         SessionConfig(n_instances=2, admission=True))
    assert ebackend.pool_precision(1).name == "fp8"
    assert sbackend.pool_precision(1).name == "fp8"
    # 8 physical pages each: 16 frames at bf16, 8 at fp8 (half the HBM);
    # a (40, 4) request needs 3 pages = 6 frames bf16 / 3 frames fp8
    assert ebackend.total_frames(0) == sbackend.total_frames(0) == 16
    assert ebackend.total_frames(1) == sbackend.total_frames(1) == 8
    rng = np.random.default_rng(0)
    lens = [(40, 4)] * 8
    outcomes = {}
    for sess, name in ((esess, "engine"), (ssess, "sim")):
        got = []
        for i, (P, D) in enumerate(lens):
            if name == "engine":
                h = sess.generate(rng.integers(0, cfg.vocab_size, P), D,
                                  slo=INTERACTIVE, rid=f"r{i}")
            else:
                h = sess.generate(prompt_len=P, decode_len=D,
                                  slo=INTERACTIVE, rid=f"r{i}")
            got.append(h.state == RequestState.REJECTED)
        outcomes[name] = got
    assert outcomes["engine"] == outcomes["sim"]
    # each instance fits 2 requests (3 of its 8 pages each)
    assert sum(outcomes["sim"]) == 4
    for sess in (esess, ssess):
        done = [h for h in sess.handles.values()
                if h.state != RequestState.REJECTED]
        for h in done:
            assert len(list(h)) == 4 and h.state == RequestState.DONE


def test_mixed_policy_raises_quantized_class_capacity(cost):
    """SLO-class precision policy on the sim: requests of a quantized
    class commit 1-frame pages inside the same bf16-denominated pool,
    so the identical pool admits ~2x their residency.  (BATCH has
    ``admits_always`` and skips admission, so the capacity effect is
    asserted on STANDARD mapped to fp8.)"""
    def run(policy):
        backend = SimBackend(cost, page_size=16, pages_per_instance=8,
                             precision_policy=policy)
        sess = ServeSession(backend,
                            ColocationPolicy(chunk=64, slo_aware=False),
                            SessionConfig(n_instances=1, admission=True))
        shed = 0
        for i in range(6):
            h = sess.generate(prompt_len=40, decode_len=4, slo=STANDARD,
                              rid=f"b{i}")
            shed += h.state == RequestState.REJECTED
        return shed, backend

    shed_bf16, _ = run(None)
    shed_mixed, backend = run("standard=fp8")
    assert backend.request_precision(0, "standard").name == "fp8"
    assert backend.request_precision(0, "interactive").name == "bf16"
    mixed = PrecisionPolicy.parse("mixed")
    assert mixed.for_slo("batch").name == "fp8"   # default mixed spec
    # 16 frames: bf16 fits 2 of the 6 (6 frames each), fp8 fits 5
    assert shed_bf16 == 4 and shed_mixed == 1


def test_sim_quantized_handoff_saves_bytes(cost):
    """PD-split handoffs out of a quantized pool move ~half the bytes;
    the sim books the savings and exposes them as a gauge."""
    def run(prec):
        backend = SimBackend(cost, page_size=32, pages_per_instance=4096,
                             kv_precision=prec)
        sess = ServeSession(backend, DynaServePolicy(cost),
                            SessionConfig(n_instances=2))
        for i in range(4):
            h = sess.generate(prompt_len=600, decode_len=24, rid=f"r{i}")
            assert len(list(h)) == 24
        return backend, sess.metrics()

    b8, m8 = run("fp8")
    b16, m16 = run("bf16")
    assert m8.completed == m16.completed == 4
    if m8.transfer_bytes_total:            # the policy did hand off
        assert b8.handoff_bytes_saved > 0
        assert b16.handoff_bytes_saved == 0
        assert m8.transfer_bytes_total < m16.transfer_bytes_total
        assert b8.gauges(0)["handoff_bytes_saved"] >= 0
    g = b8.gauges(0)
    assert g["kv_frames_total"] >= g["kv_frames_free"] >= 0


# ---------------------------------------------------------------------------
# Prefix cache: one precision per shared page
# ---------------------------------------------------------------------------
def test_prefix_cache_precision_tags():
    pc = PrefixCache(page_size=4)
    toks = list(range(12))
    pc.insert(toks, precision="fp8")
    assert pc.match_len(toks, precision="fp8") == 12
    assert pc.match_len(toks, precision="bf16") == 0   # format mismatch
    assert pc.match_len(toks) == 12                    # blind probe walks
    c = pc.claim(toks, precision="fp8")
    assert c.tokens == 12
    pc.release(c)
    # an insert at another precision must NOT chain under fp8 nodes
    pc.insert(toks + [99, 98, 97, 96], precision="bf16")
    assert pc.match_len(toks + [99, 98, 97, 96], precision="bf16") == 0


# ---------------------------------------------------------------------------
# Cost model: precision-aware transfer pricing
# ---------------------------------------------------------------------------
def test_cost_model_quantized_transfer_bytes(cost):
    full = cost.kv_bytes_per_tok_at(None)
    q8 = cost.kv_bytes_per_tok_at(FP8)
    assert cost.kv_bytes_per_tok_at(BF16) == full
    assert q8 < full
    # 1-byte codes + two f32 per-token scales per attention layer
    assert q8 == cost.kv_bytes_per_tok_at(INT8)
    assert cost.kv_transfer_bytes(100, FP8) == 100 * q8
    assert cost.kv_transfer_time(100, FP8) < cost.kv_transfer_time(100)


# ---------------------------------------------------------------------------
# Prometheus surface: quantization wins visible live
# ---------------------------------------------------------------------------
def test_prometheus_exposes_precision_gauges(cost):
    """`ServingMetrics.sample` must publish the per-precision occupancy
    and handoff-savings gauges the backends meter."""
    from repro.serving.metrics import ServingMetrics

    backend = SimBackend(cost, page_size=32, pages_per_instance=4096,
                         kv_precision="fp8")
    sess = ServeSession(backend, DynaServePolicy(cost),
                        SessionConfig(n_instances=2))
    hub = ServingMetrics()
    sess.observers.append(hub)
    h = sess.generate(prompt_len=600, decode_len=8, rid="r0")
    it = iter(h)
    next(it)                 # request resident: pages occupied
    hub.sample(sess)
    assert len(list(it)) == 7
    text = hub.render()
    assert 'key="kv_frames_total"' in text
    assert 'key="kv_frames_free"' in text
    assert 'key="kv_pages_used_fp8"' in text
    assert 'key="handoff_bytes_saved"' in text

"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles in kernels/ref.py (interpret=True executes the kernel body on
CPU).  The parity sweeps cover ragged sequence lengths, every GQA group
size the assigned archs use (MHA / GQA / MQA), and the page-size range
of the paged KV pool."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import (
    chunked_prefill_attention_op, chunked_prefill_attention_ref,
    gather_pages, paged_decode_attention_op, paged_decode_attention_ref,
    paged_prefill_attention_op, to_pool_layout,
)

RNG = np.random.default_rng(7)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


def _pool(shape, dtype=jnp.float32):
    """Random token-major pages (n_pages, page, KV, hd) in the pool's
    head-major layout (n_pages, KV, page, hd)."""
    return to_pool_layout(_rand(shape, dtype))[0]


def _seq_major(kv):
    """Dense head-major (B, KV, S, hd) -> token-major (B, S, KV, hd)."""
    return kv.swapaxes(1, 2)


def _prefill(q, k, v, off, ks=None, vs=None, **kw):
    """The head-major chunked-prefill op on the oracle's token-major
    operands: q (B,Tq,H,hd), k/v (B,S,KV,hd), scales (B,S)."""
    out = chunked_prefill_attention_op(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), off,
        None if ks is None else ks[:, None],
        None if vs is None else vs[:, None], interpret=True, **kw)
    return out.swapaxes(1, 2)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Tq,S,H,KV,hd,bq,bk", [
    (1, 8, 32, 4, 4, 32, 8, 8),        # MHA
    (2, 24, 64, 8, 2, 64, 8, 16),      # GQA, ragged chunk
    (2, 16, 48, 6, 1, 128, 16, 16),    # MQA, wide head
    (1, 33, 70, 4, 2, 64, 16, 32),     # non-multiple sizes (wrapper pads)
])
def test_chunked_prefill_vs_ref(dtype, B, Tq, S, H, KV, hd, bq, bk):
    q = _rand((B, Tq, H, hd), dtype)
    k = _rand((B, S, KV, hd), dtype)
    v = _rand((B, S, KV, hd), dtype)
    off = jnp.asarray(RNG.integers(0, S - Tq, B), jnp.int32)
    out = _prefill(q, k, v, off, bq=bq, bk=bk)
    exp = chunked_prefill_attention_ref(q, k, v, off)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


def test_chunked_prefill_zero_offset_is_plain_causal():
    """offsets == 0 must equal vanilla causal flash attention."""
    B, T, H, hd = 2, 32, 4, 64
    q = _rand((B, T, H, hd), jnp.float32)
    k = _rand((B, T, H, hd), jnp.float32)
    v = _rand((B, T, H, hd), jnp.float32)
    out = _prefill(q, k, v, jnp.zeros(B, jnp.int32), bq=8, bk=8)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(hd)
    mask = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(mask[None, None], scores, -1e30)
    exp = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,hd,page,ppseq", [
    (2, 8, 2, 64, 8, 4),
    (3, 4, 4, 32, 16, 2),      # MHA
    (1, 16, 2, 128, 8, 8),     # deep GQA
])
def test_paged_decode_vs_ref(dtype, B, H, KV, hd, page, ppseq):
    n_pages = B * ppseq + 2
    q = _rand((B, H, hd), dtype)
    kp = _pool((n_pages, page, KV, hd), dtype)
    vp = _pool((n_pages, page, KV, hd), dtype)
    tbl = jnp.asarray(
        RNG.permutation(n_pages)[:B * ppseq].reshape(B, ppseq), jnp.int32)
    lens = jnp.asarray(RNG.integers(1, page * ppseq + 1, B), jnp.int32)
    out = paged_decode_attention_op(q, kp, vp, tbl, lens, interpret=True)
    exp = paged_decode_attention_ref(q, kp, vp, tbl, lens)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("page", [8, 16, 32])
@pytest.mark.parametrize("qpk", [1, 2, 3, 4, 5, 8])
def test_paged_decode_gqa_and_page_size_sweep(qpk, page):
    """Parity across GQA group sizes x page sizes with ragged lengths
    (every sequence at a different, non-page-aligned context)."""
    B, KV, hd, ppseq = 3, 2, 64, 3
    H = KV * qpk
    n_pages = B * ppseq + 1
    q = _rand((B, H, hd), jnp.float32)
    kp = _pool((n_pages, page, KV, hd), jnp.float32)
    vp = _pool((n_pages, page, KV, hd), jnp.float32)
    tbl = jnp.asarray(
        RNG.permutation(n_pages)[:B * ppseq].reshape(B, ppseq), jnp.int32)
    # ragged: 1 token, mid-page, page-aligned
    lens = jnp.asarray([1, page * 2 - 3, page * ppseq], jnp.int32)
    out = paged_decode_attention_op(q, kp, vp, tbl, lens, interpret=True)
    exp = paged_decode_attention_ref(q, kp, vp, tbl, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("page,n_pp,lens", [
    # the kernel streams 256-token blocks: 32 pages of 8, 8 of 32
    (8, 40, [256, 257, 255, 320]),      # at a block multiple, one past, short
    (32, 10, [256, 257, 255, 320]),
    (8, 40, [1, 7, 100, 200]),          # rows shorter than one block
    (8, 3, [1, 9, 24, 17]),             # a table narrower than a block
    (8, 40, [1, 320, 1, 320]),          # 1-token rows beside full tables
    (32, 10, [320, 1]),
])
def test_paged_decode_block_edges(page, n_pp, lens):
    """Parity where the kernel's blocks begin and end: each row streams
    only its own pages, block by block, and masks the tail of its last
    block."""
    B, KV, qpk, hd = len(lens), 2, 3, 64
    n_pages = B * n_pp + 1
    q = _rand((B, KV * qpk, hd), jnp.float32)
    kp = _pool((n_pages, page, KV, hd), jnp.float32)
    vp = _pool((n_pages, page, KV, hd), jnp.float32)
    tbl = jnp.asarray(
        RNG.permutation(n_pages)[:B * n_pp].reshape(B, n_pp), jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    out = paged_decode_attention_op(q, kp, vp, tbl, lens, interpret=True)
    exp = paged_decode_attention_ref(q, kp, vp, tbl, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_empty_row_is_zero():
    """A row of length 0 streams no page and returns zeros, after a row
    whose blocks left the buffers full, and the row after it is exact."""
    B, KV, qpk, hd, page, n_pp = 3, 2, 3, 64, 8, 40
    q = _rand((B, KV * qpk, hd), jnp.float32)
    kp = _pool((B * n_pp, page, KV, hd), jnp.float32)
    vp = _pool((B * n_pp, page, KV, hd), jnp.float32)
    tbl = jnp.arange(B * n_pp, dtype=jnp.int32).reshape(B, n_pp)
    lens = jnp.asarray([300, 0, 77], jnp.int32)
    out = np.asarray(paged_decode_attention_op(q, kp, vp, tbl, lens,
                                               interpret=True))
    exp = np.asarray(paged_decode_attention_ref(q, kp, vp, tbl, lens))
    np.testing.assert_array_equal(out[1], 0.0)
    np.testing.assert_allclose(out[[0, 2]], exp[[0, 2]], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("page,Tq,ctx", [
    (8, 5, 11),       # ragged chunk, ragged prefix
    (16, 16, 16),     # page-aligned resume
    (32, 9, 0),       # fresh prefill, oversized page
])
def test_paged_prefill_matches_dense_chunked_ref(page, Tq, ctx):
    """The paged-prefill path (gather pages -> chunked kernel) must equal
    the dense chunked-prefill oracle on the logically identical KV."""
    B, H, KV, hd = 2, 4, 2, 32
    total = ctx + Tq
    ppseq = -(-total // page) + 1
    n_pages = B * ppseq + 1
    q = _rand((B, Tq, H, hd), jnp.float32)
    kp = _pool((n_pages, page, KV, hd), jnp.float32)
    vp = _pool((n_pages, page, KV, hd), jnp.float32)
    tbl = jnp.asarray(
        RNG.permutation(n_pages)[:B * ppseq].reshape(B, ppseq), jnp.int32)
    off = jnp.full((B,), ctx, jnp.int32)
    out = paged_prefill_attention_op(q, kp, vp, tbl, off, interpret=True)
    k = _seq_major(gather_pages(kp, tbl))
    v = _seq_major(gather_pages(vp, tbl))
    exp = chunked_prefill_attention_ref(q, k, v, off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


def test_paged_prefill_unwritten_page_slack_is_masked():
    """Garbage in the not-yet-written tail of the last page (and in
    sentinel table entries past the sequence) must not leak into the
    chunk's outputs — causality masks everything past offsets+Tq."""
    B, Tq, H, KV, hd, page = 1, 6, 4, 2, 32, 8
    ppseq, n_pages = 3, 6
    q = _rand((B, Tq, H, hd), jnp.float32)
    kp = _pool((n_pages, page, KV, hd), jnp.float32)
    vp = _pool((n_pages, page, KV, hd), jnp.float32)
    tbl = jnp.asarray([[1, 2, 0]], jnp.int32)   # page 0 = sentinel entry
    off = jnp.asarray([4], jnp.int32)           # chunk covers [4, 10)
    out1 = paged_prefill_attention_op(q, kp, vp, tbl, off, interpret=True)
    kp2 = kp.at[2, :, 2:].set(1e6).at[0].set(-1e6)  # poison beyond pos 10
    vp2 = vp.at[2, :, 2:].set(-1e6).at[0].set(1e6)
    out2 = paged_prefill_attention_op(q, kp2, vp2, tbl, off, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)


def test_chunked_prefill_per_row_ragged_offsets():
    """Mixed unified batches give every row its own resume offset; the
    kernel's scalar-prefetched offsets must mask per row."""
    B, Tq, S, H, hd = 3, 8, 40, 4, 32
    q = _rand((B, Tq, H, hd), jnp.float32)
    k = _rand((B, S, H, hd), jnp.float32)
    v = _rand((B, S, H, hd), jnp.float32)
    off = jnp.asarray([0, 13, 32 - Tq], jnp.int32)
    out = _prefill(q, k, v, off, bq=8, bk=8)
    exp = chunked_prefill_attention_ref(q, k, v, off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


# (table width, length): the length's last page and the pages after it
# fall inside the last 32-page block the kernel streams
POISON = [(4, 11), (40, 267)]


def _poison(pool, length, page, value):
    """``pool`` (identity table) with every token at or past ``length``
    set to ``value``: the tail of the length's last page and all later
    pages."""
    last, used = divmod(length, page)
    pool = pool.at[last + 1:].set(value)
    return pool.at[last, :, used:].set(value)


@pytest.mark.parametrize("ppseq,length", POISON)
def test_paged_decode_ignores_pages_beyond_length(ppseq, length):
    """Garbage in pages past ``length`` must not leak into the output."""
    B, H, KV, hd, page = 1, 4, 2, 32, 8
    n_pages = ppseq + 4
    q = _rand((B, H, hd), jnp.float32)
    kp = _pool((n_pages, page, KV, hd), jnp.float32)
    vp = _pool((n_pages, page, KV, hd), jnp.float32)
    tbl = jnp.arange(ppseq, dtype=jnp.int32)[None]
    lens = jnp.array([length], jnp.int32)
    out1 = paged_decode_attention_op(q, kp, vp, tbl, lens, interpret=True)
    kp2 = _poison(kp, length, page, 1e6)     # poison tokens past length
    vp2 = _poison(vp, length, page, -1e6)
    out2 = paged_decode_attention_op(q, kp2, vp2, tbl, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Quantized KV pages: fp8/int8 codes + per-token scales, dequantized
# in-register by the same kernels.  Two-sided parity: the quantized kernel
# must match the oracle run on the *dequantized* values tightly (the kernel
# mechanics add no error beyond the f32 math), and match the full-precision
# oracle within the format's quantization error budget.
# ---------------------------------------------------------------------------
from repro.kernels.ops import (  # noqa: E402
    dequantize_kv, gather_scales, kv_storage_dtype, quantize_kv,
)

QTOL = {"fp8": 0.15, "int8": 0.04}      # abs error vs full-precision oracle
QPREC = ["fp8", "int8"]


def _qpool(shape, prec):
    """Random token-major pages, quantized per token row, in the pool
    layout: (full-precision pool, code pool, scale planes, dequantized
    pool)."""
    x = _rand(shape, jnp.float32)
    codes, scales = quantize_kv(x, prec)
    pool, _ = to_pool_layout(x)
    cp, sp = to_pool_layout(codes, scales)
    return pool, cp, sp, to_pool_layout(dequantize_kv(codes, scales))[0]


@pytest.mark.parametrize("prec", QPREC)
def test_quantize_roundtrip_error_bound(prec):
    x = _rand((5, 16, 2, 64), jnp.float32)
    codes, scales = quantize_kv(x, prec)
    assert codes.dtype == kv_storage_dtype(prec)
    assert scales.shape == (5, 16) and scales.dtype == jnp.float32
    back = dequantize_kv(codes, scales)
    err = float(jnp.max(jnp.abs(back - x)))
    # symmetric amax quantization: per-row error <= scale/2 (int8 rounds)
    # or ~scale * ulp spacing (fp8); both comfortably under QTOL here
    assert err < QTOL[prec], err


@pytest.mark.parametrize("prec", QPREC)
@pytest.mark.parametrize("page,qpk,ppseq", [(8, 1, 3), (16, 2, 3),
                                            (32, 4, 3), (8, 3, 36)])
def test_paged_decode_quantized_parity(prec, page, qpk, ppseq):
    """GQA sizes x page sizes x ragged lengths through the quantized
    decode kernel, within one block and across two."""
    B, KV, hd = 3, 2, 64
    H = KV * qpk
    n_pages = B * ppseq + 1
    q = _rand((B, H, hd), jnp.float32)
    kp, kc, ks, kd = _qpool((n_pages, page, KV, hd), prec)
    vp, vc, vs, vd = _qpool((n_pages, page, KV, hd), prec)
    tbl = jnp.asarray(
        RNG.permutation(n_pages)[:B * ppseq].reshape(B, ppseq), jnp.int32)
    lens = jnp.asarray([1, page * 2 - 3, page * ppseq], jnp.int32)  # ragged
    out = paged_decode_attention_op(q, kc, vc, tbl, lens, ks, vs,
                                    interpret=True)
    # tight vs the oracle on the dequantized values: kernel mechanics only
    exp_dq = paged_decode_attention_ref(q, kd, vd, tbl, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp_dq),
                               rtol=2e-5, atol=2e-5)
    # loose vs the full-precision oracle: bounded quantization error
    exp = paged_decode_attention_ref(q, kp, vp, tbl, lens)
    assert float(jnp.max(jnp.abs(out - exp))) < QTOL[prec]


@pytest.mark.parametrize("prec", QPREC)
@pytest.mark.parametrize("B,Tq,S,KV,qpk,bq,bk", [
    (2, 24, 64, 2, 4, 8, 16),     # GQA, ragged chunk
    (1, 33, 70, 2, 2, 16, 32),    # non-multiple sizes (wrapper pads scales)
    (3, 8, 40, 4, 1, 8, 8),       # MHA, per-row ragged offsets
])
def test_chunked_prefill_quantized_parity(prec, B, Tq, S, KV, qpk, bq, bk):
    hd = 64
    q = _rand((B, Tq, KV * qpk, hd), jnp.float32)
    k = _rand((B, S, KV, hd), jnp.float32)
    v = _rand((B, S, KV, hd), jnp.float32)
    off = jnp.asarray(RNG.integers(0, S - Tq, B), jnp.int32)
    kc, ks = quantize_kv(k, prec)
    vc, vs = quantize_kv(v, prec)
    out = _prefill(q, kc, vc, off, ks, vs, bq=bq, bk=bk)
    exp_dq = chunked_prefill_attention_ref(q, dequantize_kv(kc, ks),
                                           dequantize_kv(vc, vs), off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp_dq),
                               rtol=2e-5, atol=2e-5)
    exp = chunked_prefill_attention_ref(q, k, v, off)
    assert float(jnp.max(jnp.abs(out - exp))) < QTOL[prec]


@pytest.mark.parametrize("prec", QPREC)
def test_paged_prefill_quantized_gathers_scales(prec):
    """The paged-prefill path must gather the scale planes alongside the
    code pages and land on the dense quantized kernel's output."""
    B, Tq, ctx, H, KV, hd, page = 2, 5, 11, 4, 2, 32, 8
    total = ctx + Tq
    ppseq = -(-total // page) + 1
    n_pages = B * ppseq + 1
    q = _rand((B, Tq, H, hd), jnp.float32)
    _, kc, ks, kd = _qpool((n_pages, page, KV, hd), prec)
    _, vc, vs, vd = _qpool((n_pages, page, KV, hd), prec)
    tbl = jnp.asarray(
        RNG.permutation(n_pages)[:B * ppseq].reshape(B, ppseq), jnp.int32)
    off = jnp.full((B,), ctx, jnp.int32)
    out = paged_prefill_attention_op(q, kc, vc, tbl, off, ks, vs,
                                     interpret=True)
    exp_dq = chunked_prefill_attention_ref(
        q, _seq_major(gather_pages(kd, tbl)),
        _seq_major(gather_pages(vd, tbl)), off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp_dq),
                               rtol=2e-5, atol=2e-5)
    assert gather_scales(ks, tbl).shape == (B, 1, ppseq * page)


@pytest.mark.parametrize("prec", QPREC)
@pytest.mark.parametrize("ppseq,length", POISON)
def test_paged_decode_quantized_ignores_poison_pages(prec, ppseq, length):
    """Garbage codes AND garbage scales in pages past ``length`` must not
    leak into the quantized decode output."""
    B, H, KV, hd, page = 1, 4, 2, 32, 8
    n_pages = ppseq + 4
    q = _rand((B, H, hd), jnp.float32)
    _, kc, ks, _ = _qpool((n_pages, page, KV, hd), prec)
    _, vc, vs, _ = _qpool((n_pages, page, KV, hd), prec)
    tbl = jnp.arange(ppseq, dtype=jnp.int32)[None]
    lens = jnp.array([length], jnp.int32)
    out1 = paged_decode_attention_op(q, kc, vc, tbl, lens, ks, vs,
                                     interpret=True)
    qmax = 127 if prec == "int8" else 448
    # poison codes and scales
    kc2 = _poison(kc, length, page, jnp.asarray(qmax, kc.dtype))
    vc2 = _poison(vc, length, page, jnp.asarray(-qmax, vc.dtype))
    ks2 = _poison(ks, length, page, 1e6)
    vs2 = _poison(vs, length, page, 1e6)
    out2 = paged_decode_attention_op(q, kc2, vc2, tbl, lens, ks2, vs2,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)

"""Pallas TPU kernel: paged GQA decode attention.

One query token per sequence against a block-table-indexed KV pool —
the vLLM paged-attention pattern adapted to TPU:

  * grid = (B,): one step per row, and inside it a loop over the row's
    own blocks of ``ppb`` pages, its trip count read from the
    scalar-prefetched ``lengths``.  A row of ``length`` tokens costs
    ``ceil(length / (ppb * page))`` blocks whatever the table's width;
    no page at or past ``length`` is copied;
  * the pool stays in HBM (``memory_space=pltpu.HBM``) and each page is
    one manual async copy of its ``(KV, page, hd)`` slab — every KV head
    of the page at once, a contiguous run of the head-major pool —
    chosen from the prefetched block table;
  * double-buffered: block j+1 (or the next row's first block) is in
    flight while block j is computed, so the rows run in order
    (``arbitrary``);
  * all KV groups in one step: for each head the ``qpk`` query rows meet
    the block's ``ppb * page`` tokens in one (qpk, hd) x (hd, ppb*page)
    score matmul and one (qpk, ppb*page) x (ppb*page, hd) value matmul,
    with online-softmax state per head carried across the blocks.

``ppb`` aims at 256 tokens a block (``max(1, 256 // page)``, at most
the table's width), enough bytes per block to keep the DMA engine busy
and few enough that the double buffer sits well inside VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_TOKENS = 256


def _kernel(tables_ref, lens_ref,          # scalar prefetch
            q_ref, k_hbm, v_hbm,           # q tile; pools left in HBM
            *rest,
            page: int, ppb: int, kv: int, scale: float, quantized: bool,
            mxu_dtype):
    if quantized:
        ks_ref, vs_ref, o_ref, kbuf, vbuf, sems, m_ref, l_ref, acc_ref, \
            slot_ref = rest
    else:
        o_ref, kbuf, vbuf, sems, m_ref, l_ref, acc_ref, slot_ref = rest
    planes = ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1))
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    n_pp = tables_ref.shape[1]
    bt = ppb * page

    def pages_of(row):
        return jnp.minimum(pl.cdiv(lens_ref[row], page), n_pp)

    def blocks_of(row):
        # a row always owns one block, so the prefetch chain from each
        # row to the next never breaks (an empty block copies nothing)
        return jnp.maximum(pl.cdiv(pages_of(row), ppb), 1)

    def count(row, blk):
        return jnp.clip(pages_of(row) - blk * ppb, 0, ppb)

    def start(row, blk, slot):
        first = blk * ppb

        def one(i, carry):
            pid = tables_ref[row, first + i]
            for src, buf, s in planes:
                pltpu.make_async_copy(src.at[pid], buf.at[slot, i],
                                      sems.at[s, slot]).start()
            return carry

        jax.lax.fori_loop(0, count(row, blk), one, 0)

    def wait(row, blk, slot):
        def one(i, carry):
            for src, buf, s in planes:
                pltpu.make_async_copy(src.at[0], buf.at[slot, i],
                                      sems.at[s, slot]).wait()
            return carry

        jax.lax.fori_loop(0, count(row, blk), one, 0)

    @pl.when(b == 0)
    def _first():
        # never-written VMEM may hold any bits; a masked token's value
        # row meets a zero probability, which only a finite value keeps 0
        for _, buf, _ in planes:
            buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        start(0, 0, 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    length = lens_ref[b]
    n_blk = blocks_of(b)

    def tile(buf, slot, h):
        # a (page, hd) f32 tile is whole (8, 128) vregs: merging the
        # pages is free
        x = buf[slot, :, h].astype(jnp.float32)              # (ppb, page, hd)
        return x.reshape(bt, x.shape[-1])

    def block(j, carry):
        slot = slot_ref[0]
        last = j + 1 >= n_blk
        nxt_row = jnp.where(last, b + 1, b)
        nxt_blk = jnp.where(last, 0, j + 1)

        @pl.when(nxt_row < n_rows)
        def _prefetch():
            start(nxt_row, nxt_blk, 1 - slot)

        wait(b, j, slot)
        valid = (j * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
                 < length)
        if quantized:
            # in-register dequant: one f32 scale per token, a lane vector
            # (1, bt).  Scaling K's rows scales the score columns, and
            # scaling V's rows scales the columns of p.
            at = pl.ds(pl.multiple_of(j * bt, bt), bt)
            ks, vs = ks_ref[0, :, at], vs_ref[0, :, at]
        for h in range(kv):
            q = q_ref[0, h].astype(mxu_dtype)                # (qpk, hd)
            # narrowing an f32 tile of bf16 values back to bf16 is exact
            k = tile(kbuf, slot, h).astype(mxu_dtype)        # (bt, hd)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if quantized:
                s = s * ks
            # -inf against a finite running max: a masked token weighs
            # exactly 0, also in a block with no valid token (length 0)
            s = jnp.where(valid, s, -jnp.inf)
            m_prev = m_ref[h]                                # (qpk, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = p * vs if quantized else p
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                pv, tile(vbuf, slot, h), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        slot_ref[0] = 1 - slot
        return carry

    jax.lax.fori_loop(0, n_blk, block, 0)
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           k_scales=None, v_scales=None, *,
                           interpret: bool = False):
    """q: (B,H,hd); k/v_pages: (n_pages,KV,page,hd);
    block_tables: (B,n_pp) int32; lengths: (B,) -> (B,H,hd).

    ``k_scales``/``v_scales``: optional (n_pages, 1, page) f32 per-token
    dequant scales for quantized (fp8/int8) page pools — gathered by the
    same block table as the code pages and applied in-register to the
    f32 scores and probabilities.  A row of length 0 returns zeros.
    """
    B, H, hd = q.shape
    n_pages, KV, page, _ = k_pages.shape
    n_pp = block_tables.shape[1]
    qpk = H // KV
    ppb = min(max(1, BLOCK_TOKENS // page), n_pp)
    bt = ppb * page
    quantized = k_scales is not None
    # bf16 K meets bf16 q on the MXU (exact products, f32 sums); any
    # other mix, and every value matmul, runs in f32
    mxu_dtype = (jnp.bfloat16 if q.dtype == jnp.bfloat16
                 and k_pages.dtype == jnp.bfloat16 else jnp.float32)

    kernel = functools.partial(_kernel, page=page, ppb=ppb, kv=KV,
                               scale=1.0 / np.sqrt(hd), quantized=quantized,
                               mxu_dtype=mxu_dtype)
    row = pl.BlockSpec((1, KV, qpk, hd), lambda b, tbl, ln: (b, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [row, hbm, hbm]
    operands = [q.reshape(B, KV, qpk, hd), k_pages, v_pages]
    scratch = [pltpu.VMEM((2, ppb, KV, page, hd), k_pages.dtype),
               pltpu.VMEM((2, ppb, KV, page, hd), v_pages.dtype)]
    if quantized:
        # each row's scales, gathered by its table and padded to whole
        # blocks: (B, 1, width).  A page's plane is one lane-padded row
        # in HBM, which no manual copy may slice.
        width = pl.cdiv(n_pp, ppb) * bt
        tbl = jnp.pad(block_tables, ((0, 0), (0, width // page - n_pp)))
        span = pl.BlockSpec((1, 1, width), lambda b, tbl, ln: (b, 0, 0))
        in_specs += [span, span]
        operands += [sc.astype(jnp.float32)[tbl].reshape(B, 1, width)
                     for sc in (k_scales, v_scales)]
    scratch += [
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((KV, qpk, 1), jnp.float32),
        pltpu.VMEM((KV, qpk, 1), jnp.float32),
        pltpu.VMEM((KV, qpk, hd), jnp.float32),
        pltpu.SMEM((1,), jnp.int32),
    ]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=in_specs,
            out_specs=row,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, qpk, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables, lengths, *operands)
    return out.reshape(B, H, hd)

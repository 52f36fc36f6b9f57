"""Pallas TPU kernel: paged GQA decode attention.

One query token per sequence against a block-table-indexed KV pool —
the vLLM paged-attention pattern adapted to TPU:

  * the physical page to stream into VMEM is chosen *in the BlockSpec
    index_map* from the scalar-prefetched block table, so page gathers
    ride the normal Pallas double-buffered HBM->VMEM pipeline (the TPU
    analogue of CUDA's gather-by-pointer);
  * grid = (B, KV, n_pages_per_seq), pages innermost-sequential with
    online-softmax scratch carried across page steps;
  * all q heads of one KV group (q_per_kv rows) are processed together so
    the MXU tile is (q_per_kv, hd) x (hd, page);
  * the pool is head-major inside a page, (n_pages, KV, page, hd), so a
    streamed tile is (page, hd): the last two block dimensions are the
    (sublane, lane) tile Mosaic requires.  A token-major page would put
    a block of 1 (one KV head) in the second-minor dimension, which the
    TPU compiler refuses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, lens_ref,          # scalar prefetch
            q_ref, k_ref, v_ref,           # VMEM tiles
            *rest,
            page: int, qpk: int, scale: float, n_pp: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lens_ref[b]
    pos = ip * page + jax.lax.broadcasted_iota(jnp.int32, (qpk, page), 1)

    @pl.when(ip * page < length)
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)             # (qpk, hd)
        k = k_ref[0, 0, :, :].astype(jnp.float32)             # (page, hd)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quantized:
            # in-register dequant: one f32 scale per token of the page,
            # a lane vector (1, page).  Scaling K's rows scales the score
            # columns, and scaling V's rows scales the columns of p.
            s = s * ks_ref[0]
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        pv = p * vs_ref[0] if quantized else p
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ip == n_pp - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           k_scales=None, v_scales=None, *,
                           interpret: bool = False):
    """q: (B,H,hd); k/v_pages: (n_pages,KV,page,hd);
    block_tables: (B,n_pp) int32; lengths: (B,) -> (B,H,hd).

    ``k_scales``/``v_scales``: optional (n_pages, 1, page) f32 per-token
    dequant scales for quantized (fp8/int8) page pools — prefetched by
    the same block-table index_map as the pages and applied in-register
    to the f32 scores and probabilities.
    """
    B, H, hd = q.shape
    n_pages, KV, page, _ = k_pages.shape
    n_pp = block_tables.shape[1]
    qpk = H // KV
    qg = q.reshape(B, KV, qpk, hd)
    grid = (B, KV, n_pp)
    quantized = k_scales is not None

    kernel = functools.partial(_kernel, page=page, qpk=qpk,
                               scale=1.0 / np.sqrt(hd), n_pp=n_pp,
                               quantized=quantized)

    in_specs = [
        pl.BlockSpec((1, 1, qpk, hd),
                     lambda b, h, ip, tbl, ln: (b, h, 0, 0)),
        # physical page chosen from the prefetched block table
        pl.BlockSpec((1, 1, page, hd),
                     lambda b, h, ip, tbl, ln: (tbl[b, ip], h, 0, 0)),
        pl.BlockSpec((1, 1, page, hd),
                     lambda b, h, ip, tbl, ln: (tbl[b, ip], h, 0, 0)),
    ]
    operands = [qg, k_pages, v_pages]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, page),
                         lambda b, h, ip, tbl, ln: (tbl[b, ip], 0, 0)),
            pl.BlockSpec((1, 1, page),
                         lambda b, h, ip, tbl, ln: (tbl[b, ip], 0, 0)),
        ]
        operands += [k_scales.astype(jnp.float32),
                     v_scales.astype(jnp.float32)]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, qpk, hd),
                                   lambda b, h, ip, tbl, ln: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((qpk,), jnp.float32),
                pltpu.VMEM((qpk,), jnp.float32),
                pltpu.VMEM((qpk, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, qpk, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, lengths, *operands)
    return out.reshape(B, H, hd)

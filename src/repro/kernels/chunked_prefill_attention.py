"""Pallas TPU kernel: chunked-prefill attention for micro-requests.

This is the compute hot-spot of DynaServe's unified execution: a
micro-request beta resuming mid-prompt attends its chunk of queries
(global positions offsets+i) against the *imported* KV prefix plus its
own freshly written K/V — flash attention with a prefix, causal inside
the chunk.

TPU adaptation (vs. the CUDA kernels vLLM uses):
  * grid = (B, H, n_q_blocks, n_kv_blocks) with the KV dimension
    innermost-sequential; online-softmax running stats (m, l, acc) live in
    VMEM scratch that persists across the KV grid steps.
  * Block shapes are MXU-aligned: q/kv tiles default to 128 rows with the
    full head_dim (a multiple of 64/128 for every assigned arch) as the
    lane dimension.  Operands are head-major, (B, H, T, hd), so each tile
    is (rows, hd) in the last two dimensions: Mosaic refuses a block of
    one head in the second-minor dimension of a token-major array.
  * GQA is expressed in the k/v index_map (kv_head = q_head // q_per_kv):
    no KV replication in VMEM.
  * Causal masking is positional arithmetic on the running offsets, so
    whole KV tiles beyond the chunk's last query position are skipped
    via @pl.when (the TPU equivalent of early block exit).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(off_ref,                      # scalar-prefetch: (B,) offsets
            q_ref, k_ref, v_ref,          # VMEM tiles
            *rest,                        # [k/v scale tiles,] out, scratch
            bq: int, bk: int, qpk: int, scale: float, n_kv: int,
            quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    off = off_ref[b]
    qpos = off + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # skip KV tiles strictly above the chunk's causal frontier
    @pl.when(ik * bk <= off + (iq + 1) * bq - 1)
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0, 0, :, :].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quantized:
            # in-register dequant: per-token f32 scales, a (1, bk) lane
            # vector streamed through the same (b, ik) tiling as the KV
            # codes; K's row scales land on the score columns, V's on p
            s = s * ks_ref[0]
        s = jnp.where(kpos <= qpos, s, NEG_INF)
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

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def chunked_prefill_attention(q, k, v, offsets, k_scales=None, v_scales=None,
                              *, bq: int = 128, bk: int = 128,
                              interpret: bool = False):
    """q: (B,H,Tq,hd); k,v: (B,KV,S,hd); offsets: (B,) int32 -> (B,H,Tq,hd)

    S and Tq are padded to the tile sizes by the ops wrapper.
    ``k_scales``/``v_scales``: optional (B, 1, S) f32 per-token dequant
    scales when k/v hold quantized (fp8/int8) codes.
    """
    B, H, Tq, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    qpk = H // KV
    bq = min(bq, Tq)
    bk = min(bk, S)
    assert Tq % bq == 0 and S % bk == 0, (Tq, bq, S, bk)
    n_q, n_kv = Tq // bq, S // bk
    grid = (B, H, n_q, n_kv)
    quantized = k_scales is not None

    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, qpk=qpk, scale=1.0 / np.sqrt(hd), n_kv=n_kv,
        quantized=quantized)

    in_specs = [
        pl.BlockSpec((1, 1, bq, hd),
                     lambda b, h, iq, ik, off: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bk, hd),
                     lambda b, h, iq, ik, off: (b, h // qpk, ik, 0)),
        pl.BlockSpec((1, 1, bk, hd),
                     lambda b, h, iq, ik, off: (b, h // qpk, ik, 0)),
    ]
    operands = [q, k, v]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, bk), lambda b, h, iq, ik, off: (b, 0, ik)),
            pl.BlockSpec((1, 1, bk), lambda b, h, iq, ik, off: (b, 0, ik)),
        ]
        operands += [k_scales.astype(jnp.float32),
                     v_scales.astype(jnp.float32)]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, bq, hd),
                                   lambda b, h, iq, ik, off: (b, h, iq, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq,), jnp.float32),
                pltpu.VMEM((bq,), jnp.float32),
                pltpu.VMEM((bq, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Tq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(offsets, *operands)

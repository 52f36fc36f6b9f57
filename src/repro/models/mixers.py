"""Temporal-mixing blocks: (local/full) GQA attention, Mamba2 SSD, RG-LRU.

All mixers share one calling convention::

    y, new_cache = mixer_fwd(kind, params, x, cfg, cache=..., pos_offset=...)

* ``cache=None``      -> full-sequence training/prefill (causal).
* ``cache={...}``     -> serving: write this chunk's state into the cache at
                         ``pos_offset`` and attend over everything cached so
                         far.  Decode is simply a chunk of length 1.

Attention caches store absolute token positions per slot (``pos``, -1 =
empty), which makes full and sliding-window (ring-buffer) caches share one
masking rule: ``valid = 0 <= kpos <= qpos  and  qpos - kpos < window``.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import (
    ParamFactory, apply_rope, init_norm, norm_fwd, rms_head_norm, rope_tables,
)
from repro.models.tp import tp_axis

NEG_INF = -1e30


# ==========================================================================
# Attention (full / local window, GQA, optional qkv bias / qk-norm / cross)
# ==========================================================================
def init_attention(pf: ParamFactory, cfg: ModelConfig, cross: bool = False):
    dm, hd = cfg.d_model, cfg.hd
    H, KV = cfg.n_heads, cfg.n_kv_heads
    p = {
        "wq": pf.dense(dm, H * hd),
        "wk": pf.dense(dm, KV * hd),
        "wv": pf.dense(dm, KV * hd),
        "wo": pf.dense(H * hd, dm),
    }
    if cfg.qkv_bias:
        p["bq"] = pf.zeros(H * hd)
        p["bk"] = pf.zeros(KV * hd)
        p["bv"] = pf.zeros(KV * hd)
    if cfg.qk_norm and not cross:
        p["q_norm"] = pf.ones(hd)
        p["k_norm"] = pf.ones(hd)
    return p


def _project_qkv(p, cfg: ModelConfig, xq, xkv):
    B, Tq, _ = xq.shape
    Tk = xkv.shape[1]
    # head counts come from the *parameter* widths, not the config:
    # inside a tensor-parallel shard_map body each shard sees only its
    # slice of the head dims (cfg keeps the global counts)
    hd = cfg.hd
    H = p["wq"].shape[-1] // hd
    KV = p["wk"].shape[-1] // hd
    q = (xq @ p["wq"])
    k = (xkv @ p["wk"])
    v = (xkv @ p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, Tq, H, hd)
    k = k.reshape(B, Tk, KV, hd)
    v = v.reshape(B, Tk, KV, hd)
    if "q_norm" in p:
        q = rms_head_norm(p["q_norm"], q)
        k = rms_head_norm(p["k_norm"], k)
    return q, k, v


def _attn_out(y, p, cfg: ModelConfig):
    """Output projection; under tensor parallelism a head-sharded
    ``wo`` (first dim < global H*hd) produces partial sums that psum
    over the mesh axis so the residual add sees replicated values.  A
    replicated ``wo`` (heads didn't divide the axis) must not be
    summed."""
    out = y @ p["wo"]
    ax = tp_axis()
    if ax is not None and p["wo"].shape[0] != cfg.n_heads * cfg.hd:
        out = jax.lax.psum(out, ax)
    return out


def _gqa_scores_to_out(cfg: ModelConfig, q, k, v, mask):
    """q: (B,Tq,H,hd); k,v: (B,S,KV,hd); mask: (B,Tq,S) bool or None."""
    B, Tq, H, hd = q.shape
    KV = k.shape[2]
    qpk = H // KV
    qg = q.reshape(B, Tq, KV, qpk, hd)
    scores = jnp.einsum("btkgh,bskh->bkgts", qg, k).astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskh->btkgh", w, v)
    return out.reshape(B, Tq, H * hd)


# Flash threshold: below this KV length the materialized (T,S) path is
# cheaper than the scan's bookkeeping.  Env-tunable for A/B rooflines.
FLASH_MIN_KV = int(os.environ.get("REPRO_FLASH_MIN_KV", "2048"))
FLASH_BLOCK = int(os.environ.get("REPRO_FLASH_BLOCK", "1024"))


def _flash_gqa(cfg: ModelConfig, q, k, v, qpos, kpos, window: int = 0,
               block: int = FLASH_BLOCK, unroll: bool = False, extra=None,
               return_stats: bool = False):
    """Block-streamed online-softmax attention (beyond-paper §Perf opt).

    Never materializes the (Tq, S) score matrix: KV is consumed in
    ``block``-sized tiles with running (m, l, acc) statistics — the jnp
    mirror of kernels/chunked_prefill_attention.py, so the compiled HBM
    roofline matches what the Pallas kernel achieves on TPU.

    q: (B,Tq,H,hd); k,v: (B,S,KV,hd); qpos: (B,Tq); kpos: (B,S) with -1
    marking invalid slots.  Causal: attend iff 0 <= kpos <= qpos (and
    within ``window`` if set).
    """
    B, Tq, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    qpk = H // KV
    block = min(block, S)
    pad = (-S) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, ((0, 0), (0, pad)), constant_values=-1)
    nb = k.shape[1] // block
    scale = 1.0 / np.sqrt(hd)
    # Keep matmul operands in the storage dtype and accumulate in f32 via
    # preferred_element_type (what the MXU does): an astype(f32) here
    # would MATERIALIZE an f32 copy of every KV tile — measured 10x bytes
    # inflation on the decode roofline (see EXPERIMENTS.md §Perf).
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(B, Tq, KV, qpk, hd)

    # Stream tiles with dynamic_slice on the ORIGINAL (B,S,KV,hd) layout.
    # (An earlier version scanned over a moveaxis'd (nb,B,block,...) stack;
    # that materializes a full transposed copy of the KV cache per layer —
    # +44 GB/layer on the decode roofline.  See EXPERIMENTS.md §Perf.)
    m0 = jnp.full((B, KV, qpk, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, qpk, Tq), jnp.float32)
    a0 = jnp.zeros((B, KV, qpk, Tq, hd), jnp.float32)

    def tile(carry, kb, vb, kpb):
        m, l, acc = carry
        s = jnp.einsum("btkgh,bskh->bkgts", qg, kb.astype(qg.dtype),
                       preferred_element_type=jnp.float32)     # (B,KV,g,Tq,bk)
        ok = (kpb[:, None, :] >= 0) & (kpb[:, None, :] <= qpos[:, :, None])
        if window:
            ok &= (qpos[:, :, None] - kpb[:, None, :]) < window
        s = jnp.where(ok[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgts,bskh->bkgth", p.astype(v.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new)

    def body(carry, i):
        kb = jax.lax.dynamic_slice_in_dim(k, i * block, block, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, i * block, block, axis=1)
        kpb = jax.lax.dynamic_slice_in_dim(kpos, i * block, block, axis=1)
        return tile(carry, kb, vb, kpb), 0

    if unroll:       # cost-extraction mode: count every tile exactly once
        carry = (m0, l0, a0)
        for i in range(nb):
            carry, _ = body(carry, i)
    else:
        carry, _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nb))
    if extra is not None:
        carry = tile(carry, *extra)       # in-flight (unappended) K/V tile
    m, l, acc = carry
    if return_stats:
        return m, l, acc
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(B, Tq, H * hd)       # (B,Tq,KV,g,hd)
    return out.astype(q.dtype)


# Set by launch/dryrun when the KV cache's SEQUENCE dim is model-sharded
# (kv_heads not divisible by the model axis): {"mesh": Mesh, "axis": str}.
# Decode then runs flash-decoding via shard_map — per-shard flash over the
# local KV slice + cross-shard online-softmax combine (pmax/psum of the
# (m, l, acc) stats) — instead of letting GSPMD replicate the whole cache
# ("involuntary full rematerialization").  §Perf iteration C1.
SEQ_SHARD: dict = {}

# Set by launch/dryrun for prefill: the cache sharding the constructed
# (scatter-free, §Perf C2) full-prompt cache must keep — without the
# constraint, ck = k inherits the activations' sharding and the per-layer
# attention loses its model-axis parallelism (measured 4x compute / 6x
# memory regression on grok prefill).
PREFILL_CACHE_SHARD: dict = {}


def _constrain_cache(ck, cv, cpos):
    if not PREFILL_CACHE_SHARD:
        return ck, cv, cpos
    from jax.sharding import NamedSharding
    mesh = PREFILL_CACHE_SHARD["mesh"]
    ck = jax.lax.with_sharding_constraint(
        ck, NamedSharding(mesh, PREFILL_CACHE_SHARD["kv_spec"]))
    cv = jax.lax.with_sharding_constraint(
        cv, NamedSharding(mesh, PREFILL_CACHE_SHARD["kv_spec"]))
    cpos = jax.lax.with_sharding_constraint(
        cpos, NamedSharding(mesh, PREFILL_CACHE_SHARD["pos_spec"]))
    return ck, cv, cpos


def _flash_decode_seqsharded(cfg: ModelConfig, q, k, v, qpos, kpos,
                             window: int, unroll: bool, extra):
    mesh, axis = SEQ_SHARD["mesh"], SEQ_SHARD["axis"]
    from jax.sharding import PartitionSpec as P
    B, Tq, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    qpk = H // KV
    if S % mesh.shape[axis] != 0:
        # cache seq not divisible by the model axis: plain flash fallback
        return _flash_gqa(cfg, q, k, v, qpos, kpos, window=window,
                          unroll=unroll, extra=extra)
    d_axes = tuple(a for a in ("pod", "data") if a in mesh.shape) or None
    if d_axes is not None:
        nd = 1
        for a in d_axes:
            nd *= mesh.shape[a]
        if B % nd != 0:
            d_axes = None          # tiny batch (long_500k B=1): replicate

    def body(q_l, k_l, v_l, qpos_l, kpos_l, ek, ev, epos):
        # q replicated over the model axis (tiny at decode); KV seq-local.
        m, l, acc = _flash_gqa(cfg, q_l, k_l, v_l, qpos_l, kpos_l,
                               window=window, unroll=unroll,
                               return_stats=True)
        m_g = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, axis)
        acc_g = jax.lax.psum(acc * corr[..., None], axis)
        # the in-flight (unappended) K/V tile joins once, after the merge
        if ek is not None:
            Bl, Tl = q_l.shape[0], q_l.shape[1]   # shard_map-local shapes
            s = jnp.einsum("btkgh,bskh->bkgts",
                           q_l.reshape(Bl, Tl, KV, qpk, hd), ek,
                           preferred_element_type=jnp.float32)
            s = s / np.sqrt(hd)
            ok = (epos[:, None, :] >= 0) & (epos[:, None, :] <= qpos_l[:, :, None])
            s = jnp.where(ok[:, None, None], s, NEG_INF)
            m_n = jnp.maximum(m_g, s.max(-1))
            pw = jnp.exp(s - m_n[..., None])
            alpha = jnp.exp(m_g - m_n)
            l_g = l_g * alpha + pw.sum(-1)
            acc_g = acc_g * alpha[..., None] + jnp.einsum(
                "bkgts,bskh->bkgth", pw.astype(ev.dtype), ev,
                preferred_element_type=jnp.float32)
        out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        out = jnp.moveaxis(out, 3, 1).reshape(
            q_l.shape[0], q_l.shape[1], H * hd)
        return out.astype(q_l.dtype)

    in_specs = (P(d_axes, None, None, None),       # q (replicated on model)
                P(d_axes, axis, None, None),       # k seq-sharded
                P(d_axes, axis, None, None),       # v
                P(d_axes, None),                   # qpos
                P(d_axes, axis),                   # kpos
                P(d_axes, None, None, None),       # extra k (in-flight)
                P(d_axes, None, None, None),       # extra v
                P(d_axes, None))                   # extra pos
    sm = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=P(d_axes, None, None), check_vma=False)
    ek, ev, epos = extra if extra is not None else (None, None, None)
    if ek is None:
        ek = jnp.zeros((B, 1, KV, hd), k.dtype)
        ev = jnp.zeros((B, 1, KV, hd), v.dtype)
        epos = jnp.full((B, 1), -1, kpos.dtype)
    # scale inside _flash_gqa applies to q; the extra-tile path scales
    # explicitly above
    return sm(q, k, v, qpos, kpos, ek, ev, epos)


def _paged_attention_fwd(p, q, k, v, cfg: ModelConfig, cache, batch_pos,
                         block_tables, page_size: int,
                         active, token_mask):
    """Attention over a paged KV pool (the DynaServe serving hot path).

    The chunk's K/V is scatter-written into physical pages chosen from
    the per-slot block table, then attention dispatches to the Pallas
    kernels: single-token batches (decode) stream pages straight from
    the pool via ``paged_decode_attention``; longer chunks (prefill /
    mixed) gather the slots' pages to a dense prefix and run
    ``chunked_prefill_attention``.  On CPU both kernels execute in
    interpret mode, so the identical code path runs in tests and on TPU.
    Returns (y_pre_wo, new_cache).
    """
    from repro.kernels.ops import (
        paged_decode_attention_op, paged_prefill_attention_op, quantize_kv,
    )
    B, T = batch_pos.shape
    n_pages = cache["k_pages"].shape[0]
    logical = batch_pos // page_size                       # (B, T)
    within = batch_pos % page_size
    n_pp = block_tables.shape[1]
    phys = jnp.take_along_axis(block_tables,
                               jnp.clip(logical, 0, n_pp - 1), axis=1)
    wmask = None
    if active is not None:
        wmask = jnp.broadcast_to(active[:, None], (B, T))
    if token_mask is not None:
        wmask = token_mask if wmask is None else (wmask & token_mask)
    if wmask is not None:
        # pad / inactive tokens must not touch the pool: redirect their
        # writes to the (nonexistent) page n_pages and drop them
        phys = jnp.where(wmask, phys, n_pages)
    quantized = "k_scales" in cache
    if quantized:
        # quantize-on-write: fp8/int8 codes into the page pool plus one
        # f32 amax scale per token row, scattered by the same
        # (phys, within) coordinates (and the same drop masking)
        prec = "int8" if cache["k_pages"].dtype == jnp.int8 else "fp8"
        kq, ksc = quantize_kv(k, prec)                    # (B,T,KV,hd),(B,T)
        vq, vsc = quantize_kv(v, prec)
        # pools are (n_pages, KV, page, hd): the (phys, :, within) index
        # takes a (B, T, KV, hd) update; scale planes are (n_pages, 1, page)
        ck = cache["k_pages"].at[phys, :, within].set(kq, mode="drop")
        cv = cache["v_pages"].at[phys, :, within].set(vq, mode="drop")
        cks = cache["k_scales"].at[phys, 0, within].set(ksc, mode="drop")
        cvs = cache["v_scales"].at[phys, 0, within].set(vsc, mode="drop")
        new_cache = {"k_pages": ck, "v_pages": cv,
                     "k_scales": cks, "v_scales": cvs}
    else:
        ck = cache["k_pages"].at[phys, :, within].set(
            k.astype(cache["k_pages"].dtype), mode="drop")
        cv = cache["v_pages"].at[phys, :, within].set(
            v.astype(cache["v_pages"].dtype), mode="drop")
        cks = cvs = None
        new_cache = {"k_pages": ck, "v_pages": cv}
    if T == 1:
        lengths = batch_pos[:, 0] + 1
        y = paged_decode_attention_op(q[:, 0], ck, cv, block_tables, lengths,
                                      cks, cvs)
        return y.reshape(B, 1, -1), new_cache
    y = paged_prefill_attention_op(q, ck, cv, block_tables, batch_pos[:, 0],
                                   cks, cvs)
    return y.reshape(B, T, -1), new_cache


def attention_fwd(p, x, cfg: ModelConfig, *, kind: str = "attn",
                  cache: Optional[dict] = None, pos_offset=0,
                  window_override: Optional[int] = None,
                  active: Optional[jax.Array] = None,
                  token_mask: Optional[jax.Array] = None,
                  valid_len: Optional[jax.Array] = None,
                  unroll: bool = False, append_external: bool = False,
                  block_tables=None, page_size: int = 0):
    """Self-attention. Returns (y, new_cache).

    ``pos_offset`` may be a scalar or a per-request (B,) vector (unified
    decode batches where each request sits at a different length).
    ``active``: optional (B,) bool — cache writes for inactive slots are
    suppressed (empty pool slots in the serving engine).
    ``block_tables`` (with a paged cache holding ``k_pages``/``v_pages``)
    selects the paged-attention path.
    """
    B, T, _ = x.shape
    window = window_override if window_override is not None else (
        cfg.window if kind == "local_attn" else 0)
    q, k, v = _project_qkv(p, cfg, x, x)

    if cache is not None and "k_pages" in cache:
        assert block_tables is not None and page_size > 0, \
            "paged cache needs block_tables + page_size"
        po = jnp.asarray(pos_offset)
        if po.ndim == 0:
            batch_pos = jnp.broadcast_to((po + jnp.arange(T))[None], (B, T))
        else:
            batch_pos = po[:, None] + jnp.arange(T)[None]
        if cfg.pos_embedding == "rope":
            sin, cos = rope_tables(batch_pos, cfg.hd, cfg.rope_theta,
                                   cfg.rope_fraction)
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
        y, new_cache = _paged_attention_fwd(
            p, q, k, v, cfg, cache, batch_pos, block_tables, page_size,
            active, token_mask)
        return _attn_out(y, p, cfg), new_cache

    if cache is None:
        positions = jnp.arange(T)
        if cfg.pos_embedding == "rope":
            sin, cos = rope_tables(positions, cfg.hd, cfg.rope_theta, cfg.rope_fraction)
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
        if T >= FLASH_MIN_KV:
            pos_b = jnp.broadcast_to(positions[None], (B, T))
            y = _flash_gqa(cfg, q, k, v, pos_b, pos_b, window=window,
                           unroll=unroll)
            return _attn_out(y, p, cfg), None
        qpos = positions[:, None]
        kpos = positions[None, :]
        m = kpos <= qpos
        if window:
            m &= (qpos - kpos) < window
        mask = jnp.broadcast_to(m[None], (B, T, T))
        y = _gqa_scores_to_out(cfg, q, k, v, mask)
        return _attn_out(y, p, cfg), None

    # ---- cached path (prefill chunk / decode) -----------------------------
    po = jnp.asarray(pos_offset)
    if po.ndim == 0:
        batch_pos = jnp.broadcast_to((po + jnp.arange(T))[None], (B, T))
    else:
        batch_pos = po[:, None] + jnp.arange(T)[None]          # (B, T)
    if append_external:
        # Decode fast path (beyond-paper §Perf): the cache is READ-ONLY in
        # the hot step; the new token's K/V rides as an in-flight flash
        # tile and is returned as a delta for the cache manager to append.
        # Eliminates the whole-buffer functional scatter+copy per layer.
        assert cache is not None
        sin, cos = rope_tables(batch_pos, cfg.hd, cfg.rope_theta,
                               cfg.rope_fraction)
        if cfg.pos_embedding == "rope":
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
        # barrier: stops XLA re-slicing the layer's cache into every
        # flash tile fusion (65x full-buffer slice duplication without)
        ckr, cvr, cpr = jax.lax.optimization_barrier(
            (cache["k"], cache["v"], cache["pos"]))
        if SEQ_SHARD:
            y = _flash_decode_seqsharded(cfg, q, ckr, cvr, batch_pos, cpr,
                                         window, unroll, (k, v, batch_pos))
        else:
            y = _flash_gqa(cfg, q, ckr, cvr, batch_pos,
                           cpr, window=window, unroll=unroll,
                           extra=(k, v, batch_pos))
        return _attn_out(y, p, cfg), {"k_delta": k, "v_delta": v,
                                   "pos_delta": batch_pos}
    if cfg.pos_embedding == "rope":
        sin, cos = rope_tables(batch_pos, cfg.hd, cfg.rope_theta, cfg.rope_fraction)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    S_buf = cache["k"].shape[1]
    # Full-prompt prefill (pos_offset statically 0, chunk covers the whole
    # buffer): the chunk IS the cache — write by construction instead of a
    # scatter.  Removes the scatter that (a) XLA charges at full buffer
    # size and (b) triggers involuntary-remat copies when the cache seq
    # dim is model-sharded.  §Perf iteration C2.
    if (T == S_buf and isinstance(pos_offset, int) and pos_offset == 0
            and active is None and token_mask is None):
        ck = k.astype(cache["k"].dtype)
        cv = v.astype(cache["v"].dtype)
        cpos = batch_pos.astype(cache["pos"].dtype)
        ck, cv, cpos = _constrain_cache(ck, cv, cpos)
        if S_buf >= FLASH_MIN_KV:
            y = _flash_gqa(cfg, q, ck, cv, batch_pos, cpos, window=window,
                           unroll=unroll)
        else:
            qp = batch_pos[:, :, None]
            kp = cpos[:, None, :]
            mask = kp <= qp
            if window:
                mask &= (qp - kp) < window
            y = _gqa_scores_to_out(cfg, q, ck, cv, mask)
        return _attn_out(y, p, cfg), {"k": ck, "v": cv, "pos": cpos}
    if window and S_buf == window:       # ring buffer
        slots = batch_pos % window
    else:
        slots = batch_pos
    bidx = jnp.arange(B)[:, None]
    kw = k.astype(cache["k"].dtype)
    vw = v.astype(cache["v"].dtype)
    pw = batch_pos.astype(cache["pos"].dtype)
    wmask = None
    if active is not None:
        wmask = jnp.broadcast_to(active[:, None], (B, T))
    if token_mask is not None:
        wmask = token_mask if wmask is None else (wmask & token_mask)
    if wmask is not None:
        # Masked (pad / inactive) tokens must not touch the cache.  With a
        # ring buffer, a pad at position p+window aliases the slot of the
        # valid token at position p, so "write back the old value" races
        # the real write — redirect masked writes out of bounds + drop.
        slots = jnp.where(wmask, slots, S_buf)
    ck = cache["k"].at[bidx, slots].set(kw, mode="drop")
    cv = cache["v"].at[bidx, slots].set(vw, mode="drop")
    cpos = cache["pos"].at[bidx, slots].set(pw, mode="drop")

    if S_buf >= FLASH_MIN_KV:
        if SEQ_SHARD and T <= 8:
            y = _flash_decode_seqsharded(cfg, q, ck, cv, batch_pos, cpos,
                                         window, unroll, None)
        else:
            y = _flash_gqa(cfg, q, ck, cv, batch_pos, cpos, window=window,
                           unroll=unroll)
        return _attn_out(y, p, cfg), {"k": ck, "v": cv, "pos": cpos}
    # (external-append handled above; small caches keep the simple path)
    qpos = batch_pos[:, :, None]                        # (B, T, 1)
    kpos = cpos[:, None, :]                             # (B, 1, S_buf)
    mask = (kpos >= 0) & (kpos <= qpos)
    if window:
        mask &= (qpos - kpos) < window
    y = _gqa_scores_to_out(cfg, q, ck, cv, mask)
    return _attn_out(y, p, cfg), {"k": ck, "v": cv, "pos": cpos}


def init_cross_attention(pf: ParamFactory, cfg: ModelConfig):
    return init_attention(pf, cfg, cross=True)


def cross_attention_fwd(p, x, cfg: ModelConfig, *, enc_out=None, cache=None):
    """Cross-attention for enc-dec decoders.  KV comes from the encoder
    output; computed once (when ``enc_out`` is given) and cached."""
    if cache is not None and enc_out is None:
        xk, xv = cache["xk"], cache["xv"]
        q, _, _ = _project_qkv(p, cfg, x, x[:, :1])   # kv unused
    else:
        q, xk, xv = _project_qkv(p, cfg, x, enc_out)
    y = _gqa_scores_to_out(cfg, q, xk, xv, None)
    new_cache = {"xk": xk, "xv": xv} if cache is not None else None
    return _attn_out(y, p, cfg), new_cache


# ==========================================================================
# Mamba2 SSD (state-space duality, chunked)
# ==========================================================================
def init_ssd(pf: ParamFactory, cfg: ModelConfig):
    dm, di = cfg.d_model, cfg.d_inner
    G, N, H = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    conv_dim = di + 2 * G * N
    return {
        "in_proj": pf.dense(dm, 2 * di + 2 * G * N + H),
        "conv_w": pf.dense(cfg.ssm_conv, conv_dim, scale=0.5),
        "conv_b": pf.zeros(conv_dim),
        "A_log": pf.uniform(H, lo=0.0, hi=1.3),   # A = -exp(A_log)
        "D": pf.ones(H),
        "dt_bias": pf.uniform(H, lo=-4.0, hi=-1.0),
        "norm": pf.ones(di),
        "out_proj": pf.dense(di, dm),
    }


def _segsum(x):
    """x: (..., T) -> (..., T, T) lower-tri cumulative segment sums."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    d = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, d, -jnp.inf)


def ssd_scan(Xd, dtA, Bm, Cm, chunk: int, init_state):
    """Chunked SSD.

    Xd:  (b, l, h, p)  dt-discretized inputs (x * dt)
    dtA: (b, l, h)     dt * A (negative)
    Bm/Cm: (b, l, h, n) per-head (groups already broadcast)
    init_state: (b, h, p, n) float32
    Returns y (b, l, h, p), final_state.
    """
    b, l, h, pdim = Xd.shape
    n = Bm.shape[-1]
    cs = min(chunk, l)
    assert l % cs == 0, (l, cs)
    nc = l // cs

    def r(t):  # (b, l, ...) -> (nc, b, cs, ...)
        return jnp.moveaxis(t.reshape(b, nc, cs, *t.shape[2:]), 1, 0)

    Xc, Ac, Bc, Cc = r(Xd), r(dtA), r(Bm), r(Cm)
    Acum = jnp.cumsum(Ac, axis=2)                          # (nc,b,cs,h)
    # intra-chunk (quadratic within chunk)
    L = jnp.exp(_segsum(jnp.moveaxis(Ac, -1, -2)))         # (nc,b,h,cs,cs)
    Ydiag = jnp.einsum("cbzhn,cbshn,cbhzs,cbshp->cbzhp",
                       Cc, Bc, L.astype(Cc.dtype), Xc)
    # states emitted by each chunk
    decay_to_end = jnp.exp(Acum[:, :, -1:, :] - Acum)      # (nc,b,cs,h)
    states = jnp.einsum("cbshn,cbsh,cbshp->cbhpn",
                        Bc, decay_to_end.astype(Bc.dtype), Xc)
    chunk_decay = jnp.exp(Acum[:, :, -1, :])               # (nc,b,h)

    def step(carry, inp):
        st, dec = inp
        new = carry * dec[..., None, None].astype(carry.dtype) + st.astype(carry.dtype)
        return new, carry                                  # emit state *before* chunk

    final_state, prev_states = jax.lax.scan(
        step, init_state.astype(jnp.float32),
        (states, chunk_decay))
    # inter-chunk contribution
    decay_from_start = jnp.exp(Acum)                       # (nc,b,cs,h)
    Yoff = jnp.einsum("cbzhn,cbhpn,cbzh->cbzhp",
                      Cc, prev_states.astype(Cc.dtype),
                      decay_from_start.astype(Cc.dtype))
    Y = Ydiag + Yoff
    Y = jnp.moveaxis(Y, 0, 1).reshape(b, l, h, pdim)
    return Y, final_state


def _causal_conv(x, w, b, tail=None, valid_len=None):
    """Depthwise causal conv.  x: (B, T, C), w: (K, C), tail: (B, K-1, C).

    ``valid_len``: per-row count of real (non-pad) tokens; the new tail is
    gathered from the last K-1 *valid* inputs so right-padding a chunk
    cannot pollute the next chunk's conv state."""
    K = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K))
    if K > 1:
        if valid_len is None:
            new_tail = xp[:, -(K - 1):]
        else:
            idx = valid_len[:, None] + jnp.arange(K - 1)[None]   # (B, K-1)
            new_tail = jnp.take_along_axis(xp, idx[..., None], axis=1)
    else:
        new_tail = tail
    return out + b, new_tail


def ssd_fwd(p, x, cfg: ModelConfig, *, cache: Optional[dict] = None,
            pos_offset=0, active: Optional[jax.Array] = None,
            token_mask: Optional[jax.Array] = None,
            valid_len: Optional[jax.Array] = None):
    """Mamba2 block. x: (B, T, dm). Returns (y, new_cache).

    ``token_mask`` (B, T): right-pad tokens get dt=0 — an exact identity
    recurrence step — so padded mixed batches leave the SSD state correct.
    """
    B, T, dm = x.shape
    di, G, N, H = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    P = cfg.ssm_head_dim
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt_raw = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
    conv_tail = cache["conv"] if cache is not None else None
    xbc, new_tail = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_tail,
                                 valid_len=valid_len)
    xbc = jax.nn.silu(xbc)
    xs, Bm, Cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    xs = xs.reshape(B, T, H, P)
    Bm = Bm.reshape(B, T, G, N)
    Cm = Cm.reshape(B, T, G, N)
    rep = H // G
    Bm = jnp.repeat(Bm, rep, axis=2)
    Cm = jnp.repeat(Cm, rep, axis=2)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    if token_mask is not None:
        dt = dt * token_mask[..., None].astype(dt.dtype)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))           # (H,)
    dtA = dt * A                                           # (B,T,H)
    Xd = xs * dt[..., None].astype(xs.dtype)

    state0 = (cache["state"] if cache is not None
              else jnp.zeros((B, H, P, N), jnp.float32))
    chunk = 1 if T == 1 else cfg.ssm_chunk
    if T % chunk != 0:
        chunk = 1 if T < cfg.ssm_chunk else T // (T // cfg.ssm_chunk)
        while T % chunk:
            chunk -= 1
    y, state = ssd_scan(Xd, dtA.astype(jnp.float32), Bm, Cm, chunk, state0)
    if cache is not None and active is not None:
        state = jnp.where(active[:, None, None, None], state, cache["state"])
        new_tail = jnp.where(active[:, None, None], new_tail, cache["conv"])
    y = y + xs * p["D"].astype(xs.dtype)[None, None, :, None]
    y = y.reshape(B, T, di)
    # gated rmsnorm then out proj (mamba2 ordering)
    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    y = (yf * jax.lax.rsqrt(jnp.mean(jnp.square(yf), -1, keepdims=True) + 1e-6)
         ).astype(x.dtype) * p["norm"]
    out = y @ p["out_proj"]
    new_cache = None
    if cache is not None:
        new_cache = {"state": state, "conv": new_tail}
    return out, new_cache


# ==========================================================================
# RG-LRU (RecurrentGemma / Griffin recurrent block)
# ==========================================================================
def init_rglru(pf: ParamFactory, cfg: ModelConfig):
    dm, W = cfg.d_model, cfg.lru_dim
    return {
        "w_gate": pf.dense(dm, W),          # gelu branch
        "w_in": pf.dense(dm, W),            # recurrent branch
        "conv_w": pf.dense(cfg.lru_conv, W, scale=0.5),
        "conv_b": pf.zeros(W),
        "w_a": pf.dense(W, W, scale=0.02),  # recurrence gate
        "b_a": pf.zeros(W),
        "w_x": pf.dense(W, W, scale=0.02),  # input gate
        "b_x": pf.zeros(W),
        "lam": pf.uniform(W, lo=2.0, hi=6.0),   # Λ; a = exp(-c·softplus(Λ)·r)
        "w_out": pf.dense(W, dm),
    }


def rglru_fwd(p, x, cfg: ModelConfig, *, cache: Optional[dict] = None,
              pos_offset=0, active: Optional[jax.Array] = None,
              token_mask: Optional[jax.Array] = None,
              valid_len: Optional[jax.Array] = None, c: float = 8.0):
    B, T, dm = x.shape
    gate = jax.nn.gelu(x @ p["w_gate"])
    u = x @ p["w_in"]
    tail = cache["conv"] if cache is not None else None
    u, new_tail = _causal_conv(u, p["conv_w"], p["conv_b"], tail,
                               valid_len=valid_len)
    r = jax.nn.sigmoid(u @ p["w_a"] + p["b_a"]).astype(jnp.float32)
    i = jax.nn.sigmoid(u @ p["w_x"] + p["b_x"])
    log_a = -c * jax.nn.softplus(p["lam"].astype(jnp.float32)) * r   # (B,T,W)
    if token_mask is not None:
        # pad tokens: a=1, v=0 -> identity recurrence step
        log_a = log_a * token_mask[..., None].astype(log_a.dtype)
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    v = (beta * (i * u).astype(jnp.float32))                          # (B,T,W)
    if token_mask is not None:
        v = v * token_mask[..., None].astype(v.dtype)

    h0 = (cache["h"] if cache is not None
          else jnp.zeros((B, cfg.lru_dim), jnp.float32))
    if T == 1:
        h = a[:, 0] * h0 + v[:, 0]
        hs = h[:, None]
    else:
        # linear recurrence h_t = a_t h_{t-1} + v_t via associative scan,
        # seeded with h0 folded into v_1.
        v = v.at[:, 0].add(a[:, 0] * h0)

        def combine(lhs, rhs):
            a1, v1 = lhs
            a2, v2 = rhs
            return a1 * a2, a2 * v1 + v2

        _, hs = jax.lax.associative_scan(combine, (a, v), axis=1)
        h = hs[:, -1]
    y = (hs.astype(x.dtype) * gate) @ p["w_out"]
    if cache is not None and active is not None:
        h = jnp.where(active[:, None], h, cache["h"])
        new_tail = jnp.where(active[:, None, None], new_tail, cache["conv"])
    new_cache = {"h": h, "conv": new_tail} if cache is not None else None
    return y, new_cache


# ==========================================================================
# dispatch
# ==========================================================================
def init_mixer(pf: ParamFactory, cfg: ModelConfig, kind: str):
    if kind in ("attn", "local_attn"):
        return init_attention(pf, cfg)
    if kind == "ssd":
        return init_ssd(pf, cfg)
    if kind == "rglru":
        return init_rglru(pf, cfg)
    raise ValueError(kind)


def mixer_fwd(kind: str, p, x, cfg: ModelConfig, *, cache=None, pos_offset=0,
              window_override=None, active=None, token_mask=None,
              valid_len=None, unroll=False, append_external=False,
              block_tables=None, page_size=0):
    if kind in ("attn", "local_attn"):
        return attention_fwd(p, x, cfg, kind=kind, cache=cache,
                             pos_offset=pos_offset,
                             window_override=window_override, active=active,
                             token_mask=token_mask, valid_len=valid_len,
                             unroll=unroll, append_external=append_external,
                             block_tables=block_tables, page_size=page_size)
    if kind == "ssd":
        return ssd_fwd(p, x, cfg, cache=cache, pos_offset=pos_offset,
                       active=active, token_mask=token_mask,
                       valid_len=valid_len)
    if kind == "rglru":
        return rglru_fwd(p, x, cfg, cache=cache, pos_offset=pos_offset,
                         active=active, token_mask=token_mask,
                         valid_len=valid_len)
    raise ValueError(kind)

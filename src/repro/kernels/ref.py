"""Pure-jnp oracles for the Pallas kernels.

These are the correctness references the kernel tests assert against
(shape/dtype sweeps with assert_allclose) and double as the portable
fallback path on backends without Pallas.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def chunked_prefill_attention_ref(q, k, v, offsets):
    """Micro-request chunked prefill attention.

    q:        (B, Tq, H, hd)  — the chunk's queries (global positions
                                 offsets[b] + i)
    k, v:     (B, S, KV, hd)  — prefix KV *including* the chunk's own
                                 K/V written at [offsets, offsets+Tq)
    offsets:  (B,) int32      — chunk start position per sequence
    Returns   (B, Tq, H, hd).
    """
    B, Tq, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    qpk = H // KV
    qg = q.reshape(B, Tq, KV, qpk, hd)
    scores = jnp.einsum("btkgh,bskh->bkgts", qg, k).astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    qpos = offsets[:, None] + jnp.arange(Tq)[None]            # (B, Tq)
    kpos = jnp.arange(S)[None]                                # (1, S)
    mask = kpos[:, None, :] <= qpos[..., None]                # (B, Tq, S)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", w.astype(v.dtype), v)
    return out.reshape(B, Tq, H, hd)


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables, lengths):
    """Paged GQA decode attention (one query token per sequence).

    q:            (B, H, hd)
    k_pages:      (n_pages, KV, page, hd) — head-major inside each page
    v_pages:      (n_pages, KV, page, hd)
    block_tables: (B, pages_per_seq) int32 — physical page per logical page
    lengths:      (B,) int32 — valid context per sequence (incl. current tok)
    Returns       (B, H, hd).
    """
    B, H, hd = q.shape
    n_pages, KV, page, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    S = pages_per_seq * page
    # gather logical KV per sequence: (B, n_pp, KV, page, hd) -> (B, S, KV, hd)
    k = jnp.swapaxes(k_pages[block_tables], 2, 3).reshape(B, S, KV, hd)
    v = jnp.swapaxes(v_pages[block_tables], 2, 3).reshape(B, S, KV, hd)
    qpk = H // KV
    qg = q.reshape(B, KV, qpk, hd)
    scores = jnp.einsum("bkgh,bskh->bkgs", qg, k).astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    valid = jnp.arange(S)[None] < lengths[:, None]            # (B, S)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", w.astype(v.dtype), v)
    return out.reshape(B, H, hd)

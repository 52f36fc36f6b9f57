"""Jit'd public wrappers around the Pallas kernels.

The kernels compile to Mosaic on TPU and execute in ``interpret=True``
mode on the CPU backend (the tests).  Inputs are padded to tile
boundaries here so callers can use ragged sizes.

Layouts.  A paged pool stores each page head-major,
``(n_pages, KV, page, hd)``, with per-token scale planes
``(n_pages, 1, page)`` for quantized formats: the shapes whose
(second-minor, minor) block dimensions Mosaic accepts; dense chunked
prefill is head-major for the same reason.  Handoff pieces use the
token-major form ``(..., n, page, KV, hd)`` / ``(..., n, page)``;
``to_pool_layout`` and ``from_pool_layout`` convert between the two.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.precision import get_precision
from repro.kernels.chunked_prefill_attention import chunked_prefill_attention
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels import ref


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted: on the CPU backend
    only.  They compile to Mosaic on TPU; any other backend has no
    implementation and is an error."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"the Pallas kernels run on TPU (compiled) or "
                           f"CPU (interpreted), not on {backend!r}")
    return backend == "cpu"


#: quantization epsilon — the amax floor that keeps scales finite
QUANT_EPS = 1e-8


def kv_storage_dtype(precision, default=jnp.bfloat16):
    """The jnp dtype a KV page pool stores at ``precision``."""
    prec = get_precision(precision)
    if not prec.quantized:
        return default
    if prec.name == "int8":
        return jnp.int8
    return jnp.float8_e4m3fn


@functools.partial(jax.jit, static_argnames=("precision",))
def quantize_kv(x, precision: str):
    """Quantize KV rows to codes + per-token scales.

    ``x``: (..., KV, hd) float; one symmetric amax scale per leading
    index (i.e. per token row across all KV heads and head dims):
    ``scale = max(amax, eps) / qmax``, ``codes ~= x / scale`` stored in
    the precision's dtype.  Returns ``(codes, scales)`` with
    ``scales.shape == x.shape[:-2]`` f32.
    """
    prec = get_precision(precision)
    assert prec.quantized, prec
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scales = jnp.maximum(amax, QUANT_EPS) / prec.qmax
    y = xf / scales[..., None, None]
    dt = kv_storage_dtype(prec)
    if dt == jnp.int8:
        y = jnp.round(y)
    return jnp.clip(y, -prec.qmax, prec.qmax).astype(dt), scales


@jax.jit
def dequantize_kv(codes, scales):
    """Inverse of :func:`quantize_kv`: (codes, scales) -> f32 KV rows."""
    return codes.astype(jnp.float32) * scales[..., None, None]


def to_pool_layout(pages, scales=None):
    """Token-major pages ``(..., n, page, KV, hd)`` (+ scales
    ``(..., n, page)``) -> the pool's head-major ``(..., n, KV, page,
    hd)`` (+ ``(..., n, 1, page)``)."""
    out = jnp.swapaxes(jnp.asarray(pages), -3, -2)
    if scales is None:
        return out, None
    return out, jnp.asarray(scales)[..., None, :]


def from_pool_layout(pages, scales=None):
    """Inverse of :func:`to_pool_layout` (works on numpy or jax
    arrays)."""
    out = pages.swapaxes(-3, -2)
    return out, (None if scales is None else scales[..., 0, :])


def _pad_to(x, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "interpret"))
def chunked_prefill_attention_op(q, k, v, offsets, k_scales=None,
                                 v_scales=None, *, bq: int = 128,
                                 bk: int = 128, interpret: bool | None = None):
    """Chunked prefill on head-major operands: q (B,H,Tq,hd), k/v
    (B,KV,S,hd) -> (B,H,Tq,hd).  Pads Tq/S to tile multiples, runs the
    kernel, un-pads.  ``interpret=None`` means :func:`interpret_mode`.

    ``k_scales``/``v_scales``: optional (B, 1, S) per-token dequant
    scales when k/v hold quantized codes."""
    if interpret is None:
        interpret = interpret_mode()
    Tq = q.shape[2]
    bq_eff = min(bq, max(8, Tq))
    bk_eff = min(bk, max(8, k.shape[2]))
    out = chunked_prefill_attention(
        _pad_to(q, 2, bq_eff), _pad_to(k, 2, bk_eff), _pad_to(v, 2, bk_eff),
        offsets.astype(jnp.int32),
        None if k_scales is None else _pad_to(k_scales, 2, bk_eff),
        None if v_scales is None else _pad_to(v_scales, 2, bk_eff),
        bq=bq_eff, bk=bk_eff, interpret=interpret)
    return out[:, :, :Tq]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_op(q, k_pages, v_pages, block_tables, lengths,
                              k_scales=None, v_scales=None, *,
                              interpret: bool | None = None):
    """q (B,H,hd) against a head-major pool (n_pages,KV,page,hd), scale
    planes (n_pages,1,page) -> (B,H,hd)."""
    if interpret is None:
        interpret = interpret_mode()
    return paged_decode_attention(q, k_pages, v_pages,
                                  block_tables.astype(jnp.int32),
                                  lengths.astype(jnp.int32),
                                  k_scales, v_scales,
                                  interpret=interpret)


def gather_pages(pages, block_tables):
    """Materialize a block-table-indexed page pool as dense head-major
    per-sequence KV: (n_pages, KV, page, hd) + (B, n_pp) ->
    (B, KV, n_pp*page, hd).

    Logical position ``t`` of sequence ``b`` lands at index ``t`` of the
    sequence axis, so the dense causal kernels apply unchanged.  Entries
    past a sequence's allocated table repeat page 0; callers mask them
    (the chunked-prefill kernel's causal frontier never reaches them)."""
    B, n_pp = block_tables.shape
    _, KV, page, hd = pages.shape
    g = pages[block_tables]                          # (B, n_pp, KV, page, hd)
    return g.swapaxes(1, 2).reshape(B, KV, n_pp * page, hd)


def gather_scales(scales, block_tables):
    """Per-page dequant scale planes -> dense per-sequence scales:
    (n_pages, 1, page) + (B, n_pp) -> (B, 1, n_pp*page)."""
    B, n_pp = block_tables.shape
    page = scales.shape[-1]
    return scales[block_tables].reshape(B, 1, n_pp * page)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "interpret"))
def paged_prefill_attention_op(q, k_pages, v_pages, block_tables, offsets,
                               k_scales=None, v_scales=None, *,
                               bq: int = 128, bk: int = 128,
                               interpret: bool | None = None):
    """Chunked prefill over a paged KV pool: gathers the slots' pages to
    dense head-major prefix KV and runs the chunked-prefill kernel.
    ``q`` (B,Tq,H,hd) is the chunk's queries at global positions
    ``offsets[b] + i``; the chunk's own K/V must already be written into
    the pages.  With a quantized pool, ``k_scales``/``v_scales`` are the
    (n_pages, 1, page) scale planes gathered alongside the code pages.
    Returns (B,Tq,H,hd)."""
    tbl = block_tables.astype(jnp.int32)
    k = gather_pages(k_pages, tbl)
    v = gather_pages(v_pages, tbl)
    ks = None if k_scales is None else gather_scales(k_scales, tbl)
    vs = None if v_scales is None else gather_scales(v_scales, tbl)
    out = chunked_prefill_attention_op(q.swapaxes(1, 2), k, v, offsets,
                                       ks, vs, bq=bq, bk=bk,
                                       interpret=interpret)
    return out.swapaxes(1, 2)


# re-export oracles for tests
chunked_prefill_attention_ref = ref.chunked_prefill_attention_ref
paged_decode_attention_ref = ref.paged_decode_attention_ref

"""Unified model: one functional forward for every architecture family.

Layer stacks are built as *pattern groups*: the repeating
``cfg.layer_pattern`` (e.g. RecurrentGemma's (rglru, rglru, local_attn))
is instantiated once per group with parameters stacked along a leading
group axis, and the stack is traversed with ``jax.lax.scan`` so the HLO
stays compact for 80-96 layer models.

Public entry points:
    init_params(cfg, key=..., abstract=False)
    init_cache(cfg, batch, max_len, abstract=False)
    forward(params, cfg, tokens, ...)           # logits (+ cache)
    loss_fn(params, cfg, batch)                 # training loss
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import (
    ParamFactory, init_mlp, init_norm, mlp_fwd, norm_fwd, sinusoidal_table,
)
from repro.models.mixers import (
    cross_attention_fwd, init_cross_attention, init_mixer, mixer_fwd,
)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _scan(f, init, xs, unroll: bool = False):
    """lax.scan or a python unroll (the dry-run's cost-extraction mode:
    XLA cost_analysis counts a while-loop body once, so rooflines must be
    measured on an unrolled module)."""
    if not unroll:
        return jax.lax.scan(f, init, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    carry, ys = init, []
    for i in range(n):
        carry, y = f(carry, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    return carry, ys


def _sinusoidal_of(pos, dim: int):
    """Sinusoidal embedding of (possibly traced) integer positions."""
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] / jnp.power(10000.0, 2 * i / dim)[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ==========================================================================
# parameter construction
# ==========================================================================
def _init_block(pf: ParamFactory, cfg: ModelConfig, kind: str):
    """One block = pre-norm + mixer + (cross-attn) + pre-norm + mlp."""
    p = {
        "norm1": init_norm(pf, cfg),
        "mixer": init_mixer(pf, cfg, kind),
    }
    if kind == "rglru":
        # Griffin recurrent blocks keep their own MLP block too
        pass
    if cfg.cross_attention:
        p["norm_x"] = init_norm(pf, cfg)
        p["cross"] = init_cross_attention(pf, cfg)
    if cfg.mlp != "none" or cfg.moe_experts:
        p["norm2"] = init_norm(pf, cfg)
        p["mlp"] = init_mlp(pf, cfg)
    return p


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs) if not isinstance(xs[0], jax.ShapeDtypeStruct)
                        else jax.ShapeDtypeStruct((len(xs),) + xs[0].shape, xs[0].dtype),
                        *trees)


def _abstract_stack(tree, n):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n,) + x.shape, x.dtype), tree)


def init_params(cfg: ModelConfig, key: Optional[jax.Array] = None,
                abstract: bool = False):
    if key is None:
        key = jax.random.PRNGKey(0)
    pf = ParamFactory(key, _dtype(cfg), abstract=abstract)
    params = {"embed": pf.dense(cfg.vocab_size, cfg.d_model, scale=0.02)}

    # decoder blocks: tuple over pattern positions, each stacked over groups
    if abstract:
        proto = tuple(_init_block(pf, cfg, k) for k in cfg.layer_pattern)
        params["blocks"] = tuple(_abstract_stack(b, cfg.n_groups) for b in proto)
    else:
        blocks = []
        for kind in cfg.layer_pattern:
            per_group = [_init_block(pf, cfg, kind) for _ in range(cfg.n_groups)]
            blocks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_group))
        params["blocks"] = tuple(blocks)

    if cfg.tail_kinds:
        assert not cfg.cross_attention, "tail blocks unsupported for enc-dec"
        params["tail"] = tuple(_init_block(pf, cfg, k) for k in cfg.tail_kinds)

    params["final_norm"] = init_norm(pf, cfg)
    if not cfg.tie_embeddings:
        params["lm_head"] = pf.dense(cfg.d_model, cfg.vocab_size, scale=0.02)

    # encoder stack (audio / enc-dec)
    if cfg.encoder_layers:
        enc_cfg = cfg.with_(n_kv_heads=cfg.n_heads, moe_experts=0, mlp="gelu",
                            layer_pattern=("attn",), cross_attention=False)
        if abstract:
            proto = _init_block(pf, enc_cfg, "attn")
            params["encoder"] = _abstract_stack(proto, cfg.encoder_layers)
        else:
            per = [_init_block(pf, enc_cfg, "attn") for _ in range(cfg.encoder_layers)]
            params["encoder"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
        params["enc_norm"] = init_norm(pf, cfg)

    if cfg.pos_embedding == "learned":
        params["pos_embed"] = pf.dense(32_768 if cfg.arch_type != "audio" else 65_536,
                                       cfg.d_model, scale=0.02)
    return params


def engine_model(arch: str, layers: Optional[int], seed: int = 0):
    """``(cfg, params)`` for the engine backend, weights random from
    ``seed``.  ``layers=None`` is the arch's reduced CPU smoke config;
    an int keeps every published width and cuts the depth to that many
    layers."""
    from repro.configs import get_config, get_smoke_config

    if layers is None:
        cfg = get_smoke_config(arch)
    else:
        cfg = get_config(arch)
        if not 0 < layers <= cfg.n_layers or layers % cfg.pattern_len:
            raise ValueError(
                f"{arch}: cannot cut {cfg.n_layers} layers (pattern of "
                f"{cfg.pattern_len}) to {layers}")
        cfg = cfg.with_(n_layers=layers)
    # one jitted program: no eager per-tensor f32 temporaries on device
    params = jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    return cfg, params


# ==========================================================================
# cache construction
# ==========================================================================
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               abstract: bool = False, window_override: Optional[int] = None):
    """Per-pattern-position caches stacked over groups (for scan)."""
    G = cfg.n_groups
    dt = _dtype(cfg)

    def make(shape, dtype):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        if dtype == jnp.int32:
            return jnp.full(shape, -1, jnp.int32)
        return jnp.zeros(shape, dtype)

    caches = []
    for kind in cfg.layer_pattern:
        eff_window = window_override if window_override is not None else cfg.window
        if kind == "attn" and window_override:
            kind_eff = "local_attn"
        else:
            kind_eff = kind
        if kind_eff in ("attn", "local_attn"):
            S_buf = min(max_len, eff_window) if (kind_eff == "local_attn" and eff_window) else max_len
            c = {
                "k": make((G, batch, S_buf, cfg.n_kv_heads, cfg.hd), dt),
                "v": make((G, batch, S_buf, cfg.n_kv_heads, cfg.hd), dt),
                "pos": make((G, batch, S_buf), jnp.int32),
            }
        elif kind == "ssd":
            c = {
                "state": make((G, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
                "conv": make((G, batch, cfg.ssm_conv - 1,
                              cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state), dt),
            }
        elif kind == "rglru":
            c = {
                "h": make((G, batch, cfg.lru_dim), jnp.float32),
                "conv": make((G, batch, cfg.lru_conv - 1, cfg.lru_dim), dt),
            }
        else:
            raise ValueError(kind)
        caches.append(c)
    cache = {"blocks": tuple(caches)}
    if cfg.tail_kinds:
        tail = init_cache(cfg.with_(n_layers=len(cfg.tail_kinds),
                                    layer_pattern=cfg.tail_kinds,
                                    cross_attention=False),
                          batch, max_len, abstract=abstract,
                          window_override=window_override)
        # strip the G=1 leading dim for tail caches
        cache["tail"] = jax.tree.map(lambda x: (
            jax.ShapeDtypeStruct(x.shape[1:], x.dtype)
            if isinstance(x, jax.ShapeDtypeStruct) else x[0]),
            tail["blocks"])
    if cfg.cross_attention:
        cache["cross"] = {
            "xk": make((cfg.n_layers, batch, cfg.encoder_len, cfg.n_kv_heads, cfg.hd), dt),
            "xv": make((cfg.n_layers, batch, cfg.encoder_len, cfg.n_kv_heads, cfg.hd), dt),
        }
    return cache


# ==========================================================================
# paged cache construction (block-table KV pool)
# ==========================================================================
def supports_paged_kv(cfg: ModelConfig) -> bool:
    """Paged KV applies to pure full-attention decoders: every layer's
    cache grows per token and positions are append-only.  Sliding-window
    ring buffers and recurrent (SSD / RG-LRU) state are O(1)-bounded and
    keep the dense slot cache; encoder-decoder and stub-frontend archs
    prefill below the token embedding and stay dense too."""
    return (all(k == "attn" for k in cfg.layer_pattern)
            and not cfg.tail_kinds
            and not cfg.cross_attention
            and not cfg.window
            and cfg.arch_type not in ("vlm", "audio"))


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     abstract: bool = False, kv_precision=None):
    """Physical page pools, stacked over groups for the scan.

    Unlike ``init_cache`` there is no per-slot sequence axis: slots map
    logical positions to (page, offset) through a block table held by
    the engine's ``BlockAllocator`` and passed into ``forward`` per
    batch.  No ``pos`` array either — a paged position is its logical
    index by construction.

    Pages are head-major, ``(G, n_pages, KV, page_size, hd)``: the
    kernels stream one ``(page_size, hd)`` tile per KV head, the tiling
    Mosaic accepts (``repro.kernels.ops`` converts to and from the
    token-major handoff form).

    ``kv_precision`` (name or ``PagePrecision``, default bf16) selects
    the page storage format: a quantized pool stores fp8/int8 codes in
    ``k_pages``/``v_pages`` plus per-token f32 dequant scales in
    ``k_scales``/``v_scales`` of shape (G, n_pages, 1, page_size) — the
    scale planes the paged kernels prefetch by the same block table."""
    from repro.core.precision import get_precision
    from repro.kernels.ops import kv_storage_dtype

    if not supports_paged_kv(cfg):
        raise ValueError(f"{cfg.name}: layer pattern "
                         f"{cfg.layer_pattern} cannot use a paged KV cache")
    G = cfg.n_groups
    prec = get_precision(kv_precision)
    dt = kv_storage_dtype(prec, default=_dtype(cfg))

    def make(shape, dtype):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jnp.zeros(shape, dtype)

    caches = []
    for _ in cfg.layer_pattern:
        c = {
            "k_pages": make((G, n_pages, cfg.n_kv_heads, page_size, cfg.hd),
                            dt),
            "v_pages": make((G, n_pages, cfg.n_kv_heads, page_size, cfg.hd),
                            dt),
        }
        if prec.quantized:
            c["k_scales"] = make((G, n_pages, 1, page_size), jnp.float32)
            c["v_scales"] = make((G, n_pages, 1, page_size), jnp.float32)
        caches.append(c)
    return {"blocks": tuple(caches)}


# ==========================================================================
# forward
# ==========================================================================
def _block_fwd(kind: str, bp, x, cfg: ModelConfig, *, cache, pos_offset,
               window_override, cross_cache=None, enc_out=None, active=None,
               token_mask=None, valid_len=None, unroll=False,
               append_external=False, block_tables=None, page_size=0):
    h, new_cache = mixer_fwd(
        kind, bp["mixer"], norm_fwd(bp["norm1"], x, cfg.norm), cfg,
        cache=cache, pos_offset=pos_offset, window_override=window_override,
        active=active, token_mask=token_mask, valid_len=valid_len,
        unroll=unroll, append_external=append_external,
        block_tables=block_tables, page_size=page_size)
    x = x + h
    new_cross = None
    if cfg.cross_attention and "cross" in bp:
        h, new_cross = cross_attention_fwd(
            bp["cross"], norm_fwd(bp["norm_x"], x, cfg.norm), cfg,
            enc_out=enc_out, cache=cross_cache)
        x = x + h
    aux = jnp.float32(0.0)
    if "mlp" in bp:
        h, aux = mlp_fwd(bp["mlp"], norm_fwd(bp["norm2"], x, cfg.norm), cfg)
        x = x + h
    return x, new_cache, new_cross, aux


def _encoder_fwd(params, cfg: ModelConfig, frames, unroll: bool = False):
    """frames: (B, enc_len, d_model) stub conv-frontend embeddings."""
    x = frames.astype(_dtype(cfg))
    x = x + sinusoidal_table(x.shape[1], cfg.d_model).astype(x.dtype)[None]
    enc_cfg = cfg.with_(n_kv_heads=cfg.n_heads, moe_experts=0, mlp="gelu",
                        layer_pattern=("attn",), cross_attention=False)

    def step(h, lp):
        h, _, _, _ = _block_fwd("attn", lp, h, enc_cfg, cache=None,
                                pos_offset=0, window_override=None,
                                unroll=unroll)
        return h, 0

    x, _ = _scan(step, x, params["encoder"], unroll=unroll)
    return norm_fwd(params["enc_norm"], x, cfg.norm)


def forward(params, cfg: ModelConfig, tokens, *, cache=None, pos_offset=0,
            extra_embeds=None, frames=None, window_override=None,
            active=None, n_valid=None, last_only: bool = False,
            remat: bool = False, unroll: bool = False,
            append_external: bool = False,
            logits_slice: Optional[int] = None,
            block_tables=None, page_size: int = 0):
    """Run the decoder stack.

    tokens: (B, T) int32.
    cache: from init_cache (serving) or None (training/full prefill);
        from init_paged_cache when ``block_tables`` is given.
    pos_offset: absolute position of tokens[:, 0] (scalar, may be traced).
    extra_embeds: (B, Tp, d_model) patch embeddings prepended to the token
        embeddings (VLM stub frontend).
    frames: (B, enc_len, d_model) audio frames (enc-dec only); triggers the
        encoder and fresh cross-KV.
    logits_slice: if set, only the last ``logits_slice`` positions are
        projected to vocab (decode wants 1; saves a (T, vocab) matmul).
    block_tables: (B, pages_per_slot) int32 physical-page table for a
        paged cache; with ``page_size`` it routes attention through the
        Pallas paged-decode / chunked-prefill kernels (interpret mode on
        CPU).
    Returns (logits, new_cache, aux_loss).
    """
    dt = _dtype(cfg)
    x = params["embed"][tokens].astype(dt) if tokens is not None else None
    if extra_embeds is not None:
        ee = extra_embeds.astype(dt)
        x = ee if x is None else jnp.concatenate([ee, x], axis=1)
    B, T, _ = x.shape
    token_mask = None
    if n_valid is not None:
        token_mask = jnp.arange(T)[None] < n_valid[:, None]

    po = jnp.asarray(pos_offset)
    if cfg.pos_embedding == "learned":
        pos = (po[:, None] + jnp.arange(T)[None]) if po.ndim else (po + jnp.arange(T))
        pe = params["pos_embed"][pos]
        x = x + (pe if po.ndim else pe[None]).astype(dt)
    elif cfg.pos_embedding == "sinusoidal":
        pos = (po[:, None] + jnp.arange(T)[None]) if po.ndim else (po + jnp.arange(T))
        pe = _sinusoidal_of(pos.reshape(-1), cfg.d_model).reshape(pos.shape + (cfg.d_model,))
        x = x + (pe if po.ndim else pe[None]).astype(dt)

    enc_out = None
    if cfg.cross_attention and frames is not None:
        enc_out = _encoder_fwd(params, cfg, frames, unroll=unroll)

    # per-layer cross caches are indexed by absolute layer, handled outside
    # the group scan for clarity (cross-KV identical per group position).
    cross_cache = cache.get("cross") if (cache and cfg.cross_attention) else None

    aux_total = jnp.float32(0.0)
    pattern = cfg.layer_pattern
    block_caches = cache["blocks"] if cache is not None else (None,) * len(pattern)

    new_cross_k, new_cross_v = [], []

    def group_step(carry, xs):
        h, aux = carry
        new_caches = []
        cross_upd = []
        for i, kind in enumerate(pattern):
            bp = xs[f"p{i}"]
            bc = xs.get(f"c{i}")
            cc = None
            if cross_cache is not None:
                cc = {"xk": xs["xk"][i], "xv": xs["xv"][i]}
            elif cfg.cross_attention and enc_out is not None:
                cc = "fresh"
            h, nc, nx, a = _block_fwd(
                kind, bp, h, cfg, cache=bc, pos_offset=pos_offset,
                window_override=window_override,
                cross_cache=None if cc in (None, "fresh") else cc,
                enc_out=enc_out, active=active,
                token_mask=token_mask, valid_len=n_valid, unroll=unroll,
                append_external=append_external,
                block_tables=block_tables, page_size=page_size)
            aux = aux + a
            new_caches.append(nc if nc is not None else 0)
            if cfg.cross_attention:
                cross_upd.append(nx if nx is not None else 0)
        out = {}
        for i in range(len(pattern)):
            out[f"c{i}"] = new_caches[i]
            if cfg.cross_attention and cross_upd[i] != 0:
                out[f"xk{i}"] = cross_upd[i]["xk"]
                out[f"xv{i}"] = cross_upd[i]["xv"]
        return (h, aux), out

    # Build scan xs: params (+caches, +cross caches) stacked over groups.
    xs = {f"p{i}": params["blocks"][i] for i in range(len(pattern))}
    if cache is not None:
        for i in range(len(pattern)):
            xs[f"c{i}"] = block_caches[i]
    if cross_cache is not None:
        # (n_layers, ...) -> (G, pattern_len, ...)
        G, PL = cfg.n_groups, len(pattern)
        xs["xk"] = cross_cache["xk"].reshape((G, PL) + cross_cache["xk"].shape[1:])
        xs["xv"] = cross_cache["xv"].reshape((G, PL) + cross_cache["xv"].shape[1:])

    step_fn = jax.checkpoint(group_step) if remat else group_step
    (x, aux_total), ys = _scan(step_fn, (x, aux_total), xs, unroll=unroll)

    # remainder blocks (n_layers % pattern_len != 0), outside the scan
    new_tail = []
    for j, kind in enumerate(cfg.tail_kinds):
        tc = cache["tail"][j] if cache is not None else None
        x, nc, _, a = _block_fwd(kind, params["tail"][j], x, cfg, cache=tc,
                                 pos_offset=pos_offset,
                                 window_override=window_override,
                                 active=active, token_mask=token_mask,
                                 valid_len=n_valid, unroll=unroll,
                                 append_external=append_external)
        aux_total = aux_total + a
        new_tail.append(nc)

    new_cache = None
    if cache is not None:
        new_blocks = []
        for i in range(len(pattern)):
            new_blocks.append(ys[f"c{i}"])
        new_cache = {"blocks": tuple(new_blocks)}
        if cfg.tail_kinds:
            new_cache["tail"] = tuple(new_tail)
        if cfg.cross_attention:
            if enc_out is not None and f"xk0" in ys:
                G, PL = cfg.n_groups, len(pattern)
                xk = jnp.stack([ys[f"xk{i}"] for i in range(PL)], axis=1)
                xv = jnp.stack([ys[f"xv{i}"] for i in range(PL)], axis=1)
                new_cache["cross"] = {
                    "xk": xk.reshape((cfg.n_layers,) + xk.shape[2:]),
                    "xv": xv.reshape((cfg.n_layers,) + xv.shape[2:]),
                }
            else:
                new_cache["cross"] = cross_cache

    x = norm_fwd(params["final_norm"], x, cfg.norm)
    if last_only:
        idx = (jnp.clip(n_valid - 1, 0) if n_valid is not None
               else jnp.full((B,), T - 1))
        x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    elif logits_slice is not None:
        x = x[:, -logits_slice:]
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
    return logits, new_cache, aux_total


# ==========================================================================
# training loss
# ==========================================================================
def loss_fn(params, cfg: ModelConfig, batch, aux_weight: float = 0.01,
            remat: bool = False, unroll: bool = False):
    """batch: {tokens, labels[, extra_embeds, frames]}; labels use -100 to
    mask (e.g. patch positions)."""
    logits, _, aux = forward(
        params, cfg, batch.get("tokens"),
        extra_embeds=batch.get("extra_embeds"),
        frames=batch.get("frames"), remat=remat, unroll=unroll)
    labels = batch["labels"]
    Tl = labels.shape[1]
    logits = logits[:, -Tl:]
    valid = labels >= 0
    labels_c = jnp.clip(labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels_c[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, nll, 0.0)
    loss = nll.sum() / jnp.clip(valid.sum(), 1)
    return loss + aux_weight * aux / cfg.n_layers, {"nll": loss, "aux": aux}

"""Plain reference: the decoder's forward pass in float32 ``jax.numpy``.

No kernels, no cache, no batching of requests: one sequence at a time,
layer by layer, with each layer's weights regenerated from the seed
(``bench.weights.layer_weights``) and widened to float32, and every
matrix product at ``precision="highest"``.  Attention is computed in
blocks of query rows so that a 16k-token sequence fits beside nothing
else on the chip.

The layer equations (one dense GQA decoder layer, pre-norm):

    h  = x + Wo · attn(rope(Wq·n1(x) + bq), rope(Wk·n1(x) + bk), Wv·n1(x) + bv)
    x' = h + W2 · (silu(Wg·n2(h)) * (Wi·n2(h)))
    n(x) = x / sqrt(mean(x²) + eps) * scale

with causal softmax attention scaled by 1/sqrt(head_dim), query head h
reading KV head h // (heads / kv_heads), and logits = n_f(x) · Wout.

One departure from the published models, shared with the program: RoPE
rotates adjacent pairs of the first ``rope_fraction * head_dim`` dims
(GPT-J pairing), where the Hugging Face checkpoints rotate the two
halves.  The two are the same model under a fixed permutation of the
q/k projection columns, which random weights do not distinguish.

``quant="fp8"`` rounds both operands of every matrix product to
float8_e4m3fn first: the lower-precision control of the check.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import ModelSpec
from bench.weights import layer_weights, top_weights

Q_BLOCK = 512          # query rows per attention block
V_BLOCK = 32768        # vocabulary columns per logits block


def _mm(a, b, quant):
    if quant == "fp8":
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, s: ModelSpec):
    """x (L, heads, hd): rotate adjacent pairs of the first rot dims."""
    rot = int(s.head_dim * s.rope_fraction)
    rot -= rot % 2
    freqs = 1.0 / (s.rope_theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32)
                                    / rot))
    ang = pos.astype(jnp.float32)[:, None] * freqs          # (L, rot/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.reshape(x[..., :rot].shape), x[..., rot:]],
                           -1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(s: ModelSpec, x, w, quant):
    """One decoder layer over a whole (padded) sequence x (L, d)."""
    L = x.shape[0]
    H, KV, hd = s.n_heads, s.n_kv_heads, s.head_dim
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    pos = jnp.arange(L)
    h = _norm(x, w["norm1/scale"], s.norm_eps)
    q, k, v = (_mm(h, w["mixer/wq"], quant), _mm(h, w["mixer/wk"], quant),
               _mm(h, w["mixer/wv"], quant))
    if s.qkv_bias:
        q, k, v = q + w["mixer/bq"], k + w["mixer/bk"], v + w["mixer/bv"]
    q = _rope(q.reshape(L, H, hd), pos, s)
    k = _rope(k.reshape(L, KV, hd), pos, s)
    v = v.reshape(L, KV, hd)
    g = H // KV
    kh = jnp.repeat(k, g, axis=1).transpose(1, 0, 2)       # (H, L, hd)
    vh = jnp.repeat(v, g, axis=1).transpose(1, 0, 2)
    n_blk = L // Q_BLOCK

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        qb = qb.transpose(1, 0, 2)                         # (H, Qb, hd)
        sc = _mm(qb, kh.transpose(0, 2, 1), quant) / np.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return _mm(p, vh, quant).transpose(1, 0, 2)        # (Qb, H, hd)

    att = jax.lax.map(block, jnp.arange(n_blk)).reshape(L, H * hd)
    x = x + _mm(att, w["mixer/wo"], quant)
    h = _norm(x, w["norm2/scale"], s.norm_eps)
    ff = jax.nn.silu(_mm(h, w["mlp/wg"], quant)) * _mm(h, w["mlp/wi"], quant)
    return x + _mm(ff, w["mlp/wo"], quant)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head_stats(s: ModelSpec, xs, head, fscale, quant, served):
    """For final hidden rows xs (n, d): the best logit, the logit of
    ``served`` (n,) and the argmax, over the whole vocabulary."""
    h = _norm(xs, fscale.astype(jnp.float32), s.norm_eps)
    best = jnp.full((xs.shape[0],), -jnp.inf)
    arg = jnp.zeros((xs.shape[0],), jnp.int32)
    got = jnp.zeros((xs.shape[0],))
    for lo in range(0, s.vocab, V_BLOCK):
        hi = min(lo + V_BLOCK, s.vocab)
        blk = jax.lax.slice_in_dim(head, lo, hi, axis=1).astype(jnp.float32)
        lg = _mm(h, blk, quant)
        m = lg.max(-1)
        arg = jnp.where(m > best, lo + lg.argmax(-1).astype(jnp.int32), arg)
        best = jnp.maximum(best, m)
        inside = (served >= lo) & (served < hi)
        idx = jnp.clip(served - lo, 0, hi - lo - 1)
        got = jnp.where(inside, jnp.take_along_axis(lg, idx[:, None], 1)[:, 0],
                        got)
    return best, got, arg


def _bucket(n: int) -> int:
    b = Q_BLOCK
    while b < n:
        b *= 2
    return b


def final_hidden(s: ModelSpec, seed: int, prompts: Sequence[np.ndarray],
                 served: Sequence[np.ndarray],
                 quant: Optional[str] = None) -> List[jax.Array]:
    """Last-layer hidden states (before the final norm) at the positions
    that produced each served token: served token j of a request with
    prompt length P came from the context prompt + served[:j], i.e. from
    position P - 1 + j.  Computed layer by layer over all requests."""
    top = top_weights(s, seed)
    xs = []
    for p, o in zip(prompts, served):
        t = np.concatenate([p, o[:-1]]).astype(np.int32)
        ids = np.zeros(_bucket(len(t)), np.int32)
        ids[:len(t)] = t
        xs.append(top["embed"][jnp.asarray(ids)].astype(jnp.float32))
    del top
    for layer in range(s.n_layers):
        w = layer_weights(s, seed, layer)
        xs = [_layer(s, x, w, quant) for x in xs]
        del w
    return [x[len(p) - 1:len(p) - 1 + len(o)]
            for x, p, o in zip(xs, prompts, served)]


def logit_gaps(s: ModelSpec, seed: int, hidden: Sequence[jax.Array],
               tokens: Sequence[np.ndarray], quant: Optional[str] = None):
    """Per request: (best logit - logit of ``tokens``) at each position
    (>= 0), and the token this precision puts first there."""
    top = top_weights(s, seed)
    head = top["embed"].T if s.tied else top["lm_head"]
    gaps, firsts = [], []
    for h, tok in zip(hidden, tokens):
        n = len(tok)
        m = 128
        while m < n:
            m *= 2
        hp = jnp.zeros((m, s.d_model), jnp.float32).at[:n].set(h)
        tp = np.zeros(m, np.int32)
        tp[:n] = tok
        best, got, arg = _head_stats(s, hp, head, top["final_norm/scale"],
                                     quant, jnp.asarray(tp))
        gaps.append(np.asarray(best - got, np.float64)[:n])
        firsts.append(np.asarray(arg)[:n])
    return gaps, firsts

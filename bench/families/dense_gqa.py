"""Dense GQA decoder: every layer alike (Qwen2, Phi-3 / Phi-4-mini).

The layer equations (one dense GQA decoder layer, pre-norm):

    h  = x + Wo · attn(rope(Wq·n1(x) + bq), rope(Wk·n1(x) + bk), Wv·n1(x) + bv)
    x' = h + W2 · (silu(Wg·n2(h)) * (Wi·n2(h)))
    n(x) = x / sqrt(mean(x²) + eps) * scale

with causal softmax attention scaled by 1/sqrt(head_dim), query head h
reading KV head h // (heads / kv_heads), and logits = n_f(x) · Wout.
The biases are there when the configuration states ``qkv_bias``.

One departure from the published models, shared with the program: RoPE
rotates adjacent pairs of the first ``rope_fraction * head_dim`` dims
(GPT-J pairing), where the Hugging Face checkpoints rotate the two
halves.  The two are the same model under a fixed permutation of the
q/k projection columns, which random weights do not distinguish.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.model import ModelSpec, shared_fields
from bench.reference import _mm, _norm, _rope, causal_attention


@dataclasses.dataclass(frozen=True, kw_only=True)
class Spec(ModelSpec):
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    qkv_bias: bool
    rope_theta: float
    rope_fraction: float


def spec(c: dict) -> Spec:
    heads = c["num_attention_heads"]
    return Spec(
        **shared_fields(c),
        n_heads=heads,
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim", c["hidden_size"] // heads),
        d_ff=c["intermediate_size"],
        qkv_bias=c["qkv_bias"],
        rope_theta=float(c["rope_theta"]),
        rope_fraction=float(c.get("partial_rotary_factor", 1.0)),
    )


def program_config(s: Spec):
    """The program's RMSNorm has a fixed epsilon and no key for it, so a
    file that states another one cannot be run as stated."""
    from repro.models.config import ModelConfig
    if s.norm_eps != 1e-6:
        raise SystemExit(f"bench: {s.name} states rms_norm_eps "
                         f"{s.norm_eps}; the program's RMSNorm uses 1e-6")
    return ModelConfig(
        name=s.name, arch_type="dense", n_layers=s.n_layers,
        d_model=s.d_model, n_heads=s.n_heads,
        n_kv_heads=s.n_kv_heads, d_ff=s.d_ff, vocab_size=s.vocab,
        head_dim=s.head_dim, mlp="swiglu", norm="rmsnorm",
        qkv_bias=s.qkv_bias, tie_embeddings=s.tied,
        rope_theta=s.rope_theta, rope_fraction=s.rope_fraction,
        dtype="bfloat16")


def top_leaves(s: Spec):
    out = [("embed", (s.vocab, s.d_model), ("normal", 0.02)),
           ("final_norm/scale", (s.d_model,), ("ones_jitter", 0.1))]
    if not s.tied:
        out.append(("lm_head", (s.d_model, s.vocab), ("normal", 0.02)))
    return out


def layer_leaves(s: Spec, layer: int):
    """(name, shape, init) of one decoder layer's leaves, the same for
    every layer; init is ("normal", std) or ("ones_jitter", std) for norm
    scales.  Biases and norm scales are drawn, not zeros and ones, so the
    reference checks them too."""
    d, hd = s.d_model, s.head_dim
    qd, kd = s.n_heads * hd, s.n_kv_heads * hd
    out = [
        ("norm1/scale", (d,), ("ones_jitter", 0.1)),
        ("mixer/wq", (d, qd), ("normal", d ** -0.5)),
        ("mixer/wk", (d, kd), ("normal", d ** -0.5)),
        ("mixer/wv", (d, kd), ("normal", d ** -0.5)),
        ("mixer/wo", (qd, d), ("normal", qd ** -0.5)),
        ("norm2/scale", (d,), ("ones_jitter", 0.1)),
        ("mlp/wi", (d, s.d_ff), ("normal", d ** -0.5)),
        ("mlp/wg", (d, s.d_ff), ("normal", d ** -0.5)),
        ("mlp/wo", (s.d_ff, d), ("normal", s.d_ff ** -0.5)),
    ]
    if s.qkv_bias:
        out += [("mixer/bq", (qd,), ("normal", 0.1)),
                ("mixer/bk", (kd,), ("normal", 0.1)),
                ("mixer/bv", (kd,), ("normal", 0.1))]
    return out


def layer(s: Spec, x, w, layer: int, quant):
    """Layer ``layer`` over a whole (padded) sequence x (L, d)."""
    return _layer(s, x, w, quant)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(s: Spec, x, w, quant):
    L = x.shape[0]
    H, KV, hd = s.n_heads, s.n_kv_heads, s.head_dim
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    pos = jnp.arange(L)
    h = _norm(x, w["norm1/scale"], s.norm_eps)
    q, k, v = (_mm(h, w["mixer/wq"], quant), _mm(h, w["mixer/wk"], quant),
               _mm(h, w["mixer/wv"], quant))
    if s.qkv_bias:
        q, k, v = q + w["mixer/bq"], k + w["mixer/bk"], v + w["mixer/bv"]
    q = _rope(q.reshape(L, H, hd), pos, s.rope_theta, s.rope_fraction)
    k = _rope(k.reshape(L, KV, hd), pos, s.rope_theta, s.rope_fraction)
    att = causal_attention(q, k, v.reshape(L, KV, hd), quant)
    x = x + _mm(att, w["mixer/wo"], quant)
    h = _norm(x, w["norm2/scale"], s.norm_eps)
    ff = jax.nn.silu(_mm(h, w["mlp/wg"], quant)) * _mm(h, w["mlp/wi"], quant)
    return x + _mm(ff, w["mlp/wo"], quant)


def matmul_flops_per_token(s: Spec) -> float:
    """Projections and MLP of every layer (embedding lookup is free)."""
    hd = s.head_dim
    per_layer = (s.d_model * (s.n_heads + 2 * s.n_kv_heads) * hd
                 + s.n_heads * hd * s.d_model + 3 * s.d_model * s.d_ff)
    return 2.0 * per_layer * s.n_layers


def head_flops(s: Spec) -> float:
    return 2.0 * s.d_model * s.vocab


def attn_flops(s: Spec, n: int, start: int) -> float:
    """Causal attention of n queries at start.. over keys 0..pos, all
    layers: QK^T and PV, 2 FLOPs each per (query, key, head dim)."""
    keys = n * start + n * (n + 1) // 2
    return 4.0 * s.n_heads * s.head_dim * keys * s.n_layers


def attn_bytes(s: Spec, n: int, start: int) -> float:
    """K and V of the n + start visible positions read once, q read and
    the output written for the n queries, all layers."""
    kv = 2 * s.n_kv_heads * s.head_dim * (start + n)
    qo = 2 * s.n_heads * s.head_dim * n
    return 2.0 * (kv + qo) * s.n_layers

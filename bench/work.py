"""The work a step needs, from the configuration and the token spans it
serves, not from how the program computes it: a roofline or a share of
peak then reads the same whatever implements the step.

A span is ``(n, start, sampled)``: ``n`` tokens at positions
``start .. start + n - 1`` of one sequence, ``sampled`` when the step
turns its last position into a token.  FLOPs count a multiply-add as 2.
Bytes are bf16 (2 bytes a value).
"""
from __future__ import annotations

import json
import pathlib

from bench.model import ModelSpec

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}")
    return table[device_kind]


def dense_flops_per_token(s: ModelSpec) -> float:
    """Projections and MLP of every layer (embedding lookup is free)."""
    hd = s.head_dim
    per_layer = (s.d_model * (s.n_heads + 2 * s.n_kv_heads) * hd
                 + s.n_heads * hd * s.d_model + 3 * s.d_model * s.d_ff)
    return 2.0 * per_layer * s.n_layers


def head_flops(s: ModelSpec) -> float:
    return 2.0 * s.d_model * s.vocab


def attn_flops(s: ModelSpec, n: int, start: int) -> float:
    """Causal attention of n queries at start.. over keys 0..pos, all
    layers: QK^T and PV, 2 FLOPs each per (query, key, head dim)."""
    keys = n * start + n * (n + 1) // 2
    return 4.0 * s.n_heads * s.head_dim * keys * s.n_layers


def attn_bytes(s: ModelSpec, n: int, start: int) -> float:
    """K and V of the n + start visible positions read once, q read and
    the output written for the n queries, all layers."""
    kv = 2 * s.n_kv_heads * s.head_dim * (start + n)
    qo = 2 * s.n_heads * s.head_dim * n
    return 2.0 * (kv + qo) * s.n_layers


def step_flops(s: ModelSpec, spans) -> float:
    """Useful FLOPs of one step: every span's tokens through the layers,
    their attention, and the head for each sampled span."""
    return sum(dense_flops_per_token(s) * n + attn_flops(s, n, start)
               + (head_flops(s) if sampled else 0.0)
               for n, start, sampled in spans)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip needs: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])

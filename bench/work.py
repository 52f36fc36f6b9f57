"""The work a step needs, from the configuration and the token spans it
serves, not from how the program computes it: a roofline or a share of
peak then reads the same whatever implements the step.

A span is ``(n, start, sampled)``: ``n`` tokens at positions
``start .. start + n - 1`` of one sequence, ``sampled`` when the step
turns its last position into a token.  FLOPs count a multiply-add as 2.
Bytes are bf16 (2 bytes a value).  The counts of the layers and the head
are the configuration's family's (``bench/families/<family>.py``), summed
over its own layers: an expert layer counts its active experts, a
windowed layer the keys in its window.
"""
from __future__ import annotations

import json
import pathlib

from bench import families
from bench.model import ModelSpec

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}")
    return table[device_kind]


def matmul_flops_per_token(s: ModelSpec) -> float:
    """Every layer's matrix products a token needs (the embedding lookup
    is free, the head is counted apart)."""
    return families.of(s).matmul_flops_per_token(s)


def head_flops(s: ModelSpec) -> float:
    return families.of(s).head_flops(s)


def attn_flops(s: ModelSpec, n: int, start: int) -> float:
    """Attention of n queries at positions start.., all layers."""
    return families.of(s).attn_flops(s, n, start)


def attn_bytes(s: ModelSpec, n: int, start: int) -> float:
    """Bytes the attention of n queries at start.. reads and writes, all
    layers."""
    return families.of(s).attn_bytes(s, n, start)


def step_flops(s: ModelSpec, spans) -> float:
    """Useful FLOPs of one step: every span's tokens through the layers,
    their attention, and the head for each sampled span."""
    fam = families.of(s)
    return sum(fam.matmul_flops_per_token(s) * n + fam.attn_flops(s, n, start)
               + (fam.head_flops(s) if sampled else 0.0)
               for n, start, sampled in spans)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip needs: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])

"""A configuration file (``bench/configs/<name>.json``) as the benchmark
reads it: the published model sizes, the cut, and the deployment.

The file keeps the source's own key names (a Hugging Face ``config.json``)
so that it can be compared with the source line by line.  This module
turns it into the sizes the weight generator, the plain reference and
the work functions use, and into the program's ``ModelConfig``.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

CONFIG_DIR = pathlib.Path(__file__).resolve().parent / "configs"


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    n_layers: int
    qkv_bias: bool
    tied: bool
    rope_theta: float
    rope_fraction: float
    norm_eps: float
    # deployment
    n_slots: int
    max_context: int
    kv_pool_tokens: int
    instances: int = 1
    chips: int = 1
    page_size: int = 8

    @property
    def n_pages(self) -> int:
        return self.kv_pool_tokens // self.page_size


def spec_from_dict(c: dict) -> ModelSpec:
    dep = c["deployment"]
    heads = c["num_attention_heads"]
    return ModelSpec(
        name=c["name"],
        d_model=c["hidden_size"],
        n_heads=heads,
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim", c["hidden_size"] // heads),
        d_ff=c["intermediate_size"],
        vocab=c["vocab_size"],
        n_layers=c["num_hidden_layers"],
        qkv_bias=c["qkv_bias"],
        tied=c["tie_word_embeddings"],
        rope_theta=float(c["rope_theta"]),
        rope_fraction=float(c.get("partial_rotary_factor", 1.0)),
        norm_eps=float(c["rms_norm_eps"]),
        n_slots=dep["n_slots"],
        max_context=dep["max_context"],
        kv_pool_tokens=dep["kv_pool_tokens"],
        instances=dep["instances"],
        chips=dep["chips"],
    )


def load_spec(name: str) -> ModelSpec:
    path = CONFIG_DIR / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"bench: no configuration file {path}")
    return spec_from_dict(json.loads(path.read_text()))


def program_config(spec: ModelSpec):
    """The program's ``ModelConfig`` for this configuration.  The
    program's RMSNorm has a fixed epsilon and no key for it, so a file
    that states another one cannot be run as stated."""
    from repro.models.config import ModelConfig
    if spec.norm_eps != 1e-6:
        raise SystemExit(f"bench: {spec.name} states rms_norm_eps "
                         f"{spec.norm_eps}; the program's RMSNorm uses 1e-6")
    return ModelConfig(
        name=spec.name, arch_type="dense", n_layers=spec.n_layers,
        d_model=spec.d_model, n_heads=spec.n_heads,
        n_kv_heads=spec.n_kv_heads, d_ff=spec.d_ff, vocab_size=spec.vocab,
        head_dim=spec.head_dim, mlp="swiglu", norm="rmsnorm",
        qkv_bias=spec.qkv_bias, tie_embeddings=spec.tied,
        rope_theta=spec.rope_theta, rope_fraction=spec.rope_fraction,
        dtype="bfloat16")

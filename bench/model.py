"""A configuration file (``bench/configs/<name>.json``) as the benchmark
reads it: the published model sizes, the cut, and the deployment.

The file keeps the source's own key names (a Hugging Face ``config.json``)
so that it can be compared with the source line by line, and names its
model family (``"family"``, a module of ``bench/families/``).  The family
turns the file into the sizes the weight generator, the plain reference
and the work functions use, and into the program's ``ModelConfig``; this
module holds what every family shares.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

from bench import families

CONFIG_DIR = pathlib.Path(__file__).resolve().parent / "configs"


@dataclasses.dataclass(frozen=True, kw_only=True)
class ModelSpec:
    """The fields every family's spec holds; a family's spec is a frozen
    subclass that adds its own sizes, so jit can take it as static."""
    family: str
    name: str
    vocab: int
    d_model: int
    n_layers: int
    norm_eps: float
    tied: bool
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


def shared_fields(c: dict) -> dict:
    """``ModelSpec``'s fields from a configuration file that uses the
    Hugging Face key names, for a family's ``spec``."""
    dep = c["deployment"]
    return dict(
        family=c["family"],
        name=c["name"],
        vocab=c["vocab_size"],
        d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        norm_eps=float(c["rms_norm_eps"]),
        tied=c["tie_word_embeddings"],
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
    c = json.loads(path.read_text())
    return families.load(c.get("family"), path).spec(c)


def program_config(spec: ModelSpec):
    """The program's ``ModelConfig`` for this configuration."""
    return families.of(spec).program_config(spec)

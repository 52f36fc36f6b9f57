"""Sharding helpers: build PartitionSpecs that only use mesh axes that
actually divide the tensor dimension (GQA kv_heads=2 cannot shard over a
16-way model axis; the axis is dropped and the dim replicated — with a
one-time warning, and ``achieved_parallelism`` records the degree each
model dimension really got so the cost model prices the replicated case
instead of assuming full speedup)."""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Sequence, Set, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisNames = Union[None, str, Tuple[str, ...]]

_warned: Set[tuple] = set()


def _warn_once(key: tuple, msg: str) -> None:
    if key in _warned:
        return
    _warned.add(key)
    warnings.warn(msg, stacklevel=3)


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def best_divisible_axes(mesh: Mesh, axes: AxisNames, dim: int) -> AxisNames:
    """Return the longest prefix of ``axes`` whose product divides ``dim``."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    picked = []
    prod = 1
    for a in axes:
        nxt = prod * _axis_size(mesh, a)
        if dim % nxt == 0:
            picked.append(a)
            prod = nxt
        else:
            _warn_once(
                (a, _axis_size(mesh, a), dim),
                f"dimension {dim} is not divisible by mesh axis "
                f"{a!r} (size {_axis_size(mesh, a)}); replicating "
                f"instead of sharding — the achieved parallel degree "
                f"is {prod}, not {nxt} (common with GQA kv_heads; the "
                f"cost model prices this via achieved_parallelism)")
            break
    if not picked:
        return None
    return picked[0] if len(picked) == 1 else tuple(picked)


@dataclasses.dataclass(frozen=True)
class AchievedParallelism:
    """Per-model-dimension parallel degree actually reached at a
    requested TP width (a dim that the width does not divide is
    replicated, degree 1 — it gets *no* speedup)."""
    requested: int
    heads: int         # attention q/o projections
    kv_heads: int      # k/v projections + the KV cache itself
    ffn: int           # dense MLP hidden dim
    experts: int       # MoE expert dim (1 on dense archs)


def achieved_parallelism(cfg, n: int) -> "AchievedParallelism":
    """Degrees each shardable dimension of ``cfg`` reaches at TP width
    ``n`` under the divisibility rule above (no mesh needed).  Emits the
    same one-time replication warning as ``best_divisible_axes``."""
    def ach(dim: int, what: str) -> int:
        if n <= 1 or dim <= 0:
            return 1
        if dim % n == 0:
            return n
        _warn_once(
            ("tp", what, n, dim),
            f"{cfg.name}: {what}={dim} is not divisible by "
            f"devices_per_instance={n}; the {what} dimension is "
            f"replicated (achieved degree 1) and gets no TP speedup")
        return 1

    moe = bool(getattr(cfg, "moe_experts", 0))
    return AchievedParallelism(
        requested=max(1, n),
        heads=ach(cfg.n_heads, "n_heads"),
        kv_heads=ach(cfg.n_kv_heads, "n_kv_heads"),
        ffn=ach(getattr(cfg, "d_ff", 0) or 0, "d_ff") if not moe else 1,
        experts=ach(cfg.moe_experts, "moe_experts") if moe else 1,
    )


def spec_for(mesh: Mesh, dims: Sequence[Tuple[int, AxisNames]]) -> P:
    """Build a PartitionSpec for a tensor given (dim_size, desired_axes)
    per dimension, dropping non-divisible axes."""
    entries = []
    used: set = set()
    for dim, axes in dims:
        ax = best_divisible_axes(mesh, axes, dim)
        # an axis may appear at most once in a spec
        if ax is not None:
            flat = (ax,) if isinstance(ax, str) else ax
            if any(a in used for a in flat):
                ax = None
            else:
                used.update(flat)
        entries.append(ax)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def named_sharding(mesh: Mesh, dims: Sequence[Tuple[int, AxisNames]]) -> NamedSharding:
    return NamedSharding(mesh, spec_for(mesh, dims))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Tensor/expert-parallel specs for a whole engine instance
# ---------------------------------------------------------------------------
def _path_names(path) -> list:
    out = []
    for p in path:
        k = getattr(p, "key", None)
        if k is not None:
            out.append(str(k))
    return out


def _axis_at(ndim: int, pos_from_end: int, axis: str) -> P:
    entries: list = [None] * ndim
    entries[ndim + pos_from_end] = axis
    return P(*entries)


def tp_param_specs(cfg, params, axis: str = "model"):
    """Megatron-style PartitionSpec tree for ``init_params`` output:
    attention q/o sharded over heads, k/v over kv_heads, dense MLP over
    the ffn dim, MoE weights over the expert dim; router, norms, embed
    and lm_head replicated.  Positions are taken from the *end* of each
    leaf's shape so stacked ``(G, ...)`` blocks and unstacked tail
    blocks get identical treatment."""
    moe = bool(getattr(cfg, "moe_experts", 0))

    def spec(path, x):
        names = _path_names(path)
        leaf = names[-1] if names else ""
        nd = len(x.shape)
        if "mixer" in names or "cross" in names:
            if leaf in ("wq", "wk", "wv", "bq", "bk", "bv"):
                return _axis_at(nd, -1, axis)
            if leaf == "wo":
                return _axis_at(nd, -2, axis)
            return P()                      # q_norm / k_norm / inner norms
        if "mlp" in names:
            if moe:
                if leaf in ("wi", "wg", "wo"):
                    return _axis_at(nd, -3, axis)   # expert dim
                return P()                  # router replicated
            if leaf in ("wi", "wg"):
                return _axis_at(nd, -1, axis)
            if leaf == "wo":
                return _axis_at(nd, -2, axis)
        return P()                          # embed, norms, lm_head, ...

    return jax.tree_util.tree_map_with_path(spec, params)


def tp_cache_specs(cache, axis: str = "model"):
    """PartitionSpec tree for a KV cache (dense or paged): the KV-head
    dim is sharded — position -2 of a dense ``(..., KV, hd)`` cache,
    -3 of a head-major ``(..., KV, page, hd)`` page pool; position
    planes and anything else are replicated."""
    def spec(path, x):
        names = _path_names(path)
        leaf = names[-1] if names else ""
        nd = len(x.shape)
        if leaf in ("k", "v"):
            return _axis_at(nd, -2, axis)
        if leaf in ("k_pages", "v_pages"):
            return _axis_at(nd, -3, axis)
        return P()
    return jax.tree_util.tree_map_with_path(spec, cache)

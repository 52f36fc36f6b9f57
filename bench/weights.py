"""Random weights from ``--seed``, made on the device in one jitted call.

The leaves, their shapes and inits come from the configuration's family
(``top_leaves``, ``layer_leaves``).  Every top leaf has its own key,
``fold_in(seed key, i)``; leaf ``j`` of layer ``l``'s own list has
``fold_in(fold_in(seed key, n_top + j), l)``.  So the plain reference
regenerates one layer at a time (``layer_weights``) and gets the very
values the served program holds, without taking anything the program
made.

The tree is laid out as the program's ``init_params`` lays it out:
``blocks`` a tuple over the positions of the layer pattern (period P),
layer ``l`` at position ``l % P`` of a leading group axis, and the
``n_layers % P`` layers past the last whole period unstacked in
``tail``.  ``check_layout`` compares the two before a run, so a change
of the program's layout stops the run instead of feeding it a wrong
tree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import families
from bench.model import ModelSpec


def seed_key(seed: int):
    """A key from any non-negative whole number (above 32 bits too)."""
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _draw(key, shape, init):
    kind, scale = init
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "ones_jitter":
        z = 1.0 + scale * z
    else:
        z = scale * z
    return z.astype(jnp.bfloat16)


def _leaf_key(key, i):
    return jax.random.fold_in(key, i)


def _nest(flat):
    """``{"mixer/wq": v, ...}`` as the program's nested dicts."""
    tree = {}
    for path, value in flat.items():
        *head, last = path.split("/")
        node = tree
        for h in head:
            node = node.setdefault(h, {})
        node[last] = value
    return tree


def _layer_draw(leaves, n_top, key, layer):
    return {name: _draw(jax.random.fold_in(_leaf_key(key, n_top + j), layer),
                        shape, init)
            for j, (name, shape, init) in enumerate(leaves)}


@functools.partial(jax.jit, static_argnums=0)
def _make(s: ModelSpec, key):
    fam = families.of(s)
    period = len(fam.program_config(s).layer_pattern)
    whole = s.n_layers - s.n_layers % period
    top = fam.top_leaves(s)
    tree = _nest({name: _draw(_leaf_key(key, i), shape, init)
                  for i, (name, shape, init) in enumerate(top)})
    blocks = []
    for p in range(period):
        leaves = fam.layer_leaves(s, p)
        if any(fam.layer_leaves(s, l) != leaves
               for l in range(p, whole, period)):
            raise ValueError(f"{s.name}: the layers at pattern position "
                             f"{p} do not share one leaf list")
        draw = lambda l, leaves=leaves: _layer_draw(leaves, len(top), key, l)
        blocks.append(_nest(jax.vmap(draw)(jnp.arange(p, whole, period))))
    tree["blocks"] = tuple(blocks)
    if whole < s.n_layers:
        tree["tail"] = tuple(
            _nest(_layer_draw(fam.layer_leaves(s, l), len(top), key, l))
            for l in range(whole, s.n_layers))
    return tree


def make_params(s: ModelSpec, seed: int):
    """The whole tree, bf16, on the default device."""
    return _make(s, seed_key(seed))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(leaves, n_top, key, layer):
    return _layer_draw(leaves, n_top, key, layer)


def layer_weights(s: ModelSpec, seed: int, layer: int):
    """Layer ``layer``'s leaves by name (``"mixer/wq"``, ...), bf16."""
    fam = families.of(s)
    return _layer(tuple(fam.layer_leaves(s, layer)), len(fam.top_leaves(s)),
                  seed_key(seed), layer)


@functools.partial(jax.jit, static_argnums=0)
def _top(s: ModelSpec, key):
    return {name: _draw(_leaf_key(key, i), shape, init)
            for i, (name, shape, init)
            in enumerate(families.of(s).top_leaves(s))}


def top_weights(s: ModelSpec, seed: int):
    """Embedding, final norm and (untied) head by name, bf16."""
    return _top(s, seed_key(seed))


def check_layout(params, cfg) -> None:
    """Raise unless ``params`` has the program's tree, shapes and dtypes."""
    from repro.models.model import init_params
    want = init_params(cfg, abstract=True)
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise SystemExit("bench: the program's parameter layout differs "
                         "from bench/weights.py; the weights cannot be "
                         "handed to it")

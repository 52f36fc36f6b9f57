"""Random weights from ``--seed``, made on the device in one jitted call.

Every leaf has its own key, ``fold_in(seed key, leaf number)``, and every
layer of a stacked leaf ``fold_in(leaf key, layer)``.  So the plain
reference regenerates one layer at a time (``layer_weights``) and gets
the very values the served program holds, without taking anything the
program made.

The tree is laid out as the program's ``init_params`` lays it out (a
scan over layers: block leaves stacked on a leading layer axis).
``check_layout`` compares the two before a run, so a change of the
program's layout stops the run instead of feeding it a wrong tree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.model import ModelSpec

def _layer_leaves(s: ModelSpec):
    """(name, shape, init) of one decoder layer's leaves; init is
    ("normal", std) or ("ones_jitter", std) for norm scales.  Biases and
    norm scales are drawn, not zeros and ones, so the reference checks
    them too."""
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


def _top_leaves(s: ModelSpec):
    out = [("embed", (s.vocab, s.d_model), ("normal", 0.02)),
           ("final_norm/scale", (s.d_model,), ("ones_jitter", 0.1))]
    if not s.tied:
        out.append(("lm_head", (s.d_model, s.vocab), ("normal", 0.02)))
    return out


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


def _put(tree, path, value):
    *head, last = path.split("/")
    for h in head:
        tree = tree.setdefault(h, {})
    tree[last] = value


@functools.partial(jax.jit, static_argnums=0)
def _make(s: ModelSpec, key):
    tree = {}
    top = _top_leaves(s)
    for i, (name, shape, init) in enumerate(top):
        _put(tree, name, _draw(_leaf_key(key, i), shape, init))
    block = {}
    layers = jnp.arange(s.n_layers)
    for j, (name, shape, init) in enumerate(_layer_leaves(s)):
        lk = _leaf_key(key, len(top) + j)
        draw = lambda l, lk=lk, shape=shape, init=init: _draw(
            jax.random.fold_in(lk, l), shape, init)
        _put(block, name, jax.vmap(draw)(layers))
    tree["blocks"] = (block,)
    return tree


def make_params(s: ModelSpec, seed: int):
    """The whole tree, bf16, on the default device."""
    return _make(s, seed_key(seed))


@functools.partial(jax.jit, static_argnums=0)
def _layer(s: ModelSpec, key, layer):
    n_top = len(_top_leaves(s))
    return {name: _draw(jax.random.fold_in(_leaf_key(key, n_top + j), layer),
                        shape, init)
            for j, (name, shape, init) in enumerate(_layer_leaves(s))}


def layer_weights(s: ModelSpec, seed: int, layer: int):
    """Layer ``layer``'s leaves by name (``"mixer/wq"``, ...), bf16."""
    return _layer(s, seed_key(seed), layer)


@functools.partial(jax.jit, static_argnums=0)
def _top(s: ModelSpec, key):
    return {name: _draw(_leaf_key(key, i), shape, init)
            for i, (name, shape, init) in enumerate(_top_leaves(s))}


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

"""The plain reference and the seeded weights against the program, on
the CPU at the smoke size."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_smoke import SEED, SPEC
from bench import reference
from bench.model import program_config
from bench.weights import (check_layout, layer_weights, make_params,
                           top_weights)

SPECS = {"qwen-like": SPEC,
         "phi-like": dataclasses.replace(SPEC, qkv_bias=False, tied=True,
                                         rope_fraction=0.75,
                                         rope_theta=1e4)}


@pytest.mark.parametrize("name", sorted(SPECS))
def test_layer_weights_are_the_served_ones(name):
    spec = SPECS[name]
    params = make_params(spec, SEED)
    check_layout(params, program_config(spec))
    top = top_weights(spec, SEED)
    assert np.array_equal(top["embed"], params["embed"])
    block = params["blocks"][0]
    for layer in range(spec.n_layers):
        for key, val in layer_weights(spec, SEED, layer).items():
            a, b = key.split("/")
            assert np.array_equal(val, block[a][b][layer]), (key, layer)
    other = make_params(spec, SEED + 1)
    assert not np.array_equal(other["embed"], params["embed"])


@pytest.mark.parametrize("name", sorted(SPECS))
def test_reference_agrees_with_program_forward(name):
    """The program's full forward pass in float32 on the same weights
    puts first the tokens the reference puts first, where the reference's
    two best are apart, and every such token is the reference's best to
    float32 rounding."""
    from repro.models.model import forward
    spec = SPECS[name]
    cfg = program_config(spec).with_(dtype="float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          make_params(spec, SEED))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, spec.vocab, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(forward(params, cfg, jnp.asarray(toks)[None])[0][0])
    prog_first = logits.argmax(-1)
    hid = reference.final_hidden(spec, SEED, [toks[:1]], [toks[1:]])
    gaps, firsts = reference.logit_gaps(spec, SEED, hid,
                                        [prog_first[:-1]])
    assert gaps[0].max() < 1e-4
    top2 = np.sort(logits, -1)[:, -2:]
    apart = (top2[:, 1] - top2[:, 0])[:-1] > 1e-3
    assert apart.sum() > 30
    assert np.array_equal(firsts[0][apart], prog_first[:-1][apart])


def test_fp8_control_is_further_from_reference():
    spec = SPEC
    rng = np.random.default_rng(1)
    p, o = rng.integers(0, spec.vocab, 24), rng.integers(0, spec.vocab, 16)
    hid = reference.final_hidden(spec, SEED, [p], [o])
    hq = reference.final_hidden(spec, SEED, [p], [o], "fp8")
    _, ref_first = reference.logit_gaps(spec, SEED, hid, [o])
    _, q_first = reference.logit_gaps(spec, SEED, hq, [o], "fp8")
    gap_q, _ = reference.logit_gaps(spec, SEED, hid, q_first)
    gap_ref, _ = reference.logit_gaps(spec, SEED, hid, ref_first)
    assert gap_ref[0].max() == 0.0
    assert gap_q[0].max() > 0.0

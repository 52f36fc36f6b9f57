"""The seam between the benchmark's shared machinery and a model family.

Parity: the dense GQA family gives the seeded weights, the reference's
hidden states and the work counts that the benchmark gave before the
dense model moved behind ``bench/families/dense_gqa.py``.  The digests
and counts below were computed once on the CPU with the code before
that move, at the smoke size (weights, hidden states) and at the two
configurations' published widths (work).

Seam: a toy family with a period-2 pattern whose two positions have
different leaves, added as a module and a configuration file only, goes
through the whole path; a file without a family, or naming none that
exists, stops with a message that names the file and the key.
"""
import dataclasses
import hashlib
import json
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_smoke
from bench_smoke import SEED, SPEC
from bench import model, reference, work
from bench.model import ModelSpec, load_spec, program_config, shared_fields
from bench.reference import _mm, _norm, _rope, causal_attention
from bench.weights import (check_layout, layer_weights, make_params,
                           top_weights)

SPECS = {"qwen-like": SPEC,
         "phi-like": dataclasses.replace(SPEC, qkv_bias=False, tied=True,
                                         rope_fraction=0.75, rope_theta=1e4)}

WEIGHTS = {
    "qwen-like": {
        "params": "497d649d2010b3403895e80dcf8fe0e86f6f032b6d3b9a5a80caf8c6aafc49db",
        "top": "30c0778d8afeda56cc0dbc3dc01b0503fe66768a737d8096c7b1415e04447e3f",
        "layers": [
            "4511e39dfd6faf800431db728aaa6ccf0c7c3fd71cf8d44c6d7502ae7472e3e5",
            "fd31b68a13102b2458747617e1abcf721decb67fdfe6ae99583eef5b026cd1ea"]},
    "phi-like": {
        "params": "57d78e83d4aea84116556635c798ca89e0856d39aea231cdddb97e25e07bab65",
        "top": "832c923150dd2a2e92dab4999b710b325d08fc6ef54b377227d680eafa8aa7dc",
        "layers": [
            "00eb023e44678531265cb37dc6abbd8e8fcde53fce7dc34bf19f6512068aed62",
            "0b8824522612bfa962a971bd484cb7c575d9e3ab4d25612bac9a951b9bee98ce"]},
}
HIDDEN = {
    "qwen-like": {
        None: "443d3c63dccf93d5689bc3df784b5e8a12bd81052ff58fad92ad17676af68a67",
        "fp8": "986c8ab915843096beda861db3d2a532775a9c8e93e37bbddfa59d204e9c5773"},
    "phi-like": {
        None: "1f368cf30e5abceb88457070eaf9b0862a5463200023dfa8768852a775c56adb",
        "fp8": "bd012972a3dcf74782c67ed14741255dd82f389f8a14f40f45fe7d981a383a61"},
}
# (prompt, served) lengths; the second crosses one 512-row query block
REQS = [(5, 3), (530, 20)]
SPANS = [(512, 0, False), (512, 7680, True), (1, 0, True), (1, 4095, True),
         (300, 2048, False), (37, 16000, True)]
WORK = {
    "qwen2.5-14b-8l": {
        "step_flops": 6902025748480.0,
        "attn_flops": [21516779520.0, 665761873920.0, 163840.0, 671088640.0,
                       108060672000.0, 97108459520.0],
        "attn_bytes": [100663296.0, 352321536.0, 196608.0, 134381568.0,
                       126091264.0, 531562496.0]},
    "phi4-mini-3.8b": {
        "step_flops": 10929463099392.0,
        "attn_flops": [51640270848.0, 1597828497408.0, 393216.0,
                       1610612736.0, 259345612800.0, 233060302848.0],
        "attn_bytes": [268435456.0, 1275068416.0, 524288.0, 537264128.0,
                       425721856.0, 2116550656.0]},
}


def _digest(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(SPECS))
def test_dense_weights_equal_the_parents(name):
    spec, want = SPECS[name], WEIGHTS[name]
    assert _digest(make_params(spec, SEED)) == want["params"]
    assert _digest(top_weights(spec, SEED)) == want["top"]
    assert [_digest(layer_weights(spec, SEED, l))
            for l in range(spec.n_layers)] == want["layers"]


@pytest.mark.parametrize("name", sorted(SPECS))
def test_dense_reference_hidden_equals_the_parents(name):
    spec = SPECS[name]
    rng = np.random.default_rng(20261019)
    prompts = [rng.integers(0, spec.vocab, p).astype(np.int32)
               for p, _ in REQS]
    served = [rng.integers(0, spec.vocab, o).astype(np.int32)
              for _, o in REQS]
    for quant, want in HIDDEN[name].items():
        got = reference.final_hidden(spec, SEED, prompts, served, quant)
        assert _digest(got) == want, quant


@pytest.mark.parametrize("config", sorted(WORK))
def test_dense_work_equals_the_parents(config):
    spec, want = load_spec(config), WORK[config]
    assert spec.family == "dense_gqa"
    assert work.step_flops(spec, SPANS) == want["step_flops"]
    assert [work.attn_flops(spec, n, p) for n, p, _ in SPANS] \
        == want["attn_flops"]
    assert [work.attn_bytes(spec, n, p) for n, p, _ in SPANS] \
        == want["attn_bytes"]


# ---- a toy family: attention, then a gated recurrent-shaped mixer ----

@dataclasses.dataclass(frozen=True, kw_only=True)
class ToySpec(ModelSpec):
    n_heads: int
    d_ff: int


PATTERN = ("attn", "rglru")


def _toy_module():
    """The module a file ``bench/families/toy.py`` would be.  Its second
    kind has the program's RG-LRU leaves but, in the reference, a plain
    gated product in place of the recurrence: the seam is under test
    here, not those equations."""
    toy = types.ModuleType("bench.families.toy")

    def spec(c):
        return ToySpec(**shared_fields(c), n_heads=c["num_attention_heads"],
                       d_ff=c["intermediate_size"])

    def program_config(s):
        from repro.models.config import ModelConfig
        return ModelConfig(
            name=s.name, arch_type="hybrid", n_layers=s.n_layers,
            d_model=s.d_model, n_heads=s.n_heads, n_kv_heads=s.n_heads,
            d_ff=s.d_ff, vocab_size=s.vocab, layer_pattern=PATTERN,
            tie_embeddings=s.tied, dtype="bfloat16")

    def top_leaves(s):
        return [("embed", (s.vocab, s.d_model), ("normal", 0.02)),
                ("final_norm/scale", (s.d_model,), ("ones_jitter", 0.1))]

    def layer_leaves(s, layer):
        d = s.d_model
        n = ("normal", d ** -0.5)
        if PATTERN[layer % 2] == "attn":
            mixer = [(f"mixer/{k}", (d, d), n)
                     for k in ("wq", "wk", "wv", "wo")]
        else:
            mixer = [("mixer/w_gate", (d, d), n), ("mixer/w_in", (d, d), n),
                     ("mixer/conv_w", (4, d), n), ("mixer/conv_b", (d,), n),
                     ("mixer/w_a", (d, d), n), ("mixer/b_a", (d,), n),
                     ("mixer/w_x", (d, d), n), ("mixer/b_x", (d,), n),
                     ("mixer/lam", (d,), n), ("mixer/w_out", (d, d), n)]
        return ([("norm1/scale", (d,), ("ones_jitter", 0.1))] + mixer
                + [("norm2/scale", (d,), ("ones_jitter", 0.1)),
                   ("mlp/wi", (d, s.d_ff), n), ("mlp/wg", (d, s.d_ff), n),
                   ("mlp/wo", (s.d_ff, d), ("normal", s.d_ff ** -0.5))])

    def layer(s, x, w, layer, quant):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        L, H = x.shape[0], s.n_heads
        h = _norm(x, w["norm1/scale"], s.norm_eps)
        if PATTERN[layer % 2] == "attn":
            pos = jnp.arange(L)
            q = _rope(_mm(h, w["mixer/wq"], quant).reshape(L, H, -1), pos,
                      1e4, 1.0)
            k = _rope(_mm(h, w["mixer/wk"], quant).reshape(L, H, -1), pos,
                      1e4, 1.0)
            v = _mm(h, w["mixer/wv"], quant).reshape(L, H, -1)
            x = x + _mm(causal_attention(q, k, v, quant), w["mixer/wo"],
                        quant)
        else:
            g = jax.nn.gelu(_mm(h, w["mixer/w_gate"], quant))
            x = x + _mm(g * _mm(h, w["mixer/w_in"], quant), w["mixer/w_out"],
                        quant)
        h = _norm(x, w["norm2/scale"], s.norm_eps)
        ff = jax.nn.silu(_mm(h, w["mlp/wg"], quant)) * _mm(h, w["mlp/wi"],
                                                            quant)
        return x + _mm(ff, w["mlp/wo"], quant)

    def n_attn(s):
        return sum(PATTERN[l % 2] == "attn" for l in range(s.n_layers))

    def matmul_flops_per_token(s):
        d = s.d_model
        mixer = {"attn": 4 * d * d, "rglru": 3 * d * d}
        return 2.0 * sum(mixer[PATTERN[l % 2]] + 3 * d * s.d_ff
                         for l in range(s.n_layers))

    def attn_flops(s, n, start):
        return 4.0 * s.d_model * (n * start + n * (n + 1) // 2) * n_attn(s)

    def attn_bytes(s, n, start):
        return 2.0 * 2 * s.d_model * (start + 2 * n) * n_attn(s)

    def head_flops(s):
        return 2.0 * s.d_model * s.vocab

    for f in (spec, program_config, top_leaves, layer_leaves, layer,
              matmul_flops_per_token, attn_flops, attn_bytes, head_flops):
        setattr(toy, f.__name__, f)
    return toy


TOY = {"family": "toy", "name": "toy", "hidden_size": 64,
       "num_attention_heads": 4, "intermediate_size": 96, "vocab_size": 300,
       "num_hidden_layers": 3, "rms_norm_eps": 1e-6,
       "tie_word_embeddings": True,
       "deployment": {"n_slots": 2, "max_context": 64, "kv_pool_tokens": 64,
                      "instances": 1, "chips": 1}}


def test_a_family_is_added_by_files_alone(tmp_path, monkeypatch):
    """Three layers on a period-2 pattern: positions 0 and 1 stacked over
    one group each, layer 2 in the program's tail; the reference and the
    work counts run through the toy's own functions."""
    assert not (bench_smoke.ROOT / "bench" / "families" / "toy.py").exists()
    monkeypatch.setitem(sys.modules, "bench.families.toy", _toy_module())
    monkeypatch.setattr(model, "CONFIG_DIR", tmp_path)
    (tmp_path / "toy.json").write_text(json.dumps(TOY))

    spec = load_spec("toy")
    assert isinstance(spec, ToySpec) and spec.d_model == 64
    cfg = program_config(spec)
    assert cfg.layer_pattern == PATTERN and cfg.tail_kinds == ("attn",)
    params = make_params(spec, SEED)
    check_layout(params, cfg)
    attn, rec = params["blocks"]
    assert "wq" in attn["mixer"] and "w_gate" in rec["mixer"]
    (tail,) = params["tail"]
    for l, block, g in ((0, attn, 0), (1, rec, 0), (2, tail, None)):
        for key, val in layer_weights(spec, SEED, l).items():
            a, b = key.split("/")
            got = block[a][b] if g is None else block[a][b][g]
            assert np.array_equal(val, got), (key, l)
    assert np.array_equal(top_weights(spec, SEED)["embed"], params["embed"])

    rng = np.random.default_rng(3)
    p, o = rng.integers(0, spec.vocab, 9), rng.integers(0, spec.vocab, 4)
    (hid,) = reference.final_hidden(spec, SEED, [p], [o])
    assert hid.shape == (4, 64) and bool(jnp.all(jnp.isfinite(hid)))
    gaps, _ = reference.logit_gaps(spec, SEED, [hid], [o])
    assert gaps[0].shape == (4,) and (gaps[0] >= 0).all()

    # two attention layers of three: 4 d² each, the other 3 d²; 3 d d_ff each
    per_token = 2.0 * (2 * 4 * 64 * 64 + 3 * 64 * 64 + 3 * 3 * 64 * 96)
    assert work.matmul_flops_per_token(spec) == per_token
    assert work.attn_flops(spec, 3, 5) == 4.0 * 64 * 21 * 2
    assert work.step_flops(spec, [(3, 5, True), (1, 9, False)]) == (
        per_token * 4 + work.attn_flops(spec, 3, 5)
        + work.attn_flops(spec, 1, 9) + 2.0 * 64 * 300)


@pytest.mark.parametrize("family", [None, "no_such_family", "../dense_gqa"])
def test_a_file_must_name_an_existing_family(family, tmp_path, monkeypatch):
    monkeypatch.setattr(model, "CONFIG_DIR", tmp_path)
    c = dict(TOY)
    if family is None:
        del c["family"]
    else:
        c["family"] = family
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(c))
    with pytest.raises(SystemExit, match='"family"') as e:
        load_spec("bad")
    assert str(path) in str(e.value)

"""End-to-end engine + serving-cluster integration: the real JAX execution
path, including cross-instance micro-request KV/state handoff."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.engine import BatchItem, InstanceEngine
from repro.engine.cluster import ServingCluster
from repro.models.model import init_params

FAMS = ["qwen2.5-14b", "mamba2-780m", "recurrentgemma-9b"]


def _gen(eng, slot, prompt, n, pos0=None):
    out = eng.run_batch([BatchItem(slot, prompt, 0, want_logits=True)])
    toks = [int(out[slot].argmax())]
    pos = len(prompt)
    for _ in range(n - 1):
        out = eng.run_batch([BatchItem(slot, np.array([toks[-1]], np.int32),
                                       pos, want_logits=True)])
        toks.append(int(out[slot].argmax()))
        pos += 1
    return toks


@pytest.mark.parametrize("name", FAMS)
def test_cross_instance_handoff_is_exact(name):
    cfg = get_smoke_config(name)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, 24).astype(np.int32)
    eng = InstanceEngine(cfg, params, n_slots=4, max_len=96)
    ref = _gen(eng, eng.alloc("r"), prompt, 6)

    A = InstanceEngine(cfg, params, n_slots=4, max_len=96)
    B = InstanceEngine(cfg, params, n_slots=4, max_len=96)
    sa = A.alloc("r")
    A.run_batch([BatchItem(sa, prompt[:16], 0)])
    pieces = A.export_state(sa, upto=16, chunk=8)
    assert len(pieces) == 2                      # chunked transfer
    sb = B.alloc("r")
    B.import_state(sb, pieces)
    out = B.run_batch([BatchItem(sb, prompt[16:], 16, want_logits=True)])
    toks = [int(out[sb].argmax())]
    pos = len(prompt)
    for _ in range(5):
        out = B.run_batch([BatchItem(sb, np.array([toks[-1]], np.int32),
                                     pos, want_logits=True)])
        toks.append(int(out[sb].argmax()))
        pos += 1
    assert toks == ref


def test_mixed_batch_prefill_plus_decode():
    """One unified iteration carrying a prefill chunk AND decode steps of
    other requests must match isolated execution."""
    cfg = get_smoke_config("qwen2.5-14b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    pa = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    pb = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)

    # isolated
    e1 = InstanceEngine(cfg, params, n_slots=4, max_len=96)
    ra = _gen(e1, e1.alloc("a"), pa, 3)
    e2 = InstanceEngine(cfg, params, n_slots=4, max_len=96)
    rb = _gen(e2, e2.alloc("b"), pb, 3)

    # mixed: b decodes while a prefills in the same iterations
    e = InstanceEngine(cfg, params, n_slots=4, max_len=96)
    sa, sb = e.alloc("a"), e.alloc("b")
    out = e.run_batch([BatchItem(sb, pb, 0, want_logits=True)])
    tb = [int(out[sb].argmax())]
    out = e.run_batch([
        BatchItem(sa, pa[:10], 0),
        BatchItem(sb, np.array([tb[-1]], np.int32), len(pb), want_logits=True),
    ])
    tb.append(int(out[sb].argmax()))
    out = e.run_batch([
        BatchItem(sa, pa[10:], 10, want_logits=True),
        BatchItem(sb, np.array([tb[-1]], np.int32), len(pb) + 1,
                  want_logits=True),
    ])
    ta = [int(out[sa].argmax())]
    tb.append(int(out[sb].argmax()))
    out = e.run_batch([
        BatchItem(sa, np.array([ta[-1]], np.int32), len(pa), want_logits=True),
    ])
    ta.append(int(out[sa].argmax()))
    out = e.run_batch([
        BatchItem(sa, np.array([ta[-1]], np.int32), len(pa) + 1,
                  want_logits=True),
    ])
    ta.append(int(out[sa].argmax()))
    assert ta == ra and tb == rb


@pytest.mark.parametrize("name", FAMS)
def test_serving_cluster_split_equals_unsplit(name):
    cfg = get_smoke_config(name)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (40, 23, 31)]
    ref_c = ServingCluster(cfg, params, n_instances=1, split=False,
                           max_len=128)
    refs = [ref_c.submit(p, 10) for p in prompts]
    ref_c.run_until_done(refs)
    dyn = ServingCluster(cfg, params, n_instances=2, split=True, max_len=128)
    outs = [dyn.submit(p, 10) for p in prompts]
    dyn.run_until_done(outs)
    for a, b in zip(refs, outs):
        assert a.generated == b.generated
    assert dyn.kv_bytes_moved >= 0


def test_vlm_and_audio_frontend_prefill():
    """Stub-frontend requests decode coherently through the engine."""
    rng = np.random.default_rng(0)
    for name in ["internvl2-76b", "whisper-large-v3"]:
        cfg = get_smoke_config(name)
        params = init_params(cfg, jax.random.PRNGKey(0))
        eng = InstanceEngine(cfg, params, n_slots=2, max_len=96)
        slot = eng.alloc("r")
        kw = {}
        n_extra = 0
        if cfg.arch_type == "vlm":
            kw["extra_embeds"] = rng.standard_normal(
                (cfg.num_patches, cfg.d_model)).astype(np.float32) * 0.02
            n_extra = cfg.num_patches
        else:
            kw["frames"] = rng.standard_normal(
                (cfg.encoder_len, cfg.d_model)).astype(np.float32) * 0.02
        prompt = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
        logits = eng.run_frontend(slot, tokens=prompt, pos_offset=0, **kw)
        assert logits.shape == (cfg.vocab_size,)
        assert np.isfinite(logits).all()
        tok = int(logits.argmax())
        pos = n_extra + len(prompt)
        for _ in range(4):
            out = eng.run_batch([BatchItem(slot, np.array([tok], np.int32),
                                           pos, want_logits=True)])
            assert np.isfinite(out[slot]).all()
            tok = int(out[slot].argmax())
            pos += 1


@pytest.mark.parametrize("layers", [0, 49, -8])
def test_engine_model_rejects_depth_outside_published(layers):
    """The full-width entry point cuts depth only within the published
    layer count (checked before any weight is built)."""
    from repro.models.model import engine_model
    with pytest.raises(ValueError, match="cannot cut"):
        engine_model("qwen2.5-14b", layers)


def test_engine_model_without_depth_is_the_smoke_config():
    from repro.models.model import engine_model
    cfg, params = engine_model("qwen2.5-14b", None)
    assert cfg == get_smoke_config("qwen2.5-14b")
    assert params["embed"].shape == (cfg.vocab_size, cfg.d_model)


@pytest.mark.parametrize("argv", [["--backend", "engine"],
                                  ["--http", "--backend", "engine"]])
def test_serve_engine_needs_smoke_or_layers(argv, capsys):
    """The engine backend never defaults to the full published depth
    (Qwen2.5-14B's 48 layers do not fit one chip): without --smoke or
    --layers the launcher refuses before building anything."""
    from repro.launch import serve
    with pytest.raises(SystemExit) as exc:
        serve.main(argv)
    assert exc.value.code == 2
    assert "--layers N" in capsys.readouterr().err


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_use_compile_cache(env_dir, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins when set (JAX reads it at start-up
    and the helper sets nothing); otherwise the cache sits at the fixed
    ``<checkout>/.jax_cache``.  Run in a fresh interpreter: JAX reads
    the variable only at import, and the cache directory is global."""
    import os
    import pathlib
    import subprocess
    import sys
    root = pathlib.Path(__file__).resolve().parents[1]
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(root / "src"))
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\nfrom repro.launch.serve import use_compile_cache\n"
         "d = use_compile_cache()\n"
         "assert d == jax.config.jax_compilation_cache_dir\nprint(d)"],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    want = tmp_path / env_dir if env_dir else root / ".jax_cache"
    assert out.stdout.strip().splitlines()[-1] == str(want)

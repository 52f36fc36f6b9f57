"""Smoke run of the served path on TPU: proves the system starts on the chip.

One process, no children.  Serves Qwen2.5-14B at its published widths,
cut in depth to fit one v5e chip, bf16, weights random from ``SEED``:

1. ``ServeSession`` + ``EngineBackend`` through ``repro.launch.serve``'s
   engine path: 2 instances on the chip, the DynaServe policy, open-loop
   arrivals, 8 requests with 256-1024-token prompts and 16-64 output
   tokens.  Every request must complete and alpha->beta handoffs must
   move KV bytes.
2. Logits: one prompt prefilled through the paged Pallas kernels, then 8
   decode steps, against ``forward(cache=None)`` on the same tokens and
   weights; the compiled decode and prefill step programs must contain
   the Mosaic kernels (``tpu_custom_call``).
3. The in-process HTTP server (``ServingServer``) on the same config
   answers two ``/v1/completions`` with token-id prompts, one over SSE.

``--chips 4`` runs only the paths that exist across chips, each against
a one-chip run on chip 0 with the same weights: a KV handoff from an
instance on chip 0 to instances on chips 1-3, and a TP=4 instance.

Times printed are smoke readings, not benchmarks.  The last line of
stdout is ``{"ok": true, "device": {...}}``; any failure exits non-zero
before it.  Usage: ``python chip_smoke.py [--chips 4]``.
"""
from __future__ import annotations

import argparse
import gc
import http.client
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "qwen2.5-14b"
LAYERS = 8            # depth cut of the 48 layers: 7.5 GB of bf16 weights
SEED = 0              # weights, prompts and the request trace
PROMPT = 512          # logits-check prompt: one 512-token prefill chunk
DECODE = 8            # decode steps compared after the prefill

# Logits tolerance, relative to the largest |reference logit| over the
# compared positions.  Both paths run bf16 weights and activations; bf16
# rounds at 2^-9 relative.  The paged path rounds K/V into the pool and
# the attention output once, from an f32 softmax in the kernel; the
# reference rounds inside XLA's blocked attention.  Those rounding
# differences pass through every residual layer and the vocab
# projection, so a correct kernel agrees to about 1e-2 of the logit
# range.  A wrong page, mask, position or scale gives an O(1) error.
LOGITS_RTOL = 5e-2
# Same program and inputs on another chip of the same kind: only the
# placement of the slot row may differ, so agreement is near-exact.
HANDOFF_RTOL = 1e-3


class CompileClock:
    """Sums JAX's compile-time events (trace, lowering, backend compile
    or persistent-cache read) and counts persistent-cache hits."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def phase(name, clock, dev, t0, c0, h0):
    peak = dev.memory_stats().get("peak_bytes_in_use", 0)
    print(f"[{name}] smoke reading, not a benchmark: wall "
          f"{time.monotonic() - t0:.1f} s, compile {clock.seconds - c0:.1f} s "
          f"({clock.hits - h0} persistent-cache hits), peak device bytes "
          f"{peak}", flush=True)


def rel_err(got, ref):
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def greedy_run(eng, prompt, n_decode, feed=None):
    """Prefill ``prompt`` in one chunk, then ``n_decode`` single-token
    steps; feeds ``feed`` tokens when given, else the greedy ones.
    Returns (logits per position from len(prompt)-1, fed tokens)."""
    from repro.engine.runner import BatchItem
    slot = eng.alloc("smoke")
    out = [eng.run_batch([BatchItem(slot, prompt, 0, True)])[slot]]
    fed = []
    for i in range(n_decode):
        tok = int(feed[i]) if feed is not None else int(np.argmax(out[-1]))
        fed.append(tok)
        out.append(eng.run_batch([BatchItem(
            slot, np.asarray([tok], np.int32), len(prompt) + i, True)])[slot])
    eng.free(slot)
    return np.stack(out), fed


def step_has_kernel(eng, key):
    """Compile the engine's jitted step for ``key`` = (T, n_pp) and
    report whether the Mosaic kernels are in the program."""
    import jax
    T, n_pp = key
    B = eng.n_slots
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=x.sharding)
    args = (jax.tree.map(spec, eng.params), jax.tree.map(spec, eng.cache),
            jax.ShapeDtypeStruct((B, T), np.int32),
            jax.ShapeDtypeStruct((B,), np.int32),
            jax.ShapeDtypeStruct((B,), np.int32),
            jax.ShapeDtypeStruct((B,), np.bool_),
            jax.ShapeDtypeStruct((B, n_pp), np.int32))
    text = eng._step_fns[key].lower(*args).compile().as_text()
    return "tpu_custom_call" in text


def check_logits(cfg, params):
    import jax
    from repro.engine.runner import InstanceEngine
    from repro.models.model import forward

    rng = np.random.default_rng(SEED)
    prompt = rng.integers(0, cfg.vocab_size, PROMPT).astype(np.int32)
    eng = InstanceEngine(cfg, params, n_slots=1, max_len=PROMPT + DECODE,
                         devices=[jax.devices()[0]])
    got, fed = greedy_run(eng, prompt, DECODE)
    tokens = np.concatenate([prompt, np.asarray(fed, np.int32)])[None]
    ref = jax.jit(lambda p, t: forward(p, cfg, t)[0][0, PROMPT - 1:])(
        params, tokens)
    err = rel_err(got, ref)
    top1 = int(np.sum(np.argmax(got, -1) == np.argmax(np.asarray(ref), -1)))
    print(f"logits: paged prefill of {PROMPT} tokens + {DECODE} decode "
          f"steps vs forward(cache=None): max|err|/max|ref| = {err:.3e} "
          f"(tolerance {LOGITS_RTOL:.0e}), top-1 agrees at {top1}/"
          f"{DECODE + 1} positions", flush=True)
    if not err <= LOGITS_RTOL:
        raise SystemExit("FAIL: paged-kernel logits outside tolerance")
    keys = sorted(eng._step_fns)
    decode = [k for k in keys if k[0] == 1]
    prefill = [k for k in keys if k[0] > 1]
    found = {"decode": step_has_kernel(eng, decode[0]),
             "prefill": step_has_kernel(eng, prefill[0])}
    print(f"tpu_custom_call in compiled step programs: {found} "
          f"(decode {decode[0]}, prefill {prefill[0]})", flush=True)
    if not all(found.values()):
        raise SystemExit("FAIL: a step program runs without the kernels")


def serve_session(cfg, params):
    from repro.launch import serve
    args = serve.build_parser().parse_args([
        "--backend", "engine", "--arch", ARCH, "--layers", str(LAYERS),
        "--instances", "2", "--policy", "dyna", "--open-loop",
        "--requests", "8", "--prompt-len", "1024", "--max-new", "64",
        "--seed", str(SEED)])
    m, session = serve.serve_engine(args, model=(cfg, params))
    moved = session.backend.kv_bytes_moved
    print(f"serve: {m.completed}/{m.offered} requests completed, "
          f"alpha->beta KV handoff bytes {moved}", flush=True)
    if m.completed != 8 or m.offered != 8:
        raise SystemExit("FAIL: not every request completed")
    if moved <= 0:
        raise SystemExit("FAIL: no alpha->beta KV handoff moved bytes")


def _post(port, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    conn.request("POST", "/v1/completions", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def serve_http():
    from repro.serving.http import ServerConfig, ServingServer
    srv = ServingServer(ServerConfig(
        port=0, backend="engine", arch=ARCH, layers=LAYERS,
        n_instances=2, engine_slots=16, engine_max_len=1120)).start()
    try:
        rng = np.random.default_rng(SEED + 1)
        prompts = [rng.integers(0, 1000, n).tolist() for n in (300, 40)]
        status, data = _post(srv.port, {"prompt": prompts[0],
                                        "max_tokens": 16})
        unary = json.loads(data) if status == 200 else {}
        n_unary = unary.get("usage", {}).get("completion_tokens", 0)
        status2, data2 = _post(srv.port, {"prompt": prompts[1],
                                          "max_tokens": 16, "stream": True})
        events = [l for l in data2.decode().splitlines()
                  if l.startswith("data: ")]
        done = status2 == 200 and events and events[-1] == "data: [DONE]"
        print(f"http: unary {status} with {n_unary} tokens, SSE {status2} "
              f"with {len(events) - 1 if done else 0} token events",
              flush=True)
        if not (status == 200 and n_unary == 16 and done):
            raise SystemExit("FAIL: HTTP completions")
    finally:
        srv.stop()


def four_chips(cfg, params):
    """Cross-chip handoff and TP=4, each against chip 0 alone."""
    import jax
    from repro.engine.backend import EngineBackend
    from repro.engine.runner import BatchItem, InstanceEngine

    devs = jax.devices()
    rng = np.random.default_rng(SEED)
    prompt = rng.integers(0, cfg.vocab_size, PROMPT).astype(np.int32)
    be = EngineBackend(cfg, params, n_slots=2, max_len=PROMPT + DECODE)
    for iid in range(4):
        be.spawn(iid)
    placed = [next(iter(be.engines[i].params["embed"].devices())).id
              for i in range(4)]
    print(f"4 one-device instances on devices {placed}", flush=True)
    if placed != [d.id for d in devs]:
        raise SystemExit("FAIL: instances are not one per chip")
    alpha = be.engines[0]
    ref, fed = greedy_run(alpha, prompt, DECODE)        # chip 0 alone
    sa = alpha.alloc("alpha")
    alpha.run_batch([BatchItem(sa, prompt, 0)])
    pieces = alpha.export_state(sa, upto=PROMPT, chunk=256)
    for iid in (1, 2, 3):
        beta = be.engines[iid]
        sb = beta.alloc("beta")
        beta.import_state(sb, pieces)
        got = [beta.run_batch([BatchItem(
            sb, np.asarray([t], np.int32), PROMPT + i, True)])[sb]
            for i, t in enumerate(fed)]
        err = rel_err(np.stack(got), ref[1:])
        print(f"handoff chip 0 -> chip {iid}: decode logits vs chip 0 "
              f"alone: max|err|/max|ref| = {err:.3e} (tolerance "
              f"{HANDOFF_RTOL:.0e})", flush=True)
        if not err <= HANDOFF_RTOL:
            raise SystemExit("FAIL: cross-chip handoff changed the logits")
    del be, alpha, beta
    gc.collect()
    tp = InstanceEngine(cfg, params, n_slots=1, max_len=PROMPT + DECODE,
                        devices=devs)
    got, _ = greedy_run(tp, prompt, DECODE, feed=fed)
    err = rel_err(got, ref)
    print(f"TP=4 instance vs chip 0 alone: max|err|/max|ref| = {err:.3e} "
          f"(tolerance {LOGITS_RTOL:.0e})", flush=True)
    if not err <= LOGITS_RTOL:
        raise SystemExit("FAIL: TP=4 logits outside tolerance")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.serve import use_compile_cache
    from repro.models.model import engine_model

    cache_dir = use_compile_cache()
    clock = CompileClock(jax)
    print(f"device_kind {dev.device_kind!r}, {len(jax.devices())} "
          f"device(s); compile cache {cache_dir}", flush=True)

    t0, c0, h0 = time.monotonic(), clock.seconds, clock.hits
    cfg, params = engine_model(ARCH, LAYERS, SEED)
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    kv_tok = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.hd * 2
    print(f"model {ARCH}: published widths d_model={cfg.d_model} "
          f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} "
          f"head_dim={cfg.hd} d_ff={cfg.d_ff} vocab={cfg.vocab_size}; "
          f"depth cut to {cfg.n_layers} of 48 layers; weights {n_bytes} "
          f"bytes ({cfg.dtype}); KV {kv_tok} bytes/token", flush=True)
    phase("init", clock, dev, t0, c0, h0)

    if args.chips == 4:
        t0, c0, h0 = time.monotonic(), clock.seconds, clock.hits
        four_chips(cfg, params)
        phase("4-chip", clock, dev, t0, c0, h0)
    else:
        for name, fn in (("serve", lambda: serve_session(cfg, params)),
                         ("logits", lambda: check_logits(cfg, params))):
            t0, c0, h0 = time.monotonic(), clock.seconds, clock.hits
            fn()
            gc.collect()
            phase(name, clock, dev, t0, c0, h0)
        del params
        gc.collect()
        t0, c0, h0 = time.monotonic(), clock.seconds, clock.hits
        serve_http()
        phase("http", clock, dev, t0, c0, h0)
    print(f"compile total: {clock.seconds:.1f} s, {clock.hits} "
          f"persistent-cache hits (smoke reading, not a benchmark)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

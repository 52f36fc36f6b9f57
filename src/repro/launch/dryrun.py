import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512"))

"""Multi-pod dry-run driver.

Lowers + compiles every (architecture x input-shape) step on the
production mesh (16x16 single-pod / 2x16x16 multi-pod) with
ShapeDtypeStruct stand-ins — no arrays are ever allocated — and extracts:

  * ``compiled.memory_analysis()``  (per-device bytes: proves it fits)
  * ``compiled.cost_analysis()``    (FLOPs / bytes for the roofline)
  * collective bytes parsed from the post-SPMD HLO text
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute operand sizes)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch chatglm3-6b \
      --shape decode_32k [--multi-pod] [--out results/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all

Env overrides (used by the CPU test-suite to keep meshes small):
  REPRO_DRYRUN_DEVICES=8  REPRO_DRYRUN_MESH=2x4  REPRO_DRYRUN_MESH_MULTI=2x2x2
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, ASSIGNED_ARCHS, INPUT_SHAPES, canonical, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    batch_shardings, cache_shardings, effective_window, input_specs,
    opt_shardings, param_shardings,
)
from repro.models import mixers as _mixers
from repro.models.model import forward
from repro.training.optimizer import AdamWConfig, adamw_init
from repro.training.train import make_train_step

# ---------------------------------------------------------------------------
# hardware constants (TPU v5e)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
LINK_BW = 50e9               # bytes/s / link (ICI)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^=]*?\))|(?:\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    per_kind = {}
    for m in _COLL_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(type_str)
        d = per_kind.setdefault(kind, {"count": 0, "bytes": 0})
        d["count"] += 1
        d["bytes"] += b
    total = sum(d["bytes"] for d in per_kind.values())
    return {"per_kind": per_kind, "bytes_per_device": total}


# ---------------------------------------------------------------------------
def _mesh_from_env(multi_pod: bool):
    key = "REPRO_DRYRUN_MESH_MULTI" if multi_pod else "REPRO_DRYRUN_MESH"
    spec = os.environ.get(key)
    if spec:
        dims = tuple(int(x) for x in spec.split("x"))
        axes = ("pod", "data", "model") if len(dims) == 3 else ("data", "model")
        return jax.make_mesh(dims, axes, axis_types=(
            jax.sharding.AxisType.Auto,) * len(axes))
    return make_production_mesh(multi_pod=multi_pod)


def _microbatches(cfg, shape) -> int:
    if shape.step != "train":
        return 1
    n = cfg.param_count()
    if n > 100e9:
        return 16
    if n > 20e9:
        return 8
    return 4


def build(cfg, shape, mesh, unroll: bool = False):
    """Returns (step_fn, in_shardings tuple, abstract args tuple).

    ``unroll=True`` replaces layer/microbatch scans with python unrolls —
    required for cost extraction because XLA's cost_analysis counts a
    while-loop body exactly once regardless of trip count."""
    kind, specs = input_specs(cfg, shape)
    # flash-decoding via shard_map when the cache seq dim is model-sharded
    # (kv_heads not divisible by the model axis) — §Perf iteration C1
    if (shape.step == "decode"
            and cfg.n_kv_heads % mesh.shape["model"] != 0
            and not cfg.is_attention_free
            and not os.environ.get("REPRO_DISABLE_SEQSHARD")):
        _mixers.SEQ_SHARD = {"mesh": mesh, "axis": "model"}
    else:
        _mixers.SEQ_SHARD = {}
    # keep the constructed full-prompt cache (§Perf C2) on the cache
    # sharding the serve path uses: (B@data, S[@model if kv small], KV, hd)
    if shape.step == "prefill" and not cfg.is_attention_free:
        from repro.launch.specs import cache_spec as _cs
        kv_spec = _cs(["blocks", 0, "k"],
                      (cfg.n_groups, shape.global_batch, shape.seq_len,
                       cfg.n_kv_heads, cfg.hd), cfg, mesh)
        pos_spec = _cs(["blocks", 0, "pos"],
                       (cfg.n_groups, shape.global_batch, shape.seq_len),
                       cfg, mesh)
        from jax.sharding import PartitionSpec as _P
        _mixers.PREFILL_CACHE_SHARD = {
            "mesh": mesh,
            "kv_spec": _P(*tuple(kv_spec)[1:]),
            "pos_spec": _P(*tuple(pos_spec)[1:]),
        }
    else:
        _mixers.PREFILL_CACHE_SHARD = {}
    params = specs["params"]
    use_fsdp = bool(cfg.sharding.fsdp)

    if kind == "train":
        opt_cfg = AdamWConfig(
            moment_dtype="bfloat16" if cfg.param_count() > 100e9 else "float32")
        opt = adamw_init(params, opt_cfg, abstract=True)
        # microbatching only matters for real memory; the unrolled cost
        # variant uses 1 so per-step flops are counted exactly once
        nmb = 1 if unroll else _microbatches(cfg, shape)
        step = make_train_step(cfg, opt_cfg, num_microbatches=nmb,
                               remat=True, unroll=unroll)
        in_sh = (param_shardings(params, cfg, mesh, train=True),
                 opt_shardings(opt, params, cfg, mesh),
                 batch_shardings(specs["batch"], mesh))
        # donate params+opt: the optimizer updates them in place
        return step, in_sh, (params, opt, specs["batch"]), (0, 1)

    wo = effective_window(cfg, shape)
    if kind == "prefill":
        has_ee = "extra_embeds" in specs
        has_fr = "frames" in specs

        def prefill_step(params, cache, tokens, *rest):
            kw = {}
            i = 0
            if has_ee:
                kw["extra_embeds"] = rest[i]; i += 1
            if has_fr:
                kw["frames"] = rest[i]; i += 1
            logits, new_cache, _ = forward(
                params, cfg, tokens, cache=cache, pos_offset=0,
                last_only=True, window_override=wo, unroll=unroll, **kw)
            return jnp.argmax(logits[:, 0], -1).astype(jnp.int32), new_cache

        args = [params, specs["cache"], specs["tokens"]]
        shard = [param_shardings(params, cfg, mesh, train=use_fsdp),
                 cache_shardings(specs["cache"], cfg, mesh),
                 batch_shardings({"tokens": specs["tokens"]}, mesh)["tokens"]]
        if has_ee:
            args.append(specs["extra_embeds"])
            shard.append(batch_shardings(
                {"extra_embeds": specs["extra_embeds"]}, mesh)["extra_embeds"])
        if has_fr:
            args.append(specs["frames"])
            shard.append(batch_shardings(
                {"frames": specs["frames"]}, mesh)["frames"])
        return prefill_step, tuple(shard), tuple(args), (1,)

    # decode: one token against a seq_len cache, donated for in-place
    # update.  (An external-append variant exists — §Perf iteration A3 —
    # but XLA-CPU cost accounting duplicates read-only cache slices per
    # flash tile, so the donated in-place form is the honest roofline.)
    def serve_step(params, cache, tokens, pos_offset):
        logits, new_cache, _ = forward(
            params, cfg, tokens, cache=cache, pos_offset=pos_offset,
            last_only=True, window_override=wo, unroll=unroll)
        return jnp.argmax(logits[:, 0], -1).astype(jnp.int32), new_cache

    args = (params, specs["cache"], specs["tokens"], specs["pos_offset"])
    shard = (param_shardings(params, cfg, mesh, train=use_fsdp),
             cache_shardings(specs["cache"], cfg, mesh),
             batch_shardings({"tokens": specs["tokens"]}, mesh)["tokens"],
             batch_shardings({"pos_offset": specs["pos_offset"]}, mesh)["pos_offset"])
    # donate the KV cache: functional .at[] updates must alias, not copy
    return serve_step, shard, args, (1,)


def roofline_terms(flops_per_dev, bytes_per_dev, coll_bytes_per_dev,
                   n_chips) -> dict:
    return {
        "compute_s": flops_per_dev / PEAK_FLOPS,
        "memory_s": bytes_per_dev / HBM_BW,
        "collective_s": coll_bytes_per_dev / LINK_BW,
    }


def _with_groups(cfg, g: int, dtype=None):
    """Same family, g pattern-groups (plus the original tail blocks)."""
    kw = {"n_layers": g * cfg.pattern_len + len(cfg.tail_kinds)}
    if cfg.encoder_layers:
        assert cfg.encoder_layers % cfg.n_groups == 0
        kw["encoder_layers"] = cfg.encoder_layers // cfg.n_groups * g
    if dtype is not None:
        kw["dtype"] = dtype
    return cfg.with_(**kw)


def extract_costs(cfg, shape, mesh) -> dict:
    """Exact roofline inputs via G-extrapolation.

    XLA's cost_analysis counts a while-loop body once, so the scan-form
    numbers undercount by the trip count.  Instead compile UNROLLED
    variants with 1 and 2 pattern-groups (seconds each) and extrapolate:
    metric(G) = m1 + (G-1)·(m2-m1), exact for homogeneous group stacks
    (embeddings/lm_head cancel in the difference)."""
    # The CPU backend has no native bf16 matmul: XLA inserts (and hoists)
    # whole-tensor f32 conversions that a TPU's MXU never materializes,
    # poisoning "bytes accessed".  Extract costs from an f32 build and
    # halve float traffic to model bf16 storage (DTYPE_SCALE).
    DTYPE_SCALE = 0.5 if cfg.dtype == "bfloat16" else 1.0
    out = {"dtype_scale": DTYPE_SCALE}
    ms = []
    for g in (1, 2):
        cfg_g = _with_groups(cfg, g, dtype="float32")
        step, in_sh, args, donate = build(cfg_g, shape, mesh, unroll=True)
        with mesh:
            compiled = jax.jit(step, in_shardings=in_sh,
                               donate_argnums=donate).lower(*args).compile()
        cost = compiled.cost_analysis()
        coll = collective_stats(compiled.as_text())
        ms.append({
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll_bytes": float(coll["bytes_per_device"]),
            "coll_per_kind": coll["per_kind"],
        })
    G = cfg.n_groups
    for k in ("flops", "bytes", "coll_bytes"):
        out[k] = ms[0][k] + (G - 1) * (ms[1][k] - ms[0][k])
    out["bytes"] *= DTYPE_SCALE
    out["coll_bytes"] *= DTYPE_SCALE
    # per-kind collective extrapolation
    kinds = set(ms[0]["coll_per_kind"]) | set(ms[1]["coll_per_kind"])
    per_kind = {}
    for k in kinds:
        b1 = ms[0]["coll_per_kind"].get(k, {"bytes": 0, "count": 0})
        b2 = ms[1]["coll_per_kind"].get(k, {"bytes": 0, "count": 0})
        per_kind[k] = {
            "bytes": b1["bytes"] + (G - 1) * (b2["bytes"] - b1["bytes"]),
            "count": b1["count"] + (G - 1) * (b2["count"] - b1["count"]),
        }
    out["coll_per_kind"] = per_kind
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool,
            keep_hlo: bool = False) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = _mesh_from_env(multi_pod)
    n_chips = mesh.size
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "n_chips": n_chips, "step": shape.step,
        "window_override": effective_window(cfg, shape),
        "status": "ok",
    }
    t0 = time.time()
    try:
        # pass 1 (scan form): proves lowering + memory analysis
        step, in_sh, args, donate = build(cfg, shape, mesh)
        with mesh:
            lowered = jax.jit(step, in_shardings=in_sh,
                              donate_argnums=donate).lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        # pass 2: exact cost extraction via unrolled G-extrapolation
        costs = extract_costs(cfg, shape, mesh)
        coll = {"per_kind": costs["coll_per_kind"],
                "bytes_per_device": costs["coll_bytes"]}
        flops = costs["flops"]
        bytes_acc = costs["bytes"]
        rec.update({
            "lower_s": round(t_lower - t0, 2),
            "compile_s": round(t_compile - t_lower, 2),
            "memory_analysis": {
                "argument_size_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
                "output_size_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
                "temp_size_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
                "generated_code_size_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
            },
            "flops_per_device": flops,
            "bytes_per_device": bytes_acc,
            "collectives": coll,
            "roofline": roofline_terms(flops, bytes_acc,
                                       coll["bytes_per_device"], n_chips),
            "hlo_ops": len(hlo.splitlines()),
            "unroll_compile_s": round(time.time() - t_compile, 2),
        })
        if keep_hlo:
            rec["hlo"] = hlo
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="sweep all assigned archs x shapes")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--print-hlo", action="store_true")
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    combos = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in INPUT_SHAPES:
                combos.append((a, s, args.multi_pod))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos.append((args.arch, args.shape, args.multi_pod))

    ok = 0
    for arch, shape, mp in combos:
        rec = run_one(arch, shape, mp, keep_hlo=args.print_hlo)
        tag = "multi" if mp else "single"
        path = os.path.join(args.out, f"{canonical(arch)}__{shape}__{tag}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        status = rec["status"]
        ok += status == "ok"
        r = rec.get("roofline", {})
        print(f"[{status:5s}] {arch:22s} {shape:12s} mesh={rec['mesh']:9s} "
              f"lower={rec.get('lower_s', '-'):>7} compile={rec.get('compile_s', '-'):>7} "
              f"comp={r.get('compute_s', 0)*1e3:8.2f}ms mem={r.get('memory_s', 0)*1e3:8.2f}ms "
              f"coll={r.get('collective_s', 0)*1e3:8.2f}ms"
              + ("" if status == "ok" else f"  {rec.get('error', '')[:120]}"),
              flush=True)
    print(f"{ok}/{len(combos)} combos lowered+compiled")
    return 0 if ok == len(combos) else 1


if __name__ == "__main__":
    sys.exit(main())

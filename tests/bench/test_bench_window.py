"""The harness's window and result line on the CPU at the smoke size,
and the command's refusal to run without a TPU."""
import json
import os
import subprocess
import sys

import numpy as np

import bench_smoke
from bench_smoke import LIMIT, MIX, SEED, SPEC

CELL = {"name": "smoke.smoke", "config": "smoke", "traffic": "smoke",
        "chips": 1}


def _bench():
    return json.loads((bench_smoke.ROOT / "BENCHMARK.json").read_text())


def test_window_and_result_line():
    sys.path.insert(0, str(bench_smoke.ROOT / "bench"))
    import run
    bench = _bench()
    out = run.measure(bench, CELL, SEED, 2.0, 0, SPEC, MIX, LIMIT)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checked"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    names = {m["name"] for m in bench["end_to_end"]
             if "workloads" not in m}
    assert names <= set(out["metrics"]) and "setup_s" in names
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])
        assert m["value"] > 0
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] >= 1
    gap = out["checked"]["max_logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"] == LIMIT
    json.dumps(out)


def test_command_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = _bench()["workloads"][0]["name"]
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", cell,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=bench_smoke.ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[-1] \
        .startswith("{")


def test_warm_shapes_cover_the_reachable_tables():
    """Every chunk bucket up to the longest prompt chunk, times every
    table bucket from the shortest initial context (the table never
    narrows below it while that population is held) to the longest."""
    from types import SimpleNamespace

    from repro.engine.runner import bucket_ladder
    from bench.harness import warm_shapes
    from bench.traffic import Arrival

    eng = SimpleNamespace(max_chunk=512, buckets=bucket_ladder(512),
                          page_size=8, page_buckets=bucket_ladder(4096))
    arr = lambda due, p, d: Arrival("r", due, np.zeros(p, np.int32), d)
    shapes = warm_shapes(eng, [arr(0.0, 300, 50), arr(0.0, 5000, 10),
                               arr(3.0, 100, 900)])
    assert {n for _, n in shapes} == {64, 128, 256, 512, 1024}
    assert {T for T, _ in shapes} == set(bucket_ladder(512))
    # no initial population: every table from one page up
    shapes = warm_shapes(eng, [arr(1.0, 20, 4), arr(2.0, 9, 30)])
    assert {n for _, n in shapes} == {1, 2, 4, 8}
    assert {T for T, _ in shapes} == {1, 2, 4, 8, 16, 32}
    assert all(n * 8 > T // 2 for T, n in shapes)


def test_deployment_is_honoured_or_refused():
    """Each cell's configuration deploys on the chips the cell asks for,
    and the harness refuses a deployment it cannot run as stated rather
    than run one instance on one chip in its place."""
    import dataclasses
    import time

    import pytest

    from bench.harness import run_cell
    from bench.model import load_spec
    for cell in _bench()["workloads"]:
        assert load_spec(cell["config"]).chips == cell["chips"]
    for dep in ({"instances": 4}, {"chips": 4}):
        with pytest.raises(SystemExit, match="one instance on one chip"):
            run_cell(dataclasses.replace(SPEC, **dep), MIX, SEED, 1.0,
                     time.monotonic())

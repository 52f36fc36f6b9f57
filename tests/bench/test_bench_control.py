"""The check's control: a run with the timed path broken underneath
(each fault a one-chip served cell can have) must come out not correct.
The harness's look for a chip is skipped; the rest of a run is driven
at the smoke size on the CPU."""
import json
import sys

import pytest

import bench_smoke
from bench.harness import FAULTS
from bench_smoke import LIMIT, MIX, SEED, SPEC


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(fault):
    sys.path.insert(0, str(bench_smoke.ROOT / "bench"))
    import run
    bench = json.loads((bench_smoke.ROOT / "BENCHMARK.json").read_text())
    cell = {"name": "smoke.smoke", "chips": 1}
    out = run.measure(bench, cell, SEED, 2.0, 0, SPEC, MIX, LIMIT,
                      fault=fault)
    assert out["correct"] is False
    assert out["checked"]["max_logit_gap"]["value"] > 10 * LIMIT


def test_fp8_control_fails_the_limit(capsys):
    """The control of the check at a size a test holds, through the same
    run and rule: the reference computed with fp8 operands, put in the
    program's place, comes out not correct, while the program's own
    served tokens of the same run lie inside the limit."""
    sys.path.insert(0, str(bench_smoke.ROOT / "bench"))
    import run
    bench = json.loads((bench_smoke.ROOT / "BENCHMARK.json").read_text())
    cell = {"name": "smoke.smoke", "chips": 1}
    out = run.measure(bench, cell, SEED + 1, 2.0, 0, SPEC, MIX, LIMIT,
                      control="fp8")
    assert out["correct"] is False
    assert out["checked"]["max_logit_gap"]["value"] > LIMIT
    line = next(x for x in capsys.readouterr().out.splitlines()
                if x.startswith("control fp8:"))
    served = float(line.split("max_logit_gap ")[1].split(";")[0])
    assert 0 <= served <= LIMIT

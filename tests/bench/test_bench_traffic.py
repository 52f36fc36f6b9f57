"""The traffic files: deterministic in the seed, the same work in the
same order for every seed, and sampled medians at the stated modes."""
import numpy as np
import pytest

import bench_smoke  # noqa: F401
from bench.traffic import TRAFFIC_DIR, generate, load_mix

MIXES = sorted(p.stem for p in TRAFFIC_DIR.glob("*.json"))


@pytest.mark.parametrize("name", MIXES)
def test_deterministic_in_seed(name):
    mix = load_mix(name)
    a = generate(mix, 2**31 + 7, 120.0, 1000, 1 << 20)
    b = generate(mix, 2**31 + 7, 120.0, 1000, 1 << 20)
    c = generate(mix, 5, 120.0, 1000, 1 << 20)
    assert [(x.due, x.output_len, x.prompt.tolist()) for x in a] == \
        [(x.due, x.output_len, x.prompt.tolist()) for x in b]
    # another seed: the same sizes and arrivals in the same order, other
    # token ids
    assert [(x.rid, x.due, len(x.prompt), x.output_len) for x in a] == \
        [(x.rid, x.due, len(x.prompt), x.output_len) for x in c]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    assert all(0 <= x.due < 120.0 for x in a)
    assert np.all(np.diff([x.due for x in a]) >= 0)
    assert all(int(x.prompt.max()) < 1000 for x in a)


@pytest.mark.parametrize("name", MIXES)
def test_medians_match_modes(name):
    """The Poisson arrivals' lengths (the initial population, part-way
    through its outputs, is left out)."""
    mix = dict(load_mix(name), initial_population=0)
    arr = generate(mix, 3, 4000.0 / mix["rate_per_s"], 50, 1 << 20)
    assert abs(len(arr) - 4000) <= 1
    for c in mix["classes"]:
        for key, lens in (("prompt", [len(x.prompt) for x in arr]),
                          ("output", [x.output_len for x in arr])):
            if len(mix["classes"]) == 1:
                assert np.median(lens) == pytest.approx(c[key]["median"],
                                                        rel=0.02)
    if len(mix["classes"]) > 1:
        # a two-class mix: the median of all prompts lies between modes
        lo, hi = sorted(c["prompt"]["median"] for c in mix["classes"])
        assert lo < np.median([len(x.prompt) for x in arr]) < hi


def test_context_bound_leaves_room_for_output():
    arr = generate(load_mix("hybrid-overload"), 1, 200.0, 50, 4096)
    assert all(len(x.prompt) + x.output_len <= 4096 for x in arr)


def test_initial_population_is_the_same_work_for_every_seed():
    mix = dict(load_mix("reasoning-overload"), initial_population=16)
    a = generate(mix, 1, 60.0, 50, 1 << 20)
    b = generate(mix, 2**31 + 5, 60.0, 50, 1 << 20)
    ia = [(len(x.prompt), x.output_len) for x in a if x.due == 0.0]
    ib = [(len(x.prompt), x.output_len) for x in b if x.due == 0.0]
    assert len(ia) == 16 and sorted(ia) == sorted(ib)
    # staggered ages: the remaining outputs spread over the whole range
    rem = sorted(d for _, d in ia)
    assert rem[0] < 0.2 * rem[-1]

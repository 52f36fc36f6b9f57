"""The decode steps' table counter and its reader: a decode step's
``engine.tables`` span carries ``pages`` (the sum over its rows of
ceil(length / page)) and ``n_pp`` (the table's width) in a real CPU
trace at the smoke size, and ``decode_table_read_pct`` reads them from
a synthetic window, or reads nothing where they are absent."""
import dataclasses
import sys

import numpy as np
import pytest

import bench_smoke
from bench import spans
from bench.harness import _instrument
from bench.trace import Context
from test_bench_spans import W, _traced, smoke_model, synthetic  # noqa: F401

from repro.engine import BatchItem, InstanceEngine

# the synthetic window's decode steps 8 and 9 (engine.tables at 133.2
# and 150.2 ms) read 5 of 4 x 4 and 6 of 4 x 8 table entries
TABLES = {133.2: dict(pages=5, n_pp=4), 150.2: dict(pages=6, n_pp=8)}


def _with_tables(events, stats=TABLES):
    out = []
    for e in events:
        at = round((e.start_ns - 500e9) / 1e6, 6)
        if e.name == "engine.tables" and at in stats:
            e = dataclasses.replace(e, stats=tuple(stats[at].items()))
        out.append(e)
    return out


def _read(events, trace_dir):
    class Win(W):
        pass
    Win.trace_dir = trace_dir
    spans._CACHE[trace_dir] = spans.from_events(events)
    sys.path.insert(0, str(bench_smoke.ROOT / "bench"))
    import run
    ctx = Context(w=Win(), tr=object(), peak={}, chips=1)
    return run._reader("decode_table_read_pct")(ctx)


def test_decode_tables_carry_the_pages_read(smoke_model, tmp_path):
    """A prefill step's tables span carries nothing; a decode step's
    carries its rows' pages and the table's width, and its dispatch
    keeps the same stats as any other step."""
    cfg, params = smoke_model
    eng = InstanceEngine(cfg, params, n_slots=4, max_len=64)
    assert eng.paged and eng.page_size == 8
    a, b = eng.alloc("a"), eng.alloc("b")
    rng = np.random.default_rng(0)
    pa, pb = (rng.integers(0, cfg.vocab_size, n).astype(np.int32)
              for n in (10, 6))
    eng.run_batch([BatchItem(a, pa[:1], 0)])
    _instrument(eng, [], lambda: True, None)
    with _traced(tmp_path):
        eng.run_batch([BatchItem(a, pa[1:], 1, True),
                       BatchItem(b, pb, 0, True)])
        eng.run_batch([BatchItem(a, pa[:1], 10, True),
                       BatchItem(b, pb[:1], 6, True)])
    got = spans.load(str(tmp_path)).spans
    tables = [s.stats for s in got if s.name == "engine.tables"]
    disp = [s.stats for s in got if s.name == "engine.dispatch"]
    # a at length 11 and b at 7 hold 2 + 1 of the table's 2 pages
    assert tables == [{}, {"n_pp": 2, "pages": 3}]
    assert disp[1] == {"seq": 2, "T": 1, "slots": 4, "tokens": 2}


def test_decode_table_read_pct_reads_decode_tables():
    # the prefill step 7's tables span carries no pages and is left out
    assert _read(_with_tables(synthetic()), "synthetic-tables") == \
        pytest.approx(100 * 11 / 48)


def test_decode_table_read_pct_skips_tables_outside_a_dispatch():
    """A tables span that no dispatch of the window holds is not put down
    to the dispatch that started last before it."""
    moved = []
    for e in _with_tables(synthetic()):
        if e.name == "engine.dispatch" and dict(e.stats)["seq"] == 9:
            e = dataclasses.replace(e, duration_ns=0.1e6)
        moved.append(e)
    assert _read(moved, "synthetic-tables-orphan") == \
        pytest.approx(100 * 5 / 16)


@pytest.mark.parametrize("events", [
    pytest.param(synthetic, id="no-page-stats"),
    pytest.param(lambda: [e for e in synthetic() if e.line == "XLA Ops"],
                 id="no-spans"),
])
def test_decode_table_read_pct_is_absent_without_page_stats(events):
    """A program older than the counter, or one without spans, reads
    nothing."""
    assert _read(events(), f"synthetic-tables-absent-{id(events)}") is None

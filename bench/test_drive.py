"""The traced part of the window: a mix's `trace_seconds` ends it at a
request boundary, and the window goes on untraced; without the key it is
the whole window."""
import dataclasses

import pytest

from bench import data, drive, harness
from bench.spec import Spec


@pytest.fixture(scope="module")
def served():
    cell = harness.load(Spec(), "deep1m_ivf_pq8.bulk", n=2000, pool=400)
    cell = dataclasses.replace(cell, mix=dict(cell.mix, size=8))
    base, pool = data.make_corpus(2**31 + 21, cell.config["data"])
    _, engine = harness.build(cell, base)
    return cell, engine, pool


@pytest.mark.parametrize("limit", [None, 0.3])
def test_traced_part(served, limit):
    cell, engine, pool = served
    engine.reset_stats()
    mix = {k: v for k, v in cell.mix.items() if k != "trace_seconds"}
    if limit is not None:
        mix["trace_seconds"] = limit
    stops = []
    win = drive.run_window(engine, pool, mix, 5, 1.2,
                           lambda: stops.append(engine.stats().n_queries))
    assert len(stops) == 1
    assert win.traced_stats.n_queries == stops[0]
    if limit is None:
        assert win.traced_s == win.end_s
        assert win.traced_stats.n_queries == win.n_rows
    else:
        assert limit <= win.traced_s < win.end_s
        assert 0 < win.traced_stats.n_queries < win.n_rows
        assert win.traced_stats.n_queries % 8 == 0


def test_untraced_window_stops_nothing(served):
    cell, engine, pool = served
    win = drive.run_window(engine, pool, dict(cell.mix, trace_seconds=0.1),
                           5, 0.5)
    assert win.traced_s == win.end_s

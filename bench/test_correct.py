"""`correct` catches what it must: a run with the timed path broken
underneath, and the control, come out not correct; a sound run does not.
CPU, small corpus; the same comparison decides a chip run."""
import numpy as np
import pytest

from bench import control, harness
from bench.spec import Spec
from repro.serve.engine import SearchEngine

CELL = "deep1m_ivf_pq8.bulk"


def _run(seconds=1.0):
    spec = Spec()
    cell = harness.load(spec, CELL, n=2000, pool=400)
    return harness.run(spec, cell, seed=2**31 + 3, seconds=seconds,
                       trace=False, rehearse=True)


def _half_batch(search):
    """Half of the batch left out: its rows answered with the others'."""
    def broken(self, queries, *a, **kw):
        d, i = search(self, queries, *a, **kw)
        h = len(i) // 2
        d[h:], i[h:] = d[:len(i) - h], i[:len(i) - h]
        return d, i
    return broken


def _altered_answer(search):
    """One id of every dispatch altered where it is produced."""
    def broken(self, queries, *a, **kw):
        d, i = search(self, queries, *a, **kw)
        i[0, 0] = (i[0, 0] + 1) % self.index.db.shape[0]
        return d, i
    return broken


def _stale(search):
    """State returned unchanged: every dispatch answers like the first."""
    first = []

    def broken(self, queries, *a, **kw):
        d, i = search(self, queries, *a, **kw)
        if not first:
            first.append((d.copy(), i.copy()))
        return first[0][0][:len(i)].copy(), first[0][1][:len(i)].copy()
    return broken


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [_half_batch, _altered_answer, _stale])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(SearchEngine, "search", fault(SearchEngine.search))
    out = _run(seconds=1.5)
    assert out["attempted"] >= 2
    assert not out["correct"], out["checks"]


def test_control_is_not_correct():
    cell = harness.load(Spec(), CELL, n=2000, pool=400)
    out = control.run_control(cell, seed=11, seconds=1.0,
                              rehearse=True)
    assert not out["correct"], out["checks"]
    assert out["checks"]["dist_gap"]["value"] > \
        out["checks"]["dist_gap"]["limit"]

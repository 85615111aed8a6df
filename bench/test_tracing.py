"""Trace reduction: interval arithmetic by hand, and a small trace of the
online IVF path recorded on one TPU v5e (bench/testdata/)."""
from pathlib import Path

import numpy as np
import pytest

from bench import tracing

TRACE = Path(__file__).parent / "testdata" / "ivf_online_v5e.xplane.pb"


def test_union_merges_overlaps():
    s, e = tracing.union(np.array([5.0, 0.0, 1.0, 10.0]),
                         np.array([6.0, 2.0, 3.0, 12.0]))
    np.testing.assert_array_equal(s, [0.0, 5.0, 10.0])
    np.testing.assert_array_equal(e, [3.0, 6.0, 12.0])


def test_gaps_split_by_host_span():
    spans = [(0.0, 4e9, "bench.serve_loop"), (4e9, 6e9, "bench.wait")]
    got = tracing.attribute(np.array([3e9, 7e9]), np.array([5e9, 8e9]),
                            spans)
    assert got == pytest.approx({"bench.serve_loop": 1.0,
                                 "bench.wait": 1.0, "host.other": 1.0})


def test_recorded_chip_trace():
    r = tracing.reduce_file(str(TRACE))
    assert r is not None and r.n_devices == 1
    assert 0 < r.busy_s < r.window_s
    idle = sum(v for _, v in r.gaps)
    assert idle == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    assert {k for k, _ in r.gaps} <= {"bench.serve_loop", "bench.wait",
                                      "bench.results", "host.other"}
    times = [v for _, v in r.ops]
    assert times == sorted(times, reverse=True)
    assert 0.5 * r.busy_s < sum(times) < 2 * r.busy_s
    assert all(name.startswith("jit_run(") for name, _ in r.ops)
    # the recorded window: 20 open-loop requests, the device busy 64%
    assert r.busy_s == pytest.approx(0.641201346)
    assert r.window_s == pytest.approx(0.968296479)

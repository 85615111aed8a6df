"""The traffic generator: a function of its seeds alone, the same work for
every run seed."""
import numpy as np

from bench import traffic

ONLINE = {"loop": "open", "rate_per_s": 10.0, "size_min": 1,
          "size_max": 16, "size_alpha": 1.5, "schedule_seed": 1, "k": 10}


def test_open_schedule_is_a_function_of_the_schedule_seed():
    a = traffic.open_schedule(ONLINE, 30.0)
    b = traffic.open_schedule(dict(ONLINE), 30.0)
    c = traffic.open_schedule(dict(ONLINE, schedule_seed=2**31 + 5), 30.0)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[1], c[1])


def test_another_schedule_seed_is_the_same_work_in_another_order():
    due_a, size_a = traffic.open_schedule(ONLINE, 30.0)
    due_b, size_b = traffic.open_schedule(dict(ONLINE, schedule_seed=2),
                                          30.0)
    assert due_a.size == due_b.size == 300
    np.testing.assert_array_equal(np.sort(size_a), np.sort(size_b))
    np.testing.assert_allclose(np.sort(np.diff(due_a)).sum(),
                               np.sort(np.diff(due_b)).sum(), rtol=0.05)
    assert np.all(np.diff(due_a) >= 0) and due_a.max() < 30.0


def test_size_distribution():
    s = traffic.size_quantiles(ONLINE, 100_000)
    assert s.min() == 1 and s.max() == 16
    w = np.arange(1, 17) ** -1.5
    mean = (np.arange(1, 17) * w).sum() / w.sum()
    assert abs(s.mean() - mean) < 0.01
    assert abs(np.mean(s == 1) - w[0] / w.sum()) < 0.001


def test_query_stream_cycles_through_the_pool():
    st = traffic.QueryStream(100, seed=3)
    first = np.concatenate([st.take(30) for _ in range(10)])   # 3 passes
    for p in range(3):
        np.testing.assert_array_equal(np.sort(first[100 * p:100 * (p + 1)]),
                                      np.arange(100))
    assert not np.array_equal(first[:100], first[100:200])
    again = traffic.QueryStream(100, seed=3)
    np.testing.assert_array_equal(again.take(300), first)

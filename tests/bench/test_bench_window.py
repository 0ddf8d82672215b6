"""Window arithmetic of the benchmark: schedules, latency, qps."""
from types import SimpleNamespace

import bench_testutil  # noqa: F401  (puts the checkout on sys.path)
import numpy as np
import pytest

from bench.window import (Request, Step, Window, arrivals, percentile,
                          poisson_gaps)


def test_schedule_same_count_and_gaps_for_every_seed():
    a = poisson_gaps(400.0, 10.0, np.random.default_rng(1))
    b = poisson_gaps(400.0, 10.0, np.random.default_rng(2**33 + 7))
    assert len(a) == len(b) == 4000
    assert a.sum() == pytest.approx(10.0)
    np.testing.assert_allclose(np.sort(a), np.sort(b))
    assert not np.array_equal(a, b)
    # exponential: mean gap 1/rate, coefficient of variation about 1
    assert a.mean() == pytest.approx(1 / 400.0)
    assert a.std() / a.mean() == pytest.approx(1.0, abs=0.1)


def test_arrivals_lie_inside_the_window():
    off = arrivals(poisson_gaps(50.0, 2.0, np.random.default_rng(0)))
    assert off[0] == 0.0 and np.all(np.diff(off) > 0) and off[-1] < 2.0


def test_schedule_without_requests_is_an_error():
    with pytest.raises(ValueError):
        poisson_gaps(0.1, 1.0, np.random.default_rng(0))


def _req(due, t_done, status="done", certified=True, service_s=0.01):
    t = SimpleNamespace(status=status, certified=certified, t_done=t_done,
                        service_s=service_s)
    return Request(due, t, due)


def test_latency_counts_from_the_due_time_over_all_requests():
    w = Window("open", 100.0, 2.0)
    w.requests = [_req(100.0 + i * 0.01, 100.0 + i * 0.01 + 0.005 * (i + 1))
                  for i in range(100)]
    lat = w.latencies_s()
    np.testing.assert_allclose(lat, 0.005 * np.arange(1, 101))
    assert percentile(lat * 1e3, 50) == pytest.approx(252.5)
    assert percentile(lat * 1e3, 99) == pytest.approx(495.05)
    assert w.qps() == pytest.approx(50.0)          # 100 done over 2 s


def test_failed_counts_not_done_and_uncertified():
    w = Window("open", 0.0, 1.0)
    w.requests = [_req(0.0, 0.1), _req(0.1, 0.2, status="failed"),
                  _req(0.2, 0.3, certified=False), _req(0.3, 0.4)]
    assert w.failed() == 2
    assert len(w.done()) == 3
    assert w.qps() == pytest.approx(3.0)


def test_closed_loop_qps_counts_an_edge_step_pro_rata():
    w = Window("closed", 10.0, 1.0)
    w.steps = [Step(10.0, 10.4, 128), Step(10.4, 10.8, 128),
               Step(10.8, 11.2, 128)]     # half of the last one inside
    assert w.qps() == pytest.approx(128 * 2.5)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        percentile([], 99)


def test_sweep_point_keeps_up_only_with_a_short_backlog():
    from bench.sweep import point
    w = Window("open", 0.0, 1.0)
    w.requests = [_req(i / 100, i / 100 + 0.05) for i in range(100)]
    w.steps = [Step(0.0, 1.0, 100)]
    pt = point(w, 100.0, 16)
    assert pt["resolved_in_window"] == 96 and pt["backlog_at_close"] == 4
    assert pt["keeps_up"] is False          # 96% resolved in the window
    w.requests = [_req(i / 100, i / 100 + 0.005) for i in range(100)]
    assert point(w, 100.0, 16)["keeps_up"] is True

"""Client loops and the arithmetic of qps, p99_ms and batch_fill, on a
scripted timeline."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import registry, traffic  # noqa: E402

closed = registry.plugin("loops", "closed")
opened = registry.plugin("loops", "open")


class ScriptedClock:
    """Time moves only when a batch is served or the loop sleeps."""

    def __init__(self, wake_late=0.0):
        self.t = 100.0
        self.wake_late = wake_late

    def now(self):
        return self.t

    def sleep_until(self, t):
        self.t = max(self.t, t + self.wake_late)


def server(clock, seconds_per_batch):
    sizes = []

    def serve(rows):
        sizes.append(len(rows))
        clock.t += seconds_per_batch(len(rows))
        b = len(rows)
        return np.zeros((b, 2)), np.tile(rows[:, None], (1, 2)), np.full(b, 7)

    return serve, sizes


def test_closed_loop_counts_all_work_over_all_time():
    clock = ScriptedClock()
    serve, sizes = server(clock, lambda b: 0.3)
    w = closed.drive(serve, {"batch": 4}, np.arange(8), 1.0, seed=0, clock=clock)
    # batches start at 0, .3, .6, .9: the fourth starts inside and counts
    assert sizes == [4, 4, 4, 4]
    assert w.seconds == pytest.approx(1.2)
    assert w.qps() == pytest.approx(16 / 1.2)
    assert [list(b.rows) for b in w.batches[:3]] == [[0, 1, 2, 3], [4, 5, 6, 7], [0, 1, 2, 3]]
    assert w.batch_fill() == 1.0


def test_closed_loop_sends_queries_in_the_given_order():
    clock = ScriptedClock()
    serve, _ = server(clock, lambda b: 0.3)
    w = closed.drive(serve, {"batch": 3}, np.array([3, 1, 2, 0]), 0.5, seed=0,
                     clock=clock)
    assert [list(b.rows) for b in w.batches] == [[3, 1, 2], [0, 3, 1]]


def test_open_loop_times_from_due_and_pads_to_powers_of_two():
    clock = ScriptedClock(wake_late=0.001)
    serve, sizes = server(clock, lambda b: 0.1)
    due = np.array([0.0, 0.01, 0.02, 0.5, 0.55, 0.56, 0.57, 0.58])
    w = opened.serve_due(serve, due, np.arange(4), max_batch=4, clock=clock)
    # t=0: request 0 alone (bucket 1), done 0.1; then 1, 2 (bucket 2), done
    # 0.2; sleep to 0.5, wake at 0.501: request 3 (bucket 1) done 0.601;
    # then 4..7 (bucket 4) done 0.701.
    assert sizes == [1, 2, 1, 4]
    np.testing.assert_allclose(
        w.latency_s, [0.1, 0.19, 0.18, 0.101, 0.151, 0.141, 0.131, 0.121], atol=1e-9)
    assert w.p99_ms() == pytest.approx(np.percentile(w.latency_s, 99) * 1e3)
    assert w.batch_fill() == pytest.approx(8 / 8)
    np.testing.assert_allclose(w.late_s, [0.001])
    assert [list(b.rows) for b in w.batches] == [[0], [1, 2], [3], [0, 1, 2, 3]]


def test_open_loop_padding_counts_in_fill():
    clock = ScriptedClock()
    serve, sizes = server(clock, lambda b: 0.05)
    due = np.array([0.0, 0.001, 0.002])  # all due at once after the first
    w = opened.serve_due(serve, due, np.arange(8), max_batch=64, clock=clock)
    assert sizes == [1, 2]
    due = np.zeros(3)
    w = opened.serve_due(server(ScriptedClock(), lambda b: 0.05)[0], due, np.arange(8), 64,
                         clock=ScriptedClock())
    assert w.slots == 4 and w.batch_fill() == pytest.approx(0.75)


def test_bucket():
    assert [traffic.bucket(b, 64) for b in (1, 2, 3, 5, 33, 64)] == [1, 2, 4, 8, 64, 64]


def test_stratified_poisson_same_gaps_in_another_order():
    proc = registry.plugin("arrivals", "stratified_poisson")
    a = proc.times({"rate_hz": 500.0}, 10.0, seed=1)
    b = proc.times({"rate_hz": 500.0}, 10.0, seed=2**33)
    assert len(a) == len(b) == 5000
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0)), np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)
    assert a[-1] == pytest.approx(10.0, rel=0.01)


def test_open_loop_due_times_come_from_the_named_process():
    """The mix names its arrival process and its rate: doubling the rate
    doubles the requests, and no request is due after the window."""
    mix = {"max_batch": 8, "arrivals": {"process": "stratified_poisson", "rate_hz": 100.0}}
    due = opened.due_times(mix, 2.0, seed=3)
    assert len(due) == 200 and due.max() < 2.0 and np.all(np.diff(due) > 0)
    mix["arrivals"]["rate_hz"] = 200.0
    assert len(opened.due_times(mix, 2.0, seed=3)) == 400
    assert opened.shapes(mix) == [1, 2, 4, 8]

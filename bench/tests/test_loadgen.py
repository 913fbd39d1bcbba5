"""The closed and Poisson generators' accounting, against a fake server on
a fake clock (each step takes ``step_s`` and serves up to ``batch``)."""
import math
from dataclasses import dataclass

import numpy as np
import pytest

from bench import loadgen


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(0.0, s)


@dataclass
class Req:
    rid: int
    query: np.ndarray
    indices: np.ndarray = None
    mask: np.ndarray = None


class FakeServer:
    def __init__(self, clock, batch=4, step_s=1.0):
        self.clock, self.batch, self.step_s = clock, batch, step_s
        self.queue, self.finished, self.n = [], [], 0

    def submit(self, q):
        r = Req(self.n, np.asarray(q))
        self.n += 1
        self.queue.append(r)
        return r

    def step(self):
        reqs, self.queue = self.queue[:self.batch], self.queue[self.batch:]
        self.clock.t += self.step_s
        for r in reqs:
            r.indices = np.asarray([int(r.query[0])] * 3)
            r.mask = np.ones(8)
            self.finished.append(r)
        return len(reqs)


QUERIES = np.arange(10, dtype=np.float32)[:, None] * np.ones((1, 2))


def test_closed_loop_keeps_outstanding_and_times_from_issue():
    clk = Clock()
    srv = FakeServer(clk, batch=4, step_s=1.0)
    win = loadgen.run_closed(srv, QUERIES, {"outstanding": 8}, 5.5,
                             clock=clk)
    # 6 steps of 4 in the window (t0 + 6 > t0 + 5.5), then 2 to drain
    assert len(win.window_steps()) == 6
    assert win.answered_in_window() == 24
    assert win.seconds == pytest.approx(6.0)
    assert win.unanswered() == 0 and len(win.qid) == 32
    lat = win.latencies()
    # the first 4 were issued at t0 and served by step 1, the next 4 by
    # step 2; every later request waits two steps behind the 8 in flight
    assert list(lat[:8]) == [1.0] * 4 + [2.0] * 4
    assert np.all(lat[8:] == 2.0)
    # the test set is cycled and every answer is filed with its query
    assert win.qid[:12] == list(range(10)) + [0, 1]
    assert all(a[0] == q for a, q in zip(win.answers, win.qid))
    assert all(r.mask is None for r in srv.finished) and not srv.finished


def test_poisson_times_from_due_time_and_records_lateness():
    clk = Clock()
    srv = FakeServer(clk, batch=100, step_s=0.5)
    rng = np.random.default_rng(0)
    win = loadgen.run_poisson(srv, QUERIES, {"rate_qps": 4.0}, 10.0,
                              rng=rng, clock=clk, sleep=clk.sleep)
    due = np.asarray(win.t_due) - win.t0
    assert len(due) == 40 and due[0] == 0.0 and due[-1] < 10.0
    sub = np.asarray(win.t_submit)
    done = np.asarray(win.t_done)
    assert np.all(sub >= np.asarray(win.t_due))
    # each answer comes one step after its submit; latency counts the wait
    # from the due time, so it is at least the step
    assert np.allclose(done - sub, 0.5)
    assert np.all(win.latencies() >= 0.5 - 1e-9)
    assert np.allclose(win.latencies(), done - np.asarray(win.t_due))
    assert win.unanswered() == 0


def test_poisson_gaps_are_the_same_multiset_for_every_seed():
    a = loadgen.arrival_offsets(50.0, 10.0, np.random.default_rng(1))
    b = loadgen.arrival_offsets(50.0, 10.0, np.random.default_rng(2))
    assert len(a) == len(b) == 500
    assert not np.allclose(a, b)
    assert np.allclose(np.sort(np.diff(np.r_[a, 10.0])),
                       np.sort(np.diff(np.r_[b, 10.0])))
    assert a[0] == 0.0 and a[-1] < 10.0


def test_poisson_counts_what_was_never_answered():
    clk = Clock()

    class Stuck(FakeServer):
        def step(self):           # serves nothing and takes a whole second
            self.clock.t += 1.0
            return 0

    srv = Stuck(clk)
    old = loadgen.DRAIN_S
    loadgen.DRAIN_S = 3.0
    try:
        win = loadgen.run_poisson(srv, QUERIES, {"rate_qps": 2.0}, 5.0,
                                  rng=np.random.default_rng(0), clock=clk,
                                  sleep=clk.sleep)
    finally:
        loadgen.DRAIN_S = old
    assert len(win.qid) == 10 and win.unanswered() == 10
    assert np.all(np.isinf(win.latencies()))
    assert all(math.isnan(t) for t in win.t_done)

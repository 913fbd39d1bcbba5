"""The load generator: one thread that offers a traffic mix to a served
store and times every request from the client's side.

A mix is a JSON file under ``bench/traffic/`` read by ``load_mix``; its
``kind`` picks the loop:

* ``closed``: ``outstanding`` searches are in flight at all times; each
  answer is replaced at once by the next query of the test set (cycled).
  A request is timed from its issue to the moment its indices are on the
  host.
* ``poisson``: open loop.  ``round(rate_qps * seconds)`` searches fall due
  in the window at exponential gaps; every seed gets the same multiset of
  gaps (the exponential's quantiles), in an order of its own.  A request is
  timed from its due time, so a stall counts against every request that
  waits behind it, and ``submit_late`` records how far behind its due time
  the generator submitted it.

The window opens at the first timed request and closes at the end of the
first step that ends at or after ``seconds`` have passed.  Requests still
in flight are then answered (for at most ``DRAIN_S`` more seconds) and
count towards the latency tail, but not towards the window's throughput.
"""
from __future__ import annotations

import json
import math
import os
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

DRAIN_S = 60.0
KINDS = ("closed", "poisson")


def load_mix(name: str, root: str) -> dict:
    path = os.path.join(root, "bench", "traffic", name + ".json")
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"traffic {name!r}: kind must be one of {KINDS}")
    return mix


def arrival_offsets(rate_qps: float, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Due times (s from the window's start) of an open-loop Poisson mix:
    the exponential's n quantiles as gaps, shuffled by ``rng``, scaled to
    fill the window exactly."""
    n = max(1, int(round(rate_qps * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u))
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


@dataclass
class Window:
    """What one window produced, per request and per step."""
    kind: str
    t0: float = 0.0
    t_close: float = 0.0
    qid: List[int] = field(default_factory=list)       # test-set row
    t_due: List[float] = field(default_factory=list)   # due or issue time
    t_submit: List[float] = field(default_factory=list)
    t_done: List[float] = field(default_factory=list)  # nan: unanswered
    answers: List[Optional[np.ndarray]] = field(default_factory=list)
    steps: List[tuple] = field(default_factory=list)   # (start, end, n)

    @property
    def seconds(self) -> float:
        return self.t_close - self.t0

    def latencies(self) -> np.ndarray:
        """Seconds from due (or issue) to answer; inf where unanswered."""
        done = np.asarray(self.t_done, float)
        lat = done - np.asarray(self.t_due, float)
        return np.where(np.isnan(done), np.inf, lat)

    def answered_in_window(self) -> int:
        done = np.asarray(self.t_done, float)
        return int(np.sum(done <= self.t_close))

    def unanswered(self) -> int:
        return int(np.sum(np.isnan(np.asarray(self.t_done, float))))

    def window_steps(self) -> List[tuple]:
        return [s for s in self.steps if s[1] <= self.t_close]


class _Client:
    """Submits test-set rows and files each answer under its request."""

    def __init__(self, srv, queries: np.ndarray, win: Window,
                 clock: Callable[[], float], annotate):
        self.srv, self.queries, self.win = srv, queries, win
        self.clock, self.annotate = clock, annotate
        self.pending = {}            # server rid -> request number

    def submit(self, qid: int, due: float) -> None:
        req = self.srv.submit(self.queries[qid % len(self.queries)])
        self.pending[req.rid] = len(self.win.qid)
        self.record(qid, due, self.clock())

    def record(self, qid: int, due: float, t_submit: float) -> None:
        w = self.win
        w.qid.append(qid % len(self.queries))
        w.t_due.append(due)
        w.t_submit.append(t_submit)
        w.t_done.append(math.nan)
        w.answers.append(None)

    def step(self) -> int:
        with self.annotate("bench.step"):
            t = self.clock()
            self.srv.step()
            now = self.clock()
        n = 0
        with self.annotate("bench.collect"):
            for req in self.srv.finished:
                i = self.pending.pop(req.rid, None)
                req.mask = None          # the (K,) row view pins the
                if i is None:            # step's whole (Q, K) host mask
                    continue
                self.win.t_done[i] = now
                self.win.answers[i] = np.asarray(req.indices)
                n += 1
            self.srv.finished.clear()
        self.win.steps.append((t, now, n))
        return n


def run_closed(srv, queries, mix, seconds, *, clock=time.perf_counter,
               annotate=None, marks=None) -> Window:
    annotate = annotate or _no_span
    opened, closed = marks or (_nothing, _nothing)
    win = Window("closed")
    c = _Client(srv, queries, win, clock, annotate)
    nxt = 0
    opened()
    win.t0 = t0 = clock()
    with annotate("bench.submit"):
        for _ in range(mix["outstanding"]):
            c.submit(nxt, t0)
            nxt += 1
    while clock() < t0 + seconds:
        c.step()
        with annotate("bench.submit"):
            while len(c.pending) < mix["outstanding"]:
                c.submit(nxt, clock())
                nxt += 1
    win.t_close = clock()
    closed()
    _drain(c, clock)
    return win


def run_poisson(srv, queries, mix, seconds, *, rng, clock=time.perf_counter,
                sleep=time.sleep, annotate=None, marks=None) -> Window:
    annotate = annotate or _no_span
    opened, closed = marks or (_nothing, _nothing)
    win = Window("poisson")
    c = _Client(srv, queries, win, clock, annotate)
    due = arrival_offsets(mix["rate_qps"], seconds, rng)
    n, i = len(due), 0
    win.t0 = t0 = clock()
    t_end = t0 + seconds
    close = None
    opened()
    while i < n or c.pending:
        now = clock()
        if close is None and now >= t_end:
            close = now
            closed()
        if now > t_end + DRAIN_S:
            break
        if i < n and t0 + due[i] <= now:
            with annotate("bench.submit"):
                while i < n and t0 + due[i] <= now:
                    c.submit(i, t0 + due[i])
                    i += 1
        if c.pending:
            c.step()
            if close is None and clock() >= t_end:
                close = clock()
                closed()
        elif i < n:
            with annotate("bench.idle"):
                sleep(max(0.0, t0 + due[i] - clock()))
    if close is None:
        closed()
    win.t_close = close if close is not None else max(clock(), t_end)
    for j in range(i, n):            # due in the window, never submitted
        c.record(j, t0 + due[j], math.nan)
    return win


def _drain(c: _Client, clock) -> None:
    deadline = clock() + DRAIN_S
    while c.pending and clock() < deadline:
        c.step()


def _nothing() -> None:
    pass


class _no_span:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def run(srv, queries, mix, seconds, *, rng, clock=time.perf_counter,
        sleep=time.sleep, annotate=None, marks=None) -> Window:
    """Offer ``mix`` to ``srv`` for ``seconds``; ``marks`` are two calls
    made as the window opens and as it closes."""
    if mix["kind"] == "closed":
        return run_closed(srv, queries, mix, seconds, clock=clock,
                          annotate=annotate, marks=marks)
    return run_poisson(srv, queries, mix, seconds, rng=rng, clock=clock,
                       sleep=sleep, annotate=annotate, marks=marks)

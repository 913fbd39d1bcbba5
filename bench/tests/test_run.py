"""Whole runs at a tiny size on the CPU: the refusal to report from it,
a workload that exists only as files of a new checkout, and the verdict
``correct`` on a sound and on a broken timed path."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness

from .conftest import ROOT, make_checkout

CLOSED = {"kind": "closed", "outstanding": 32}
POISSON = {"kind": "poisson", "rate_qps": 60.0}


def test_run_py_refuses_the_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "sift1m-l2.closed", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no accelerator" in p.stderr


def test_run_py_fails_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ cannot run."""
    make_checkout(str(tmp_path), {"t.closed": ("sift1m-mcam3-l2-d2d",
                                               CLOSED)})
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "t.closed", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=str(tmp_path), env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_harness_refuses_the_cpu():
    with pytest.raises(harness.NoChip):
        harness.check_devices(1)


def test_a_workload_added_as_files_only_runs(tmp_path, jax_cache):
    root = make_checkout(str(tmp_path), {
        "t-l2.closed": ("sift1m-mcam3-l2-d2d", CLOSED),
        "t-dot.poisson": ("glove100-mcam3-dot", POISSON)})
    for name, kind in (("t-l2.closed", "closed"),
                       ("t-dot.poisson", "poisson")):
        cell = harness.load_cell(name, root)
        assert cell.root == root and cell.mix["kind"] == kind
        res = harness.run(cell, 2**31 + 7, 1.5, False, time.perf_counter(),
                          allow_cpu=True, log=lambda s: None)
        assert res["correct"] is True, res["checks"]
        assert res["failed"] == 0 and res["attempted"] > 0
        m = res["metrics"]
        assert set(m) == {"search_qps", "peak_hbm_gib", "setup_s"} | (
            {"search_p95_ms"} if kind == "poisson" else set())
        assert m["search_qps"]["value"] > 0
        assert list(res)[-1] == "checks"
        assert res["checks"]["answer_gap"]["value"] == 0.0
        json.dumps(res)


def test_trace_run_reports_per_layer_metrics_it_can_read(tmp_path,
                                                        jax_cache):
    root = make_checkout(str(tmp_path), {
        "t.poisson": ("sift1m-mcam3-l2-d2d", POISSON)})
    cell = harness.load_cell("t.poisson", root)
    lines = []
    res = harness.run(cell, 3, 1.0, True, time.perf_counter(),
                      allow_cpu=True, log=lines.append)
    assert res["correct"] is True
    # each phase is logged as it ends, then all in one line, then the checks
    ended = [s.split()[1] for s in lines if s.startswith("phase ")]
    assert ended == ["setup", "window", "drain", "stop_trace", "reference",
                     "trace_load", "reduce"]
    summary = next(i for i, s in enumerate(lines) if s.startswith("phases"))
    assert all(name in lines[summary] for name in ended)
    assert [s.split()[1] for s in lines[summary + 1:]] == list(res["checks"])
    # the CPU trace has no device plane: only host-clock metrics read
    assert set(res["metrics"]) == {"step_ms.closed"} or \
        set(res["metrics"]) <= {"step_ms.closed", "step_ms.open",
                                "submit_late_ms.open"}
    assert "busy_s" in res["device"] and "window_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


class Faulty:
    """The program's simulator with the timed path broken underneath."""

    def __init__(self, sim, fault):
        self.sim, self.fault, self.last = sim, fault, None

    def __getattr__(self, name):
        return getattr(self.sim, name)

    def query(self, state, queries, key=None, valid_count=None):
        idx, mask = self.sim.query(state, queries, key=key,
                                   valid_count=valid_count)
        idx = np.array(idx)
        if self.fault == "altered":        # one answer changed where made
            idx[:, -1] = (idx[:, -1] + 1) % state.spec.K
        elif self.fault == "half_batch":   # second half never searched
            h = idx.shape[0] // 2
            idx[h:] = idx[:h]
        elif self.fault == "stale":        # the step returns the last one's
            prev, self.last = self.last, idx.copy()
            if prev is not None:
                idx = prev
        return idx, mask


@pytest.mark.parametrize("fault", ["altered", "half_batch", "stale"])
@pytest.mark.parametrize("base", ["sift1m-mcam3-l2-d2d",
                                  "glove100-mcam3-dot"])
def test_a_broken_timed_path_reads_not_correct(tmp_path, jax_cache,
                                               monkeypatch, fault, base):
    root = make_checkout(str(tmp_path), {"t.closed": (base, CLOSED)})
    cell = harness.load_cell("t.closed", root)
    build = harness.build

    def broken(cell, seed):
        srv, queries = build(cell, seed)
        srv.sim = Faulty(srv.sim, fault)
        return srv, queries

    monkeypatch.setattr(harness, "build", broken)
    res = harness.run(cell, 11, 1.0, False, time.perf_counter(),
                      allow_cpu=True, log=lambda s: None)
    assert res["correct"] is False
    assert res["checks"]["answer_gap"]["value"] > \
        res["checks"]["answer_gap"]["limit"]

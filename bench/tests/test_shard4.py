"""The four-chip cell at a tiny size on four CPU devices: whole runs of the
sharded store against the kNN reference (sound, and with the cross-chip
exchange broken), the scopes a traced run's program carries, and the
readers of the cell's per-layer metrics.

The runs go in a subprocess that asks XLA for four host devices before
JAX starts, so the device count does not depend on what this process has
already initialised."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench import work
from bench.program_trace import ProgramTrace
from bench.trace import Event

from .conftest import ROOT, make_checkout

CONFIG = "bigann4m-mcam3-l2-sharded"
CELL = "t.shard4"
PLANES = [f"/device:TPU:{i}" for i in range(4)]

_RUNS = r'''
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
root, cache, repo = sys.argv[1:4]
sys.path[:0] = [repo, os.path.join(repo, "src")]
import jax
import jax.numpy as jnp
from bench import harness, program_trace as pt

harness.enable_cache(cache)
cell = harness.load_cell("t.shard4", root)


def run(seed, trace):
    return harness.run(cell, seed, 1.0, trace, time.perf_counter(),
                       allow_cpu=True, log=lambda s: None)


out = {"devices": len(jax.devices()), "sound": run(2**31 + 7, False),
       "traced": run(5, True)}
path = pt.newest(os.path.join(pt.TRACE_ROOT, "t.shard4"))
out["op_names"] = {m: names for m, names in pt.op_names(path).items()
                   if "_query_jit" in m}


def own_candidates_only(x, axis_name, *, axis=0, tiled=False, **kw):
    """An exchange that returns each chip's own candidates alone."""
    return x if tiled else jnp.expand_dims(x, axis)


jax.lax.all_gather = own_candidates_only
out["broken"] = run(11, False)
print("RESULT " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("shard4"))
    make_checkout(root, {CELL: (CONFIG, {"kind": "closed",
                                         "outstanding": 32})})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        w["chips"] = 4
    with open(path, "w") as f:
        json.dump(bench, f)
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, "-c", _RUNS, root,
         str(tmp_path_factory.mktemp("jaxcache")), ROOT],
        env=env, capture_output=True, text=True, timeout=600)
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    assert p.returncode == 0 and lines, (p.stdout[-2000:], p.stderr[-4000:])
    return json.loads(lines[-1][len("RESULT "):])


def test_sharded_cell_reads_correct_against_the_reference(runs):
    assert runs["devices"] == 4
    for name in ("sound", "traced"):
        res = runs[name]
        assert res["correct"] is True, res["checks"]
        assert res["failed"] == 0 and res["attempted"] > 0
        assert res["device"]["count"] == 4
        assert res["checks"]["answer_gap"]["value"] == 0.0
    assert runs["sound"]["metrics"]["search_qps"]["value"] > 0


def test_a_broken_exchange_reads_not_correct(runs):
    """Each chip answering from its own top-k candidates alone."""
    res = runs["broken"]
    assert res["correct"] is False
    assert res["checks"]["answer_gap"]["value"] > \
        res["checks"]["answer_gap"]["limit"]


def _scoped(runs, pattern: str):
    """(module, instruction) pairs whose scope path matches ``pattern``."""
    import re
    rx = re.compile(pattern)
    return [(m, ins) for m, names in runs["op_names"].items()
            for ins, scope in names.items()
            if "shard_map" in scope and rx.search(scope)]


def _ctx(runs, ops, **kw):
    return SimpleNamespace(ops={p: list(ops) for p in PLANES}, chips=4,
                           n_steps=2, program_trace=ProgramTrace(
                               names=runs["op_names"]), **kw)


@pytest.mark.parametrize("metric", ["exchange_ms", "merge_ms"])
def test_the_scoped_readers_find_the_traced_programs_ops(runs, metric):
    """The traced run's program names its cross-chip merge and its local
    merge under the scopes the readers look for; device ops named after
    those instructions give readings, each per chip and per step."""
    import importlib
    reader = importlib.import_module(f"bench.metrics.{metric}")
    pattern = reader.EXCHANGE if metric == "exchange_ms" else reader.MERGE
    found = _scoped(runs, pattern)
    assert found, metric
    ops = [Event(f"%{ins} = f32[8] fusion(x)", 10 * i, 10 * i + 4, mod)
           for i, (mod, ins) in enumerate(found)]
    assert reader.read(_ctx(runs, ops)) == pytest.approx(
        4 * len(found) / 1e6 / 2)
    other = [Event(f"%{ins} = f32[8] fusion(x)", 0, 4, "jit_other(1)")
             for _, ins in found]
    assert reader.read(_ctx(runs, other)) is None


def test_exchange_ms_reads_nothing_from_a_program_without_the_scope(runs):
    from bench.metrics import exchange_ms
    unscoped = {m: {ins: scope.replace("cam.shard.exchange", "")
                    for ins, scope in names.items()}
                for m, names in runs["op_names"].items()}
    (mod, ins), = _scoped(runs, exchange_ms.EXCHANGE)[:1]
    ctx = _ctx(runs, [Event(f"%{ins} = f32[8] fusion(x)", 0, 4, mod)])
    ctx.program_trace = ProgramTrace(names=unscoped)
    assert exchange_ms.read(ctx) is None
    ctx.program_trace = None
    assert exchange_ms.read(ctx) is None


def _config():
    with open(os.path.join(ROOT, "bench", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_shard_kernel_roofline_counts_one_chips_share():
    """Least time of a chip's K / 4 rows over the kernel's time per chip
    and step: compute bound at the BIGANN-4M shape, 2 * 256 * 1M * 128
    int8 ops at the v5e's 393 TOP/s."""
    from bench import trace as tr
    from bench.metrics import cam_search_roofline, shard_kernel_roofline
    total = work.search_work(_config(), 256)
    share = shard_kernel_roofline.chip_work(total, 4)
    assert share["ops"] == pytest.approx(total["ops"] / 4)
    assert share["bytes"] == pytest.approx(
        total["K"] / 4 * 128 * total["w"] + 256 * 128 * 4 + 256 * 10 * 8)
    peaks = work.load_peaks("TPU v5 lite")
    least, bound = work.least_time(share, peaks)
    assert bound == "compute"
    assert least == pytest.approx(2 * 256 * 1e6 * 128 / 393e12)
    kernel = "%cam_search_fused_pallas.1 = (f32[7813,1,256,128]) custom-call"
    ms = 4.0                                        # per chip and step
    ops = [Event(kernel, 0, ms * 1e6), Event(kernel, 1e9, 1e9 + ms * 1e6)]
    ctx = SimpleNamespace(ops={p: list(ops) for p in PLANES}, chips=4,
                          n_steps=2, tr=tr, work=total,
                          device_kind="TPU v5 lite")
    assert shard_kernel_roofline.read(ctx) == pytest.approx(
        100 * least / (ms / 1e3))
    one = SimpleNamespace(ops={PLANES[0]: ops}, chips=1, n_steps=2, tr=tr,
                          work=total, device_kind="TPU v5 lite")
    assert shard_kernel_roofline.read(one) == pytest.approx(
        cam_search_roofline.read(one))
    ctx.ops = {p: [] for p in PLANES}
    assert shard_kernel_roofline.read(ctx) is None

"""The program's own marks in a trace (``bench/program_trace.py``) and the
readers built on them, on synthetic events and on traces recorded on the
CPU."""
import glob
import os
import time
from types import SimpleNamespace

import pytest

from bench import harness
from bench import program_trace as pt
from bench import trace as tr
from bench.program_trace import ProgramTrace, Span
from bench.trace import Event

from .conftest import make_checkout

PLANE = "/device:TPU:0"


def _xplane(d) -> str:
    path, = glob.glob(os.path.join(str(d), "**", "*.xplane.pb"),
                      recursive=True)
    return path


def test_fetch_ms_reads_the_fetch_spans_clipped_to_the_window():
    from bench.metrics import fetch_ms
    host = [Event("bench.window", 0, 100, depth=0),
            Event("cam.serve.step", -30, 40, depth=2),
            Event("cam.serve.fetch", -10, 30, depth=3),   # 30 in the window
            Event("cam.serve.step", 40, 90, depth=2),
            Event("cam.serve.fetch", 60, 90, depth=3),
            Event("cam.serve.fetch", 95, 120, depth=3),   # 5 in the window
            Event("np.asarray(jax.Array)", 60, 90, depth=4)]
    ctx = SimpleNamespace(trace=tr.Trace(host=host), lo=0, hi=100,
                          n_steps=2, ops={PLANE: [Event("op", 0, 1)]})
    assert fetch_ms.read(ctx) == pytest.approx((30 + 30 + 5) / 1e6 / 2)
    assert pt.host_ms_per_step(ctx, "cam.serve.wait") is None
    ctx.trace = tr.Trace(host=[Event("cam.serve.fetch", 200, 300)])
    assert fetch_ms.read(ctx) is None        # none in the window
    ctx.trace, ctx.ops = tr.Trace(host=host), {}
    assert fetch_ms.read(ctx) is None        # no device to copy from


def test_merge_ms_reads_the_ops_under_the_merge_scope():
    from bench.metrics import merge_ms
    mod = "jit__query_jit(3)"
    scopes = {
        "cam_search_fused_pallas.1": "jit(_query_jit)/cam.search/jit(cam_"
        "search_fused_pallas)/cam.kernel/cam_search_fused_pallas/"
        "pallas_call",
        "custom-call": "jit(_query_jit)/cam.merge/top_k",
        "select_reduce_fusion": "jit(_query_jit)/cam.merge/reduce_max",
        "copy.5": "",
        "fusion.2": "jit(_query_jit)/cam.merged/mul",      # another name
        "fusion.3": "jit(_query_jit)/vmap(cam.merge)/top_k"}
    ops = [Event("%cam_search_fused_pallas.1 = f32[8] custom-call(x)", 0,
                 4, mod),
           Event("%custom-call = f32[8] custom-call(y)", 4, 7, mod),
           Event("%select_reduce_fusion = f32[8] fusion(z)", 6, 9, mod),
           Event("%copy.5 = f32[8] copy(z)", 9, 10, mod),
           Event("%fusion.2 = f32[8] fusion(w)", 10, 11, mod),
           Event("%fusion.3 = f32[8] fusion(v)", 12, 14, mod),
           Event("%fusion.3 = f32[8] fusion(v)", 14, 15, "jit_other(1)")]
    ctx = SimpleNamespace(ops={PLANE: ops}, chips=1, n_steps=1,
                          program_trace=ProgramTrace(names={mod: scopes}))
    # the union of [4, 9] and [12, 14]
    assert merge_ms.read(ctx) == pytest.approx(7 / 1e6)
    assert pt.scoped_ms_per_step(ctx, r"(^|/)cam\.kernel(/|$)") \
        == pytest.approx(4 / 1e6)
    ctx.program_trace = ProgramTrace(names={mod: {k: "" for k in scopes}})
    assert merge_ms.read(ctx) is None         # a program with no scopes
    ctx.program_trace = None
    assert merge_ms.read(ctx) is None         # no trace of this run


def test_fetch_mib_reads_the_fetch_spans_byte_stat():
    from bench.metrics import fetch_mib
    mib = 2 ** 20
    spans = [Span("bench.window", 0, 100),
             Span("cam.serve.fetch", -10, 5, {"fetch_bytes": 9 * mib}),
             Span("cam.serve.fetch", 10, 20, {"fetch_bytes": 3 * mib}),
             Span("cam.serve.fetch", 50, 60, {"fetch_bytes": 5 * mib}),
             Span("cam.serve.step", 50, 60, {"step_num": 4}),
             Span("cam.serve.fetch", 100, 110, {"fetch_bytes": 9 * mib})]
    ctx = SimpleNamespace(lo=0, hi=100, ops={PLANE: [Event("op", 0, 1)]},
                          program_trace=ProgramTrace(spans=spans))
    assert fetch_mib.read(ctx) == pytest.approx(4.0)
    ctx.program_trace = ProgramTrace(spans=[
        Span("cam.serve.fetch", 10, 20)])            # an older program
    assert fetch_mib.read(ctx) is None
    ctx.program_trace = None
    assert fetch_mib.read(ctx) is None
    ctx.program_trace, ctx.ops = ProgramTrace(spans=spans), {}
    assert fetch_mib.read(ctx) is None        # no device to copy from


def test_of_takes_the_newest_trace_only_where_its_window_is_the_runs(
        tmp_path):
    import jax
    import jax.numpy as jnp
    for d in ("old", "new"):
        jax.profiler.start_trace(str(tmp_path / d))
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("cam.serve.fetch",
                                              fetch_bytes=7):
                jnp.ones(4).block_until_ready()
        jax.profiler.stop_trace()
        time.sleep(0.05)
    spans = pt.host_spans(_xplane(tmp_path / "new"))
    win = next(s for s in spans if s.name == "bench.window")
    fetch = next(s for s in spans if s.name == "cam.serve.fetch")
    assert fetch.stats == {"fetch_bytes": 7}
    assert win.start <= fetch.start and fetch.end <= win.end
    ctx = SimpleNamespace(lo=win.start)
    got = pt.of(ctx, str(tmp_path))
    assert got is not None and got.spans == spans
    assert pt.of(ctx, "/nonexistent") is got          # loaded once
    assert pt.of(SimpleNamespace(lo=win.start - 1), str(tmp_path)) is None
    assert pt.of(SimpleNamespace(lo=0), str(tmp_path / "none")) is None


def test_op_names_read_the_scopes_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("cam.merge"):
            return jax.lax.top_k(x * 2.0, 3)[0]

    x = jnp.ones((8, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    names = pt.op_names(_xplane(tmp_path))
    mod = next(m for m in names if m.startswith("jit_f("))
    scopes = set(names[mod].values())
    assert any(s.startswith("jit(f)/cam.merge/") for s in scopes), scopes
    got = ProgramTrace(names=names)
    assert got.scope_of(Event("%nothing.1 = f32[] x", 0, 1, mod)) == ""
    ins, scope = next((k, v) for k, v in names[mod].items()
                      if "cam.merge" in v)
    assert got.scope_of(Event(f"%{ins} = f32[8,3] x", 0, 1, mod)) == scope


def test_a_traced_run_records_each_steps_fetched_bytes(tmp_path,
                                                       jax_cache):
    """On a whole traced run on the CPU: every step's fetch span in the
    window carries the bytes of the whole padded (Q, K) f32 mask and the
    (Q, k) ids."""
    root = make_checkout(str(tmp_path), {
        "t.closed": ("sift1m-mcam3-l2-d2d", {"kind": "closed",
                                             "outstanding": 32})})
    cell = harness.load_cell("t.closed", root)
    res = harness.run(cell, 5, 1.0, True, time.perf_counter(),
                      allow_cpu=True, log=lambda s: None)
    assert res["correct"] is True
    spans = pt.host_spans(pt.newest(os.path.join(pt.TRACE_ROOT,
                                                 "t.closed")))
    win = next(s for s in spans if s.name == "bench.window")
    ctx = SimpleNamespace(lo=win.start, hi=win.end,
                          program_trace=ProgramTrace(spans=spans))
    cfg = cell.config
    q, k = cfg["cam"]["sim"]["serve_batch"], cfg["cam"]["app"]["match_param"]
    rows = cfg["cam"]["circuit"]["rows"]
    padded = -(-cfg["rows"] // rows) * rows
    assert pt.span_stat_mean(ctx, "cam.serve.fetch", "fetch_bytes") \
        == q * padded * 4 + q * k * 4

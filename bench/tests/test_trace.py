"""The trace reduction on synthetic events and on a trace recorded on the
CPU."""
from types import SimpleNamespace

import pytest

from bench import trace as tr
from bench.trace import Event


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 7), (0, 2), (1, 3), (9, 9), (7, 8)]) == [
        (0, 3), (5, 8)]


def test_busy_and_gaps_and_idle_share():
    ops = [Event("a", 0, 10), Event("b", 5, 20), Event("c", 30, 40)]
    assert tr.busy_ns(ops) == 30
    assert tr.gaps(ops, -5, 50) == [(-5, 0), (20, 30), (40, 50)]
    ctx = SimpleNamespace(ops={"/device:TPU:0": tr.clip(ops, -5, 50)},
                          lo=-5, hi=50, chips=1, tr=tr)
    from bench.metrics import device_idle
    assert device_idle.read(ctx) == pytest.approx(100 * (1 - 30 / 55))


def test_clip_cuts_to_the_window():
    got = tr.clip([Event("a", 0, 10), Event("b", 12, 15)], 5, 13)
    assert [(e.name, e.start, e.end) for e in got] == [("a", 5, 10),
                                                       ("b", 12, 13)]


def test_kernel_and_module_matching_per_step():
    k = "%cam_search_fused_pallas.1 = (f32[8,1,256,128]) custom-call(x)"
    ops = [Event(k, 0, 4, module="jit__query_jit(1)"),
           Event("%fusion.1 = f32[8] fusion(%cam_search_fused_pallas.1)",
                 4, 6, module="jit__query_jit(1)"),
           Event("%copy.2 = f32[8] copy(x)", 8, 9, module="jit_other"),
           Event(k, 10, 14, module="jit__query_jit(1)")]
    ctx = SimpleNamespace(ops={"/device:TPU:0": ops}, chips=1, n_steps=2,
                          tr=tr)
    from bench.metrics import kernel_ms, search_device_ms
    assert kernel_ms.read(ctx) == pytest.approx(8 / 1e6 / 2)
    assert search_device_ms.read(ctx) == pytest.approx(10 / 1e6 / 2)
    ctx.ops = {"/device:TPU:0": [Event("%copy.2 = f32[8] copy(x)", 8, 9)]}
    assert kernel_ms.read(ctx) is None       # nothing to read: no number


def test_two_chips_average():
    ops = {"/device:TPU:0": [Event("x", 0, 10)],
           "/device:TPU:1": [Event("x", 0, 30)]}
    ctx = SimpleNamespace(ops=ops, chips=2, n_steps=1, lo=0, hi=40, tr=tr)
    assert tr.per_step_ms(ctx, "x") == pytest.approx(20 / 1e6)
    from bench.metrics import device_idle
    assert device_idle.read(ctx) == pytest.approx(50.0)


def test_host_activity_names_the_deepest_covering_span():
    host = [Event("bench.window", 0, 100, depth=0),
            Event("bench.step", 10, 60, depth=1),
            Event("TransferFromDevice", 30, 58, depth=2),
            Event("bench.collect", 60, 62, depth=1)]
    assert tr.host_activity(host, 31, 57, skip=("bench.window",)) == \
        "TransferFromDevice"
    assert tr.host_activity(host, 12, 20, skip=("bench.window",)) == \
        "bench.step"
    assert tr.host_activity(host, 200, 210) == "(no host span)"


def test_roofline_reads_least_time_over_device_time():
    from bench import work
    from bench.metrics import search_roofline
    w = {"ops": 0.0, "bytes": 819e9 * 1e-3, "peak_ops": "bf16_flops_per_s"}
    ops = [Event("k", 0, 4e6, module="jit__query_jit(7)")]   # 4 ms a step
    ctx = SimpleNamespace(ops={"/device:TPU:0": ops}, chips=1, n_steps=1,
                          tr=tr, work=w, device_kind="TPU v5 lite")
    assert search_roofline.read(ctx) == pytest.approx(25.0)
    assert work.least_time(w, work.load_peaks("TPU v5 lite"))[1] == \
        "memory"


def test_load_reads_host_spans_of_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=tr.capture_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    win, step = t.span("bench.window"), t.span("bench.step")
    assert win is not None and step is not None
    assert win.start <= step.start and step.end <= win.end
    assert step.depth > win.depth
    assert t.ops == {}                 # the CPU has no device plane

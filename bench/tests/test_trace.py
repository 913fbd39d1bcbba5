"""The trace reduction on synthetic events and on a trace recorded on the
CPU."""
import time
from types import SimpleNamespace

import numpy as np
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


# ------------------------------------------------ gap attribution, one sweep
def _oracle_host_activity(host, s, t, skip=()):
    """The per-gap scan over every host event that the sweep replaces."""
    best, key = "(no host span)", (-1, -1.0)
    for e in host:
        if e.name in skip:
            continue
        ov = min(e.end, t) - max(e.start, s)
        if ov <= 0:
            continue
        k = (e.depth, ov) if ov >= 0.5 * (t - s) else (-1, ov)
        if k > key:
            best, key = e.name, k
    return best


NAMES = ("bench.window", "bench.step", "cam.serve.fetch", "cam.serve.wait",
         "np.asarray", "TransferFromDevice", "ThreadpoolListener")


def _nest(rng, lo, hi, depth, out):
    """Spans nested inside [lo, hi] down to depth 3, on integer times (so
    overlaps tie), named from a small pool (so names repeat)."""
    t = lo
    while depth < 4 and t < hi:
        s = int(rng.integers(t, hi + 1))
        e = int(rng.integers(s, hi + 1))
        out.append(Event(str(rng.choice(NAMES)), s, e, depth=depth))
        if e - s > 2 and rng.random() < 0.7:
            _nest(rng, s, e, depth + 1, out)
        t = e + int(rng.integers(0, 6))


def _random_trace(rng):
    lo, hi = 0, int(rng.integers(20, 400))
    host = [Event("bench.window", lo, hi, depth=0)]
    for _ in range(int(rng.integers(1, 5))):        # threads
        _nest(rng, lo - int(rng.integers(0, 10)),
              hi + int(rng.integers(0, 10)), int(rng.integers(0, 2)), host)
    host = [host[i] for i in rng.permutation(len(host))]
    if rng.random() < 0.5:                           # device idle gaps
        ops = []
        for _ in range(int(rng.integers(0, 30))):
            s = int(rng.integers(lo - 5, hi + 5))
            ops.append(Event("op", s, s + int(rng.integers(1, 20))))
        gaps = tr.gaps(ops, lo, hi)
    else:                          # any sorted, disjoint gaps, edges too
        cuts = np.unique(rng.integers(lo - 20, hi + 20, 2 * int(
            rng.integers(1, 25))))
        gaps = [(float(a), float(b)) for a, b in zip(cuts[::2], cuts[1::2])]
    skip = tuple(rng.choice(NAMES, int(rng.integers(0, 3)), replace=False))
    return host, gaps, skip


def test_sweep_names_every_gap_as_the_per_gap_scan_does():
    rng = np.random.default_rng(20261020)
    for _ in range(300):
        host, gaps, skip = _random_trace(rng)
        want = [_oracle_host_activity(host, s, t, skip) for s, t in gaps]
        assert tr.attribute_gaps(host, gaps, skip) == want
        assert [tr.host_activity(host, s, t, skip) for s, t in gaps] == want


def test_sweep_breaks_ties_by_the_events_order_in_the_trace():
    """Equal keys: the event earlier in ``host`` wins, though it starts
    later."""
    host = [Event("later", 10, 30, depth=1), Event("earlier", 0, 20, depth=1),
            Event("partial", 0, 12, depth=2)]
    assert _oracle_host_activity(host, 10, 20) == "later"
    assert tr.attribute_gaps(host, [(10, 20), (40, 50)]) == [
        "later", "(no host span)"]
    assert tr.attribute_gaps(host[1:], [(10, 20)]) == ["earlier"]


# One served step as a TPU v5e trace records it: device idle gaps (G) and
# host events (H) a step, read from a traced sift1m-l2.closed run (13.16
# and 241.4 over 112 steps)
G_PER_STEP, H_PER_STEP = 13, 241


def _synthetic_trace(steps, seed=0):
    """``steps`` steps of 1 ms: a step span with H - 1 runtime events
    inside it on four threads, and G device idle gaps between ops."""
    rng = np.random.default_rng(seed)
    host, ops = [], []
    for k in range(steps):
        t0 = k * 1e6
        host.append(Event("bench.step", t0, t0 + 1e6, depth=0))
        s = np.sort(rng.uniform(t0, t0 + 1e6, H_PER_STEP - 1))
        d = rng.uniform(1e3, 5e4, H_PER_STEP - 1)
        host += [Event(NAMES[2 + i % 5], a, min(a + b, t0 + 1e6),
                       depth=1 + i % 3) for i, (a, b) in enumerate(zip(s, d))]
        edges = np.linspace(t0, t0 + 1e6, G_PER_STEP + 1)
        ops += [Event("op", a, a + 0.6 * (b - a))
                for a, b in zip(edges[:-1], edges[1:])]
    gaps = tr.gaps(ops, 0.0, steps * 1e6)
    return host, gaps


def _sweep_seconds(steps):
    host, gaps = _synthetic_trace(steps)
    t = time.perf_counter()
    names = tr.attribute_gaps(host, gaps, skip=("bench.window",))
    assert len(names) == len(gaps) >= G_PER_STEP * (steps - 1)
    return time.perf_counter() - t


def test_sweep_grows_linearly_with_the_steps():
    """2,000 steps (about what a 51 s window holds once the host fetch is
    gone) reduce in seconds; a quadratic reduction would take ~400x the
    time of 100 steps."""
    short = min(_sweep_seconds(100) for _ in range(3))
    long = min(_sweep_seconds(2000) for _ in range(2))
    assert long < 30.0
    assert long / short < 60.0

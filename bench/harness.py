"""One run of one benchmark cell: set-up, measured window, correctness.

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration file (``configs[].file``), its traffic mix
(``bench/traffic/<traffic>.json``), its data generator
(``bench/data/<config.data.generator>.py``), its reference
(``bench/refs/<config.reference>.py``) and each per-layer metric's reader
(``bench/metrics/<name before the first dot>.py``).  A cell, a mix or a
metric is added by adding files and entries; no code here names one.

A run: make the data on the device from the seed, write the store, warm
the one served step shape, then offer the mix for ``seconds`` through
``CAMSearchServer`` (``bench/loadgen.py``).  After the window the peak
device memory is read, the program's state is freed, and the reference
rebuilds the store from the same seed and checks every answer the client
received.  ``--trace 1`` records the window with
the profiler and reports the per-layer metrics instead of the end-to-end
ones.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Callable, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, ".cache")
OP_NAME_CHARS = 160       # device op names are HLO text: keep their head
WARM_STEPS = 2
GIB = float(1 << 30)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict              # the configuration file's contents
    mix: dict                 # the traffic file's contents
    end_to_end: List[dict]
    per_layer: List[dict]     # the metrics this cell reports
    root: str


def _load_file(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def plugin(root: str, kind: str, name: str):
    """``bench/<kind>/<name>.py`` of the checkout at ``root``."""
    path = os.path.join(root, "bench", kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} module {name!r} ({path})")
    return _load_file(path, f"bench_{kind}_{name.replace('-', '_')}")


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    from bench import loadgen
    mix = loadgen.load_mix(w["traffic"], root)
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer, root)


def seed_key(seed: int):
    """A PRNG key from any whole number (beyond 32 bits too)."""
    import jax
    k = jax.random.PRNGKey(seed % (1 << 31))
    return jax.random.fold_in(k, (seed >> 31) % (1 << 31))


def process_env() -> None:
    """Before JAX is imported: the program takes the benchmark's compile
    cache, at a fixed path inside the checkout, and the TPU runtime logs
    there too unless told otherwise (never to a fixed path outside)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE_DIR, "jax")
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(CACHE_DIR, "tpu_logs"))


def enable_cache(path: str = os.path.join(CACHE_DIR, "jax")) -> str:
    import jax
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_devices(chips: int, allow_cpu: bool = False):
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" and not allow_cpu:
        raise NoChip(f"JAX found no accelerator (platform "
                     f"{devs[0].platform!r}); refusing to report from it")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts the programs JAX builds: every compile-or-load, and the
    persistent cache's hits and misses among them."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.n = {"built": 0, "hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._built)
        jax.monitoring.register_event_listener(self._cache)

    def _built(self, event, duration, **kw):
        if event == self.BUILD:
            self.n["built"] += 1

    def _cache(self, event, **kw):
        if event == self.HIT:
            self.n["hits"] += 1
        elif event == self.MISS:
            self.n["misses"] += 1

    def since(self, before: dict) -> dict:
        return {k: v - before.get(k, 0) for k, v in self.n.items()}


def generate(config: dict, key, root: str = ROOT):
    """The configuration's data: (stored rows, test queries) on the
    device, from ``key``."""
    gen = config["data"]
    mod = plugin(root, "data", gen["generator"])
    params = {k: v for k, v in gen.items() if k != "generator"}
    return mod.generate(key, rows=config["rows"], dims=config["dims"],
                        queries=config["queries"], **params)


def _keys(seed: int):
    import jax
    key = seed_key(seed)
    return (jax.random.fold_in(key, 1), jax.random.fold_in(key, 2),
            jax.random.fold_in(key, 3))


def build(cell: Cell, seed: int):
    """Data, store and server of one run; returns (srv, host queries)."""
    import jax

    from repro.core import CAMASim, CAMConfig
    from repro.runtime.serve_loop import CAMSearchServer

    k_data, k_write, k_serve = _keys(seed)
    data, queries = generate(cell.config, k_data, cell.root)
    queries = np.asarray(queries)
    cam = CAMASim(CAMConfig.from_dict(cell.config["cam"]))
    state = cam.write(data, key=k_write)
    jax.block_until_ready(state.grid)
    del data
    srv = CAMSearchServer(cam, state, key=k_serve)
    return srv, queries


def warm(srv, queries: np.ndarray) -> None:
    """Serve ``WARM_STEPS`` full batches: compiles (or loads) the one
    search shape every step of the window uses, and the host copies."""
    for s in range(WARM_STEPS):
        for i in range(srv.batch):
            srv.submit(queries[(s * srv.batch + i) % len(queries)])
        while srv.queue:
            srv.step()
        for r in srv.finished:
            r.mask = None
        srv.finished.clear()


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def answered(win):
    """(distinct test-set rows, per-answer index into them, the answers
    (M, k)) of every request answered in the window and its drain."""
    done = [i for i, a in enumerate(win.answers) if a is not None]
    qids = np.asarray([win.qid[i] for i in done], np.int64)
    uniq, qidx = np.unique(qids, return_inverse=True)
    served = (np.stack([win.answers[i] for i in done]) if done
              else np.zeros((0, 1), np.int64))
    return uniq, qidx, served


def reference(cell: Cell, seed: int):
    """The configuration's reference store for ``seed``, rebuilt from the
    generated data, and the test queries on the device."""
    ref_mod = plugin(cell.root, "refs", cell.config["reference"])
    k_data, k_write, _ = _keys(seed)
    data, queries = generate(cell.config, k_data, cell.root)
    return ref_mod.Reference(cell.config, data, k_write), queries


def check(cell: Cell, seed: int, win) -> dict:
    """The reference's verdict on every answer the client received."""
    import jax.numpy as jnp
    t = time.perf_counter()
    ref, queries = reference(cell, seed)
    uniq, qidx, served = answered(win)
    g = (ref.gaps(jnp.take(queries, jnp.asarray(uniq), axis=0), qidx,
                  served) if len(qidx) else np.zeros(0))
    limit = cell.config["limits"]["answer_gap"]
    return {
        "answer_gap": {"value": float(g.max()) if g.size else 0.0,
                       "limit": limit},
        "unanswered": {"value": win.unanswered(), "limit": 0},
        "_answers": int(g.size), "_queries": int(len(uniq)),
        "_over_0": int(np.sum(g > 0)), "_over": int(np.sum(g > limit)),
        "_seconds": time.perf_counter() - t}


def per_layer_values(cell: Cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = plugin(cell.root, "metrics", m["name"].split(".")[0])
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def trace_context(cell: Cell, win, trace_dir: str, chips: int) -> tuple:
    """Context the per-layer readers see, plus the run's device record
    and breakdown from the trace."""
    import jax

    from bench import trace as tr
    from bench import work
    t = tr.load(trace_dir)
    span = t.span("bench.window")
    if span is None:
        raise RuntimeError("the trace holds no bench.window span")
    lo, hi = span.start, span.end
    planes = sorted(t.ops)[:chips]
    ops = {p: tr.clip(t.ops[p], lo, hi) for p in planes}
    n = max(1, len(win.window_steps()))
    busy = [tr.busy_ns(ops[p]) for p in planes]
    q = cell.config["cam"]["sim"]["serve_batch"]
    ctx = SimpleNamespace(
        window=win, trace=t, ops=ops, lo=lo, hi=hi, n_steps=n,
        chips=len(planes), kind=win.kind, tr=tr,
        work=work.search_work(cell.config, q),
        device_kind=jax.devices()[0].device_kind)
    window_s = (hi - lo) / 1e9
    device = {"busy_s": float(np.mean(busy)) / 1e9 if busy else 0.0,
              "window_s": window_s}
    all_ops = [e for p in planes for e in ops[p]]
    idle = {}
    for p in planes:
        for s, e in tr.gaps(ops[p], lo, hi):
            who = tr.host_activity(t.host, s, e, skip=("bench.window",))
            idle[who] = idle.get(who, 0.0) + (e - s) / 1e9 / len(planes)
    breakdown = {
        "device_ops": [[n[:OP_NAME_CHARS], v] for n, v in tr.top_by_name(
            all_ops, 10, scale=len(planes))],
        "idle_gaps": [[k, v] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:10]]}
    return ctx, device, breakdown


def window_span(annotate):
    """Enter/exit hooks that mark the measured window in the trace."""
    box = {}

    def open_():
        box["s"] = annotate("bench.window")
        box["s"].__enter__()

    def close():
        if "s" in box:
            box.pop("s").__exit__(None, None, None)
    return open_, close


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, *, allow_cpu: bool = False,
        log: Callable[[str], None] = lambda s: print(s, file=sys.stderr,
                                                      flush=True)) -> dict:
    import jax

    from bench import loadgen, work

    devices = check_devices(cell.chips, allow_cpu)
    log(f"cell {cell.name}: {devices[0].device_kind} x {len(jax.devices())}"
        f", seed {seed}, {seconds} s, trace {int(trace)}")
    compiles = CompileCounter()
    srv, queries = build(cell, seed)
    warm(srv, queries)
    trace_dir = os.path.join(CACHE_DIR, "trace", cell.name)
    annotate = jax.profiler.TraceAnnotation
    if trace:
        from bench import trace as tr
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=tr.capture_options())
    rng = np.random.default_rng([seed, 0x6C6F6164])
    setup_s = time.perf_counter() - t_start
    in_setup = dict(compiles.n)
    win = loadgen.run(srv, queries, cell.mix, seconds, rng=rng,
                      annotate=annotate, marks=window_span(annotate))
    in_window = compiles.since(in_setup)
    if trace:
        jax.profiler.stop_trace()
    peak = peak_bytes(devices)
    spec = srv.state.spec
    steps = np.asarray([e - s for s, e, _ in win.window_steps()]) * 1e3
    if steps.size:
        log("step ms: min {:.1f} median {:.1f} max {:.1f}; first {}".format(
            steps.min(), np.median(steps), steps.max(),
            [round(x, 1) for x in steps[:4]]))
    log(f"window {win.seconds:.3f} s, {len(win.window_steps())} steps, "
        f"{win.answered_in_window()} answered in it, {len(win.qid)} "
        f"requests; programs built in set-up {in_setup}, in the window and "
        f"its drain {in_window}; store nv={spec.nv} "
        f"nh={spec.nh} R={spec.R} C={spec.C}; peak {peak} B")
    del srv, spec
    gc.collect()
    checks = check(cell, seed, win)
    log("reference: {} answers to {} distinct queries checked in {:.2f} s;"
        " {} with a gap above 0, {} above the limit".format(*(
            checks.pop(k) for k in ("_answers", "_queries", "_seconds",
                                    "_over_0", "_over"))))
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    p95 = float(np.percentile(win.latencies(), 95))
    result = {"correct": bool(correct), "attempted": len(win.qid),
              "failed": win.unanswered(), "metrics": {}}
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": peak}
    if trace:
        ctx, extra, breakdown = trace_context(cell, win, trace_dir,
                                              cell.chips)
        device.update(extra)
        if ctx.ops:                       # a device trace: peaks exist
            least, bound = work.least_time(
                ctx.work, work.load_peaks(ctx.device_kind))
            log(f"least time of a step's work {least * 1e3:.4f} ms, "
                f"{bound} bound; device busy {extra['busy_s']:.3f} s of "
                f"{extra['window_s']:.3f} s")
        result["metrics"] = per_layer_values(cell, ctx)
        result["device"] = device
        result["breakdown"] = breakdown
    else:
        values = {
            "search_qps": win.answered_in_window() / win.seconds,
            "search_p95_ms": p95 * 1e3 if np.isfinite(p95) else None,
            "peak_hbm_gib": peak / GIB,
            "setup_s": setup_s}
        for m in cell.end_to_end:
            v = values.get(m["name"])
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv: Optional[list] = None, t_start: Optional[float] = None
         ) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    enable_cache()
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result))
    return 0

"""Benchmark entrypoint: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--devices N]

Prints ``name,us_per_call,derived`` CSV per benchmark.  ``--full`` runs the
larger sweeps (the default is sized for CI).  ``--devices N`` caps the
sharded weak-scaling sweep's device counts (default 4, 0 skips the
sweep); the sweep runs in this process on the devices JAX has, so on the
CPU set ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before
starting.  The dry-run roofline table is produced separately by
repro.launch.dryrun (512 fake devices) and read back here if present.
Any failing phase raises, so the run exits non-zero.

Every CSV row is also dumped to ``BENCH_kernels.json`` next to the repo
root, so successive PRs leave a machine-readable perf trajectory.
"""
from __future__ import annotations

import contextlib
import io
import json
import pathlib
import sys
import time

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / "BENCH_kernels.json"


def merge_bench_rows(rows: list, path: pathlib.Path = BENCH_JSON) -> list:
    """Replace-by-name merge into the JSON perf trajectory.

    A partial run (e.g. ``--devices 0``, or the standalone
    ``sharded_perf`` sweep) must refresh its own rows without destroying
    rows only other sweeps emit; a corrupt/truncated file self-heals."""
    existing = []
    if path.exists():
        try:
            existing = json.loads(path.read_text())
        except ValueError:
            existing = []
    fresh = {r["name"] for r in rows}
    merged = [r for r in existing if r.get("name") not in fresh] + rows
    path.write_text(json.dumps(merged, indent=1))
    return merged


def check_floors(rows: list) -> None:
    """Fail loudly when a row records a broken guarantee: any parity bit
    ``match=False``, a ``recall=`` that fell below the ``floor=`` the
    same row declares, a serve-loop ``p99_us=`` tail latency that blew
    through the row's ``floor_p99_us=`` ceiling, a kernel Q-sweep whose
    qps is not monotone nondecreasing (``qps_monotone=False``; the
    pipelined kernels' contract — the plain ``monotone=`` field some
    sharded rows record is informational, not floored), or a
    measured-vs-estimated drift ``est_ratio=`` above the ``ratio_ceil=``
    the row declares (the insert-rate estimate was once silently 8800x
    off).  Run in CI so a perf row can't silently regress from
    "bit-identical"/"recall cleared"/"SLO met" to "close enough"."""
    import re
    bad = []
    for r in rows:
        d = str(r.get("derived", ""))
        if re.search(r"\bmatch=False\b", d):
            bad.append(f"{r['name']}: match=False ({d})")
        # fields are '_'-separated key=value runs, so \b can't anchor the
        # key starts (the '_' before a key is itself a word character)
        m = re.search(r"(?:^|_)recall=([0-9.]+)", d)
        f = re.search(r"(?:^|_)floor=([0-9.]+)", d)
        if m and f and float(m.group(1)) < float(f.group(1)):
            bad.append(f"{r['name']}: recall {m.group(1)} < floor "
                       f"{f.group(1)} ({d})")
        p = re.search(r"(?<!floor_)p99_us=([0-9.]+)", d)
        pf = re.search(r"floor_p99_us=([0-9.]+)", d)
        if p and pf and float(p.group(1)) > float(pf.group(1)):
            bad.append(f"{r['name']}: p99 {p.group(1)}us > floor "
                       f"{pf.group(1)}us ({d})")
        if re.search(r"(?:^|_)qps_monotone=False\b", d):
            bad.append(f"{r['name']}: qps_monotone=False ({d})")
        er = re.search(r"(?:^|_)est_ratio=([0-9.]+)", d)
        rc = re.search(r"(?:^|_)ratio_ceil=([0-9.]+)", d)
        if er and rc and float(er.group(1)) > float(rc.group(1)):
            bad.append(f"{r['name']}: est_ratio {er.group(1)} > ceiling "
                       f"{rc.group(1)} ({d})")
        # accuracy guards (fig5 / reliability_bench): a row's acc= must
        # clear its own acc_floor= (mitigated/self-healing legs) and stay
        # under its acc_ceil= (unmitigated legs — proves the injected
        # faults are real, not a silent no-op)
        a = re.search(r"(?:^|_)acc=([0-9.]+)", d)
        af = re.search(r"(?:^|_)acc_floor=([0-9.]+)", d)
        ac = re.search(r"(?:^|_)acc_ceil=([0-9.]+)", d)
        if a and af and float(a.group(1)) < float(af.group(1)):
            bad.append(f"{r['name']}: acc {a.group(1)} < floor "
                       f"{af.group(1)} ({d})")
        if a and ac and float(a.group(1)) > float(ac.group(1)):
            bad.append(f"{r['name']}: acc {a.group(1)} > ceiling "
                       f"{ac.group(1)} ({d})")
    if bad:
        raise RuntimeError("benchmark floor violations:\n  "
                           + "\n  ".join(bad))


def _run_and_collect(fn, rows: list) -> None:
    """Run a benchmark main, echo its stdout, and parse the CSV rows."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        fn()
    text = buf.getvalue()
    print(text, end="")
    for line in text.splitlines():
        parts = line.strip().split(",", 2)
        if len(parts) == 3:
            name, us, derived = parts
            try:
                rows.append({"name": name, "us_per_call": float(us),
                             "derived": derived})
            except ValueError:
                pass  # not a CSV row (stray print)


def main() -> None:
    full = "--full" in sys.argv
    devices = 4
    if "--devices" in sys.argv:
        devices = int(sys.argv[sys.argv.index("--devices") + 1])
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (autotune_bench, cascade_bench, fig4_sweep,
                   fig5_nonidealities, kernel_bench, reliability_bench,
                   serve_bench, sharded_bench, sharded_perf,
                   table4_validation)

    rows: list = []

    def emit(name, us, derived):
        print(f"{name},{us},{derived}")
        rows.append({"name": name, "us_per_call": float(us),
                     "derived": str(derived)})

    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    _run_and_collect(table4_validation.main, rows)
    _run_and_collect(sharded_perf.main, rows)
    _run_and_collect(fig4_sweep.main, rows)
    _run_and_collect(fig5_nonidealities.main, rows)
    _run_and_collect(lambda: reliability_bench.main(backend="functional"),
                     rows)
    _run_and_collect(kernel_bench.main, rows)
    _run_and_collect(lambda: cascade_bench.main(ci=not full), rows)
    _run_and_collect(lambda: serve_bench.main(backend="both"), rows)
    _run_and_collect(lambda: autotune_bench.main(backend="functional"),
                     rows)
    if devices > 0:
        _run_and_collect(lambda: sharded_bench.main(devices), rows)

    # roofline summary (if the dry-run has produced results)
    from . import roofline_table
    cells = roofline_table.load("baseline", "single")
    if cells:
        bounds = {}
        for e in cells:
            b = e["roofline"]["bottleneck"]
            bounds[b] = bounds.get(b, 0) + 1
        emit("dryrun_cells_single", 0,
             f"n={len(cells)}_bottlenecks={bounds}")
    cells_m = roofline_table.load("baseline", "multi")
    if cells_m:
        emit("dryrun_cells_multi", 0, f"n={len(cells_m)}")

    if full:
        res = fig4_sweep.run()
        emit("fig4_full", 0, fig4_sweep.check_trends(res))
        out = fig5_nonidealities.run()
        emit("fig5_full", 0, fig5_nonidealities.check_trends(out))
    emit("total_wall_s", round((time.perf_counter() - t0) * 1e6),
         f"{time.perf_counter() - t0:.1f}s")
    check_floors(rows)
    merged = merge_bench_rows(rows)
    print(f"bench_json,0,rows={len(merged)}_path={BENCH_JSON.name}")


if __name__ == "__main__":
    main()

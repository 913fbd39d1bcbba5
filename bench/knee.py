"""Find a served configuration's knee: the highest open-loop rate it
sustains with no growing backlog.

    python3 bench/knee.py --workload <cell> --seed <n> [--seconds 8]
        [--shares 0.5,0.7,0.8,0.9,0.95,1.0,1.05]

One process sets up the cell's store once, measures the closed-loop rate
(1024 in flight) as the capacity, then offers Poisson arrivals at each
share of it in turn.  For each rate it prints the throughput, the p50 and
p95 latency from due time, and how many requests were still waiting when
the window closed.  The knee is written into an open-loop traffic file by
hand, as a number: the benchmark never searches for a rate.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    import argparse

    import numpy as np

    from bench import harness, loadgen
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--shares", default="0.5,0.7,0.8,0.9,0.95,1.0,1.05")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.enable_cache()
    harness.check_devices(cell.chips)
    srv, queries = harness.build(cell, args.seed)
    harness.warm(srv, queries)
    closed = loadgen.run_closed(srv, queries, {"outstanding": 1024},
                                args.seconds)
    cap = closed.answered_in_window() / closed.seconds
    print(json.dumps({"closed_qps": cap, "steps": len(closed.steps)}),
          flush=True)
    rng = np.random.default_rng(args.seed)
    for share in (float(s) for s in args.shares.split(",")):
        win = loadgen.run_poisson(srv, queries, {"rate_qps": share * cap},
                                  args.seconds, rng=rng)
        lat = win.latencies()
        due = np.asarray(win.t_due)
        sub = np.asarray(win.t_submit, float)
        waiting = int(np.sum((due <= win.t_close) & (
            np.asarray(win.t_done, float) > win.t_close)))
        print(json.dumps({
            "share": share, "rate_qps": share * cap,
            "qps": win.answered_in_window() / win.seconds,
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "late_ms": float(np.nanmean(sub - due)) * 1e3,
            "waiting_at_close": waiting,
            "steps": len(win.window_steps())}), flush=True)
    return 0


if __name__ == "__main__":
    from bench import harness
    harness.process_env()
    sys.exit(main())

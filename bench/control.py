"""Readings that the correctness limits are set from; not run by the
benchmark's own runs.

    python3 bench/control.py --workload <cell> --seeds 1,2,...
        [--control-seeds 3] [--seconds 3]

For each seed, in one process: build the cell's store, serve the cell's
own mix for a short window, and read the program's ``answer_gap`` exactly
as a benchmark run does (``harness.check``).  For the first
``--control-seeds`` seeds, also put each of the configuration's controls
(``refs/<reference>.py``, ``Reference.control_answers``: the reference in
a lowered precision) in the program's place on the same queries
and read its gap over every distinct query the window answered.  One JSON
line per seed.  The limit of a number lies
above the largest program reading and below the smallest control reading.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(cell, seed: int, seconds: float, controls, *, allow_cpu=False
             ) -> dict:
    import gc

    import jax.numpy as jnp
    import numpy as np

    from bench import harness, loadgen
    harness.check_devices(cell.chips, allow_cpu)
    srv, queries = harness.build(cell, seed)
    harness.warm(srv, queries)
    win = loadgen.run(srv, queries, cell.mix, seconds,
                      rng=np.random.default_rng(seed))
    del srv
    gc.collect()
    out = {"seed": seed, "answered": len(win.qid) - win.unanswered(),
           "program": harness.check(cell, seed, win)["answer_gap"]["value"]}
    if controls:
        ref, qs = harness.reference(cell, seed)
        uniq, qidx, _ = harness.answered(win)
        q = jnp.take(qs, jnp.asarray(uniq), axis=0)
        out["control"] = {}
        for c in controls:
            g = ref.gaps(q, np.arange(len(uniq)), ref.control_answers(q, c))
            out["control"][c] = {"gap": float(g.max()),
                                 "queries_over_0": int(np.sum(g > 0)),
                                 "queries": len(uniq)}
    return out


def main(argv=None) -> int:
    import argparse

    from bench import harness
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.enable_cache()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        r = readings(cell, seed, args.seconds,
                     cell.config["controls"] if i < args.control_seeds
                     else ())
        r["seconds"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    from bench import harness
    harness.process_env()
    sys.exit(main())

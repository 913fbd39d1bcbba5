"""``camasim-run``: execute one JSON experiment config end to end.

    camasim-run CONFIG.json [--entries K] [--dims N] [--queries Q]
                            [--seed S] [--include-write] [--plan-only]
    camasim-run CONFIG.json --autotune [--objective edp] [--top T]

The config is the FULL experiment description (app/arch/circuit/device
design levels + the sim execution section); the CLI drives
``CAMASim.from_json`` through write -> query -> eval_perf on synthetic
data and prints the performance report as JSON to stdout.  With
``--plan-only`` no data is ever written: the architecture is derived from
the (entries, dims) shape alone (estimator-only planning).

``--autotune`` extends plan-only semantics to the whole DEPLOYMENT space:
it sweeps the ``sim``-section knobs (q_tile / devices / link /
top_p_banks / ...) purely on the estimator, prints the ranked candidate
table to stderr, writes the winning full config as
``CONFIG.tuned.json`` next to the input, and emits a JSON summary
(objective, winning knobs/metrics, tuned path) to stdout.  Still zero
writes — the tuned config deploys by re-running with it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Optional


def _jsonable(obj):
    """Report -> plain JSON: PerfResult leaves become their field dicts."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return _jsonable(dataclasses.asdict(obj))
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    return obj


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(prog="camasim-run", description=__doc__)
    ap.add_argument("config", help="path to the JSON experiment config")
    ap.add_argument("--entries", type=int, default=64,
                    help="stored entries K (default 64)")
    ap.add_argument("--dims", type=int, default=32,
                    help="entry dims N (default 32)")
    ap.add_argument("--queries", type=int, default=8,
                    help="query batch size (default 8)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--include-write", action="store_true",
                    help="add the write-path prediction to the report")
    ap.add_argument("--plan-only", action="store_true",
                    help="estimator-only: no functional simulation at all")
    ap.add_argument("--autotune", action="store_true",
                    help="estimator-only deployment sweep: rank sim-section "
                         "candidates, write CONFIG.tuned.json next to the "
                         "input")
    ap.add_argument("--objective", default="edp",
                    help="autotune ranking objective "
                         "(latency|energy|area|edp|qps; default edp)")
    ap.add_argument("--top", type=int, default=10,
                    help="rows of the ranked table to print (default 10)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from repro.core import CAMASim
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    sim = CAMASim.from_json(args.config)
    cfg = sim.config
    print(f"config : {args.config}", file=sys.stderr)
    print(f"backend: {cfg.sim.backend} (use_kernel={cfg.sim.use_kernel})",
          file=sys.stderr)

    if args.autotune:
        res = sim.autotune(args.entries, args.dims,
                           objective=args.objective,
                           queries_per_batch=args.queries)
        print(f"autotune: {len(res.candidates)} candidates ranked by "
              f"{res.objective} ({res.skipped} invalid skipped)",
              file=sys.stderr)
        print(res.table(top=args.top), file=sys.stderr)
        tuned_path = (args.config[:-len(".json")]
                      if args.config.endswith(".json")
                      else args.config) + ".tuned.json"
        with open(tuned_path, "w") as f:
            f.write(res.config.to_json(indent=1))
            f.write("\n")
        print(f"tuned  : {tuned_path}", file=sys.stderr)
        best = res.best
        json.dump({
            "objective": res.objective,
            "entries": res.entries,
            "dims": res.dims,
            "queries_per_batch": res.queries_per_batch,
            "candidates": len(res.candidates),
            "skipped": res.skipped,
            "tuned_config": tuned_path,
            "best": {"knobs": _jsonable(best.knobs),
                     "metrics": _jsonable(best.metrics)},
        }, sys.stdout, indent=1)
        print()
        return 0

    if args.plan_only:
        sim.plan(args.entries, args.dims)
    else:
        key = jax.random.PRNGKey(args.seed)
        k1, k2, k3 = jax.random.split(key, 3)
        stored = jax.random.uniform(k1, (args.entries, args.dims))
        if cfg.app.distance == "range":      # ACAM [lo, hi] range store
            stored = jnp.stack([stored, stored + 0.2], axis=-1)
        queries = jax.random.uniform(k2, (args.queries, args.dims))
        state = sim.write(stored, key=k3)
        res = sim.query(state, queries)
        hits = int((jnp.asarray(res.mask) > 0).any(-1).sum())
        print(f"search : {args.queries} queries against "
              f"{args.entries}x{args.dims} store, "
              f"{hits} with >=1 match", file=sys.stderr)
        print(f"arch   : {sim.arch_specifics().describe()}", file=sys.stderr)
        if getattr(state, "rel", None) is not None:
            import numpy as np
            healed = int(np.asarray(state.rel.retired).sum())
            unhealed = int(np.asarray(state.rel.failed).sum())
            print(f"reliab : {healed} rows healed onto spares, "
                  f"{unhealed} failed unhealed", file=sys.stderr)

    perf = sim.eval_perf(n_queries=args.queries,
                         include_write=args.include_write)
    json.dump(_jsonable(perf.to_dict()), sys.stdout, indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())

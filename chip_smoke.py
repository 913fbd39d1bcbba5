#!/usr/bin/env python3
"""Smoke test of the CAM store and serve engine on a TPU.

    python3 chip_smoke.py [--seed S] [--rows K]          # one chip
    python3 chip_smoke.py --chips 4 [--seed S] [--rows K]  # four chips

One chip (the default): a ``CAMASim`` store of ``--rows`` (1,048,576)
rows x 128 dims, generated from ``--seed`` as a clustered mixture (the
SIFT-1M shape of ann-benchmarks), on ``examples/configs/serve.json``'s
semantics at R = C = 128 with the fused Pallas kernels on: 3-bit l2 best
match (k = 3), adder/comparator merges, D2D programming noise folded per
row slot, 4096 rows of insert head-room.  ``CAMSearchServer`` serves four
full batches of 256 searches, one insert run and one delete run, then two
more batches.  Every served answer is checked against the jnp reference
path (``use_kernel=False``) on the same state and queries: index sets
agree except at distance ties within ``TIE_RTOL``.  Deleted ids never
return, inserted rows are found, and the compiled search program holds
the fused kernel (``tpu_custom_call``).

Four chips (``--chips 4``, that phase alone): ``ShardedCAMSimulator`` over
a 4-device bank mesh on 4 x ``--rows`` rows with
``examples/configs/sharded.json``'s semantics (no device noise, so the
int8 code path runs) at R = C = 128, compared bit-for-bit on a 64-query
batch with ``FunctionalSimulator(c2c_fold='bank')`` over the same store
on one chip, and exactly with the jnp reference.

Exits non-zero, printing no result, unless JAX's first device is a TPU
and every check passes.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

DIMS = 128
BATCH = 256
SPARE_ROWS = 4096
# Float-path tie tolerance: the kernel's l2 norm expansion
# ||s||^2 - 2 s.q + ||q||^2 and the reference's direct sum of squares
# round differently, by a few f32 ulps of ||s||^2 + ||q||^2.  Answers
# that differ only between rows whose exact distances lie within
# TIE_RTOL * (||s||^2 + ||q||^2) of each other count as ties.
TIE_RTOL = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


def serve_config(rows_total: int, *, rows: int = 128, cols: int = 128,
                 batch: int = BATCH, use_kernel: bool = True):
    """serve.json's semantics at R x C subarrays, cascade off."""
    from repro.core import CAMConfig
    return CAMConfig.from_dict(dict(
        app=dict(distance="l2", match_type="best", match_param=3,
                 data_bits=3),
        arch=dict(h_merge="adder", v_merge="comparator"),
        circuit=dict(rows=rows, cols=cols, cell_type="mcam",
                     sensing="best", sensing_limit=0.0),
        device=dict(device="fefet", variation="d2d", variation_std=0.05),
        sim=dict(backend="functional", use_kernel=use_kernel,
                 capacity=rows_total, serve_batch=batch, d2d_fold="row")))


def sharded_config(*, rows: int = 128, cols: int = 128,
                   backend: str = "sharded"):
    """sharded.json's semantics at R x C subarrays, kernels on."""
    from repro.core import CAMConfig
    return CAMConfig.from_dict(dict(
        app=dict(distance="l2", match_type="best", match_param=3,
                 data_bits=3),
        arch=dict(h_merge="adder", v_merge="comparator"),
        circuit=dict(rows=rows, cols=cols, cell_type="mcam",
                     sensing="best", sensing_limit=0.0),
        device=dict(device="fefet", variation="none", variation_std=0.0),
        sim=dict(backend=backend, use_kernel=True, c2c_fold="bank")))


def clustered_rows(key, n: int, dims: int, *, n_clusters: int = 1024,
                   spread: float = 0.1, sharding=None):
    """(n, dims) f32 rows: uniform cluster centres plus gaussian spread,
    made on the device (optionally laid out with ``sharding``)."""
    import jax
    import jax.numpy as jnp

    def make(key):
        kc, ka, kn = jax.random.split(key, 3)
        centres = jax.random.uniform(kc, (n_clusters, dims))
        assign = jax.random.randint(ka, (n,), 0, n_clusters)
        return centres[assign] + spread * jax.random.normal(kn, (n, dims))

    return jax.jit(make, out_shardings=sharding)(key)


def search_program_text(sim, state, queries, key) -> str:
    """Compiled HLO of the search step ``CAMSearchServer`` dispatches."""
    import jax.numpy as jnp
    backend = getattr(sim, "backend", sim)
    count = jnp.asarray(queries.shape[0], jnp.int32)
    lowered = type(backend)._query_jit.lower(backend, state, queries, key,
                                             count)
    return lowered.compile().as_text()


def exact_distances(state, qcodes, ids):
    """float64 squared l2 distances of query codes (Q, N) to stored rows
    ``ids`` (Q, k) of the noisy grid, on the host."""
    import numpy as np
    spec = state.spec
    grid = state.grid
    ids = np.asarray(ids)
    v, r = ids // spec.R, ids % spec.R
    rows = np.asarray(grid[v.reshape(-1), :, r.reshape(-1)], np.float64)
    rows = rows.reshape(*ids.shape, -1)[..., :spec.N]
    q = np.asarray(qcodes, np.float64)[:, None, :]
    d = ((rows - q) ** 2).sum(-1)
    scale = (rows ** 2).sum(-1) + (q ** 2).sum(-1)
    return d, scale


def compare_to_reference(state, ref_sim, queries, got, key) -> dict:
    """Served indices ``got`` (Q, k) vs the jnp reference on the same
    state and queries: identical sets, or sets whose sorted exact
    distances agree within the tie tolerance."""
    import numpy as np
    want = np.asarray(ref_sim.query(state, queries, key=key).indices)
    got = np.asarray(got)
    same = np.array([set(a) == set(b) for a, b in zip(got, want)])
    ties = bad = 0
    if not same.all():
        rows = np.where(~same)[0]
        qcodes = ref_sim.query_codes(state, queries[rows])
        dg, sg = exact_distances(state, qcodes, got[rows])
        dw, sw = exact_distances(state, qcodes, want[rows])
        tol = TIE_RTOL * np.maximum(sg.max(-1), sw.max(-1))[:, None]
        ok = np.abs(np.sort(dg, -1) - np.sort(dw, -1)) <= tol
        ties = int(ok.all(-1).sum())
        bad = int((~ok.all(-1)).sum())
    return {"queries": int(got.shape[0]), "identical": int(same.sum()),
            "ties": ties, "mismatches": bad}


def serve_phase(*, rows_total: int, seed: int, dims: int = DIMS,
                rows: int = 128, cols: int = 128, batch: int = BATCH,
                n_batches: int = 4, n_insert: int = 1024,
                n_clusters: int = 1024, check_kernel_program: bool = True
                ) -> dict:
    """Write, serve, mutate and re-serve one store; raise on any failed
    check and return the phase's numbers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import CAMASim, FunctionalSimulator
    from repro.kernels import cam_search
    from repro.runtime.serve_loop import CAMSearchServer

    if batch < cam_search.SMALL_Q_CROSSOVER:
        raise ValueError("batches below SMALL_Q_CROSSOVER would take the "
                         "jnp twin, not the kernel")
    out: dict = {}
    cfg = serve_config(rows_total + SPARE_ROWS, rows=rows, cols=cols,
                       batch=batch)
    cam = CAMASim(cfg)
    ref = FunctionalSimulator(cfg.replace(sim=dict(use_kernel=False)))
    kd, kq, kn, ki = jax.random.split(jax.random.PRNGKey(seed), 4)

    t0 = time.perf_counter()
    data = clustered_rows(kd, rows_total, dims, n_clusters=n_clusters)
    state = cam.write(data, key=jax.random.fold_in(kd, 1))
    jax.block_until_ready(state.grid)
    out["write_s"] = time.perf_counter() - t0
    spec = state.spec
    log(f"store: {rows_total} rows x {dims} dims written in "
        f"{out['write_s']:.3f} s (nv={spec.nv}, nh={spec.nh}, "
        f"R={spec.R}, C={spec.C}, padded_K={spec.padded_K})")
    budget = cam_search.device_model()["vmem_budget_bytes"]
    vb = cam_search.resident_banks(spec.nv, spec.nh, spec.R, spec.C,
                                   budget_bytes=budget)
    qt = cam_search.choose_q_tile(spec.R, spec.C, banks=spec.nv,
                                  segs=spec.nh, budget_bytes=budget)
    log(f"kernel blocks: vb={vb} banks/step, q_tile={qt}, VMEM budget "
        f"{budget} B")

    # queries: stored rows plus a little noise, so neighbours are real
    pick = jax.random.randint(kq, (n_batches * batch,), 0, rows_total)
    queries = np.asarray(data[pick] + 0.02 * jax.random.normal(
        kn, (n_batches * batch, dims)))
    srv = CAMSearchServer(cam, state, batch=batch,
                          key=jax.random.PRNGKey(seed + 1))
    if check_kernel_program:
        t0 = time.perf_counter()
        text = search_program_text(cam, state, jnp.asarray(queries[:batch]),
                                   srv.key)
        out["compile_s"] = time.perf_counter() - t0
        if "tpu_custom_call" not in text:
            raise AssertionError("fused kernel missing from the compiled "
                                 "search program")
        log(f"search program compiled in {out['compile_s']:.3f} s; "
            "fused kernel present (tpu_custom_call)")

    def serve(qs):
        """Submit ``qs`` as whole batches, run the server, return the
        indices in submission order plus per-step wall times."""
        assert qs.shape[0] % batch == 0
        reqs = [srv.submit(q) for q in qs]
        steps = []
        while srv.queue:
            t0 = time.perf_counter()
            srv.step()
            steps.append(time.perf_counter() - t0)
        idx = np.stack([r.indices for r in reqs])
        srv.finished.clear()         # drop the served (Q, K) mask rows
        return idx, steps

    first_step = srv._steps
    got, steps = serve(queries)
    out["batches"] = len(steps)
    out["step_s"] = steps
    log(f"served {len(steps)} batches of {batch}: step seconds "
        f"{[round(s, 4) for s in steps]}")
    checks = []
    for b in range(n_batches):
        sl = slice(b * batch, (b + 1) * batch)
        key = jax.random.fold_in(srv.key, first_step + b)
        checks.append(compare_to_reference(
            srv.state, ref, jnp.asarray(queries[sl]), got[sl], key))
    log(f"reference agreement before mutations: {checks}")

    # mutations: one insert run and one delete run, then search again
    new_rows = np.asarray(clustered_rows(ki, n_insert, dims,
                                         n_clusters=n_clusters))
    ins = srv.submit_insert(new_rows)
    deleted = np.unique(got[:batch, 0])
    srv.submit_delete(deleted)
    n_ins_q = min(n_insert, batch)
    after_q = np.concatenate([new_rows[:n_ins_q],
                              queries[:2 * batch - n_ins_q]])
    t0 = time.perf_counter()
    first_step = srv._steps
    got2, steps2 = serve(after_q)
    out["mutate_serve_s"] = time.perf_counter() - t0
    out["batches"] += len(steps2)
    if ins.ids is None or len(ins.ids) != n_insert:
        raise AssertionError("the insert run did not complete")
    log(f"inserted {n_insert} rows, deleted {deleted.size} ids, served "
        f"{len(steps2)} more batches: step seconds "
        f"{[round(s, 4) for s in steps2]}")
    found = np.array([ins.ids[i] in got2[i] for i in range(n_ins_q)])
    if not found.all():
        raise AssertionError(f"{int((~found).sum())} inserted rows not "
                             "found by their own query")
    if np.isin(got2, deleted).any():
        raise AssertionError("deleted ids returned after the delete")
    for b in range(len(steps2)):
        sl = slice(b * batch, (b + 1) * batch)
        key = jax.random.fold_in(srv.key, first_step + b)
        checks.append(compare_to_reference(
            srv.state, ref, jnp.asarray(after_q[sl]), got2[sl], key))
    log(f"inserted rows found: {int(found.sum())}/{n_ins_q}; deleted ids "
        f"returned: 0; reference agreement after mutations: {checks[-2:]}")
    bad = sum(c["mismatches"] for c in checks)
    if bad:
        raise AssertionError(f"{bad} queries disagree with the jnp "
                             "reference beyond distance ties")
    out["checks"] = checks
    return out


def sharded_phase(*, rows_per_chip: int, seed: int, chips: int,
                  dims: int = DIMS, rows: int = 128, cols: int = 128,
                  n_queries: int = 64, n_clusters: int = 1024) -> dict:
    """Sharded store over a ``chips``-device bank mesh vs the one-chip
    functional reference; raise on any difference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import FunctionalSimulator, ShardedCAMSimulator
    from repro.launch.mesh import make_cam_mesh

    mesh = make_cam_mesh(chips)
    if mesh.devices.size != chips:
        raise AssertionError(f"mesh {mesh} does not span {chips} devices")
    sim = ShardedCAMSimulator(sharded_config(rows=rows, cols=cols), mesh)
    ref = FunctionalSimulator(sharded_config(rows=rows, cols=cols,
                                             backend="functional"))
    jnp_ref = FunctionalSimulator(sharded_config(
        rows=rows, cols=cols, backend="functional").replace(
            sim=dict(use_kernel=False)))
    kd, kq, kn = jax.random.split(jax.random.PRNGKey(seed), 3)
    K = chips * rows_per_chip
    t0 = time.perf_counter()
    data = clustered_rows(kd, K, dims, n_clusters=n_clusters,
                          sharding=NamedSharding(mesh, P("bank")))
    state = sim.write(data)
    jax.block_until_ready(state.grid)
    write_s = time.perf_counter() - t0
    placed = state.grid.sharding.device_set
    if len(placed) != chips:
        raise AssertionError(f"grid placed on {len(placed)} devices")
    log(f"sharded store: {K} rows x {dims} dims over {chips} devices "
        f"({sorted(d.id for d in placed)}) written in {write_s:.3f} s")
    pick = jax.random.randint(kq, (n_queries,), 0, K)
    queries = data[pick] + 0.02 * jax.random.normal(kn, (n_queries, dims))
    queries = jax.device_put(queries, NamedSharding(mesh, P()))
    t0 = time.perf_counter()
    got = sim.query(state, queries)
    jax.block_until_ready(got.indices)
    query_s = time.perf_counter() - t0
    if len(got.indices.sharding.device_set) != chips:
        raise AssertionError("sharded search did not run on every device")
    # the same store on one chip (clean codes dropped: search never
    # reads them)
    one = jax.devices()[0]
    ref_state = jax.device_put(
        type(state)(state.grid, state.lo, state.hi, state.spec,
                    state.col_valid, state.row_valid), one)
    q1 = jax.device_put(queries, one)
    del data
    want = ref.query(ref_state, q1)
    exact = jnp_ref.query(ref_state, q1)
    same_idx = bool(np.array_equal(np.asarray(got.indices),
                                   np.asarray(want.indices)))
    same_mask = bool(np.array_equal(np.asarray(got.mask),
                                    np.asarray(want.mask)))
    same_jnp = bool(np.array_equal(np.asarray(want.indices),
                                   np.asarray(exact.indices)))
    log(f"sharded search of {n_queries} queries in {query_s:.3f} s "
        f"(compile included); bit-identical to the one-chip kernel "
        f"reference: indices={same_idx} mask={same_mask}; one-chip kernel "
        f"== jnp reference: {same_jnp}")
    if not (same_idx and same_mask and same_jnp):
        raise AssertionError("sharded search differs from the one-chip "
                             "reference")
    return {"write_s": write_s, "query_s": query_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=1 << 20,
                    help="stored rows (per chip with --chips 4)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {devices[0].device_kind} x {len(devices)}; compile cache "
        f"{enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(rows_per_chip=args.rows, seed=args.seed, chips=4)
    else:
        serve_phase(rows_total=args.rows, seed=args.seed)
    stats = devices[0].memory_stats() or {}
    log(f"wall {time.perf_counter() - t0:.3f} s; device 0 peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

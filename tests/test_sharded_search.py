"""Sharded CAM search: multi-device parity + merge/sense properties.

Three layers of guarantees:
  * a 4-host-device subprocess sweep asserting ``ShardedCAMSimulator`` is
    bit-identical to the single-device ``FunctionalSimulator`` across all
    {exact, best, threshold} x {l2, l1, hamming, dot} combos, including
    C2C noise (per-bank RNG folding), the Pallas kernel path, ACAM 5-D
    [lo, hi] range grids on the fused range kernel, best-match with
    match_param > padded_K (clamp + -1 pad parity), and the device
    reliability subsystem (slot-keyed fault maps, drift aging, write-verify
    + spare healing, scrub — with and without the mutable-store path);
  * property tests (hypothesis, offline shim) for the cross-device merge
    invariants: the local-top-k + re-rank comparator is split-invariant,
    associative, and (absent score ties) shard-order permutation
    invariant; the gather merge is split-invariant;
  * sense-amplifier monotonicity: loosening ``sensing_limit`` never
    removes a match, for every sensing mode.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import merge, subarray


# ---------------------------------------------------------------------------
# multi-device parity (subprocess: XLA host-device trick must precede
# jax init, reusing the JAX_PLATFORMS=cpu pattern from the batched-search PR)
# ---------------------------------------------------------------------------
_PARITY_SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import zlib
import jax, jax.numpy as jnp, numpy as np
from repro.core import (AppConfig, ArchConfig, CAMASim, CAMConfig,
                        CircuitConfig, DeviceConfig, FunctionalSimulator,
                        ShardedCAMSimulator)
from repro.launch.mesh import make_cam_mesh

assert len(jax.devices()) == 4, jax.devices()
mesh = make_cam_mesh(4)
mesh_q = make_cam_mesh(2, 2)

def check(cfg, K=37, N=12, Q=9, use_kernel=False, query_axis=None,
          c2c_tile=1, tag=""):
    m = mesh_q if query_axis else mesh
    # the config-driven facade must be bit-identical to constructing the
    # backends directly: run the whole matrix a third time through
    # CAMASim with sim.backend='sharded' (same mesh geometry via config)
    base_sim = dict(use_kernel=use_kernel, c2c_query_tile=c2c_tile,
                    c2c_fold="bank")
    sim = FunctionalSimulator(cfg.replace(sim=base_sim))
    ssim = ShardedCAMSimulator(cfg.replace(sim=base_sim), m,
                               query_axis=query_axis)
    fac = CAMASim(cfg.replace(sim=dict(
        base_sim, backend="sharded",
        devices=2 if query_axis else 4,
        query_shards=2 if query_axis else 1)))
    k1, k2 = jax.random.split(jax.random.PRNGKey(zlib.crc32(tag.encode())))
    stored = jax.random.uniform(k1, (K, N))
    if cfg.circuit.cell_type == "acam":     # 5-D [lo, hi] range grid
        stored = jnp.stack([stored, stored + 0.2], axis=-1)
    queries = jax.random.uniform(k2, (Q, N))
    qkey = jax.random.PRNGKey(7)
    ia, ma = sim.query(sim.write(stored), queries, key=qkey)
    ib, mb = ssim.query(ssim.write(stored), queries, key=qkey)
    ic, mc = fac.query(fac.write(stored), queries, key=qkey)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib), err_msg=tag)
    np.testing.assert_array_equal(np.asarray(ma), np.asarray(mb), err_msg=tag)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ic),
                                  err_msg="facade-" + tag)
    np.testing.assert_array_equal(np.asarray(ma), np.asarray(mc),
                                  err_msg="facade-" + tag)
    print("OK", tag)

def cfg_for(match, distance, h_merge, v_merge, sensing, variation="none"):
    return CAMConfig(
        app=AppConfig(distance=distance, match_type=match, match_param=3,
                      data_bits=3),
        arch=ArchConfig(h_merge=h_merge, v_merge=v_merge),
        circuit=CircuitConfig(rows=8, cols=8, cell_type="mcam",
                              sensing=sensing, sensing_limit=0.5),
        device=DeviceConfig(device="fefet", variation=variation,
                            variation_std=0.4))

n = 0
for distance in ("l2", "l1", "hamming", "dot"):
    check(cfg_for("exact", distance, "and", "gather", "exact"),
          tag=f"exact-{distance}")
    check(cfg_for("best", distance, "adder", "comparator", "best"),
          tag=f"best-{distance}")
    check(cfg_for("threshold", distance, "adder", "gather", "threshold"),
          tag=f"threshold-{distance}")
    n += 3

# voting h-merge (the approximate paper merge; global pmax tie-break)
check(cfg_for("best", "l2", "voting", "comparator", "best"), tag="voting")
# C2C noise with per-shard RNG folding, one per match type
check(cfg_for("exact", "hamming", "and", "gather", "exact", "c2c"),
      tag="c2c-exact")
check(cfg_for("best", "l2", "adder", "comparator", "best", "c2c"),
      tag="c2c-best")
check(cfg_for("threshold", "l1", "adder", "gather", "threshold", "c2c"),
      tag="c2c-threshold")
# Pallas fused kernel path (interpret mode on CPU)
check(cfg_for("best", "l2", "adder", "comparator", "best"),
      use_kernel=True, tag="kernel-best")
check(cfg_for("exact", "hamming", "and", "gather", "exact"),
      use_kernel=True, tag="kernel-exact")
# query-axis sharding (2 banks x 2 query shards), incl. c2c cycle slicing
check(cfg_for("best", "l2", "adder", "comparator", "best"), Q=8,
      query_axis="query", tag="qshard-best")
check(cfg_for("best", "l2", "adder", "comparator", "best", "c2c"), Q=8,
      query_axis="query", c2c_tile=2, tag="qshard-c2c")
n += 9

# ACAM 5-D [lo, hi] range grids on the fused range kernel, all sensings,
# jnp path, and C2C on the per-bank fold over the 5-D grid
def acam_cfg(match, h_merge, v_merge, sensing, variation="none"):
    return CAMConfig(
        app=AppConfig(distance="range", match_type=match, match_param=3,
                      data_bits=0),
        arch=ArchConfig(h_merge=h_merge, v_merge=v_merge),
        circuit=CircuitConfig(rows=8, cols=8, cell_type="acam",
                              sensing=sensing, sensing_limit=0.5),
        device=DeviceConfig(device="fefet", variation=variation,
                            variation_std=0.05))

check(acam_cfg("exact", "and", "gather", "exact"), use_kernel=True,
      tag="acam-kernel-exact")
check(acam_cfg("best", "adder", "comparator", "best"), use_kernel=True,
      tag="acam-kernel-best")
check(acam_cfg("threshold", "adder", "gather", "threshold"),
      use_kernel=True, tag="acam-kernel-threshold")
check(acam_cfg("exact", "and", "gather", "exact"), tag="acam-jnp-exact")
check(acam_cfg("exact", "and", "gather", "exact", "c2c"), use_kernel=True,
      tag="acam-kernel-c2c")
n += 5

# pipelined (bank-blocked) schedule off-switch: sim.pipeline=False on the
# sharded backend must be bit-identical BOTH to the pipelined sharded run
# and to the single-device reference — covering the fused point kernel
# (with the quantized-code int fast path) and the ACAM range kernel
def check_pipeline(cfg, tag=""):
    base = dict(use_kernel=True, c2c_fold="bank")
    k1, k2 = jax.random.split(jax.random.PRNGKey(zlib.crc32(tag.encode())))
    stored = jax.random.uniform(k1, (37, 12))
    if cfg.circuit.cell_type == "acam":
        stored = jnp.stack([stored, stored + 0.2], axis=-1)
    queries = jax.random.uniform(k2, (9, 12))
    ref = FunctionalSimulator(cfg.replace(sim=dict(base, pipeline=True)))
    ia, ma = ref.query(ref.write(stored), queries)
    for pipe in (True, False):
        s = ShardedCAMSimulator(cfg.replace(sim=dict(base, pipeline=pipe)),
                                mesh)
        ib, mb = s.query(s.write(stored), queries)
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib),
                                      err_msg=f"pipe-{pipe}-{tag}")
        np.testing.assert_array_equal(np.asarray(ma), np.asarray(mb),
                                      err_msg=f"pipe-{pipe}-{tag}")
    print("OK pipeline", tag)

check_pipeline(cfg_for("best", "l2", "adder", "comparator", "best"),
               tag="point-best")
check_pipeline(cfg_for("exact", "hamming", "and", "gather", "exact"),
               tag="point-hamming")
check_pipeline(acam_cfg("best", "adder", "comparator", "best"),
               tag="acam-best")
n += 3

# best-match merge with match_param > padded_K: the single-device clamp
# + -1 pad must agree with the sharded candidate re-rank (regression for
# the unclamped jax.lax.top_k crash in v_merge_comparator_topk)
big_k = CAMConfig(
    app=AppConfig(distance="l2", match_type="best", match_param=64,
                  data_bits=3),
    arch=ArchConfig(h_merge="adder", v_merge="comparator"),
    circuit=CircuitConfig(rows=8, cols=8, cell_type="mcam", sensing="best"),
    device=DeviceConfig(device="fefet"))
check(big_k, tag="bigk-best")
n += 1

# search cascade: signature prefilter with top_p_banks = nv must be
# bit-identical to prefilter=off on BOTH backends (per-device routing with
# p_loc = nv_loc degenerates to the full scan), incl. the C2C bank fold
# and the kernel path
def check_cascade(cfg, use_kernel=False, c2c_tile=1, tag=""):
    base_sim = dict(use_kernel=use_kernel, c2c_query_tile=c2c_tile,
                    c2c_fold="bank")
    K, N, Q = 37, 12, 9
    k1, k2 = jax.random.split(jax.random.PRNGKey(zlib.crc32(tag.encode())))
    stored = jax.random.uniform(k1, (K, N))
    queries = jax.random.uniform(k2, (Q, N))
    qkey = jax.random.PRNGKey(7)
    ref = FunctionalSimulator(cfg.replace(sim=base_sim))
    st = ref.write(stored)
    ia, ma = ref.query(st, queries, key=qkey)
    cas = dict(base_sim, prefilter="signature", top_p_banks=st.spec.nv)
    for mk, sim_kw in (("func", {}),
                       ("shard", dict(backend="sharded", devices=4))):
        c = CAMASim(cfg.replace(sim=dict(cas, **sim_kw)))
        ib, mb = c.query(c.write(stored), queries, key=qkey)
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib),
                                      err_msg=f"cascade-{mk}-{tag}")
        np.testing.assert_array_equal(np.asarray(ma), np.asarray(mb),
                                      err_msg=f"cascade-{mk}-{tag}")
    print("OK cascade", tag)

check_cascade(cfg_for("exact", "hamming", "and", "gather", "exact"),
              use_kernel=True, tag="exact-kernel")
check_cascade(cfg_for("best", "l2", "adder", "comparator", "best"),
              use_kernel=True, tag="best-kernel")
check_cascade(cfg_for("best", "l2", "voting", "comparator", "best"),
              tag="voting")
check_cascade(cfg_for("threshold", "l1", "adder", "gather", "threshold",
                      "c2c"), c2c_tile=2, tag="threshold-c2c")
n += 4

# device reliability: slot-keyed fault maps, drift aging, write-verify +
# spare-row healing, and background scrub must all be bit-identical across
# shardings (fault maps fold per global row slot; every host-side decision
# — spare planning, scrub-row picks, free-slot order — reads replicated
# data), including the mutable-store insert/delete path
REL = dict(enabled=True, stuck_frac=0.02, dead_row_frac=0.05,
           verify_retries=2, verify_tol=0.3, spares_per_bank=2,
           drift_rate=0.01, scrub_rows=4, fault_seed=11)

def check_reliability(cfg, tag="", mutate=False, query_axis=None,
                      c2c_tile=1, Q=9):
    m = mesh_q if query_axis else mesh
    base_sim = dict(c2c_fold="bank", d2d_fold="row", capacity=64,
                    c2c_query_tile=c2c_tile)
    cfg = cfg.replace(reliability=dict(REL))
    K, N = 37, 12
    k1, k2 = jax.random.split(jax.random.PRNGKey(zlib.crc32(tag.encode())))
    stored = jax.random.uniform(k1, (K, N))
    if cfg.circuit.cell_type == "acam":
        stored = jnp.stack([stored, stored + 0.2], axis=-1)
    queries = jax.random.uniform(k2, (Q, N))
    wkey, qkey, mkey = (jax.random.PRNGKey(3), jax.random.PRNGKey(7),
                        jax.random.PRNGKey(5))
    sim = FunctionalSimulator(cfg.replace(sim=base_sim))
    ssim = ShardedCAMSimulator(cfg.replace(sim=base_sim), m,
                               query_axis=query_axis)
    sa, sb = sim.write(stored, wkey), ssim.write(stored, wkey)
    if mutate:
        extra = jax.random.uniform(jax.random.PRNGKey(13), (5, N))
        sa, ida = sim.insert(sa, extra, mkey)
        sb, idb = ssim.insert(sb, extra, mkey)
        np.testing.assert_array_equal(np.asarray(ida), np.asarray(idb),
                                      err_msg="ids-" + tag)
        sa, sb = sim.delete(sa, ida[:2]), ssim.delete(sb, idb[:2])
    sa, sb = sim.age_tick(sa, 10), ssim.age_tick(sb, 10)
    sa, sb = sim.scrub(sa, mkey), ssim.scrub(sb, mkey)
    ia, ma = sim.query(sa, queries, key=qkey)
    ib, mb = ssim.query(sb, queries, key=qkey)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib), err_msg=tag)
    np.testing.assert_array_equal(np.asarray(ma), np.asarray(mb), err_msg=tag)
    print("OK reliability", tag)

check_reliability(cfg_for("best", "l2", "adder", "comparator", "best"),
                  tag="rel-best")
check_reliability(cfg_for("exact", "hamming", "and", "gather", "exact",
                          "both"), tag="rel-noise-exact")
check_reliability(cfg_for("best", "l2", "adder", "comparator", "best",
                          "d2d"), mutate=True, tag="rel-mutate")
check_reliability(acam_cfg("best", "adder", "comparator", "best"),
                  tag="rel-acam")
check_reliability(cfg_for("best", "l2", "adder", "comparator", "best"),
                  Q=8, query_axis="query", tag="rel-qshard")
n += 5
print(f"PARITY_OK {n}")
'''


def _run_subprocess(script: str, timeout: int = 900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.multidevice
def test_sharded_parity_4_devices():
    proc = _run_subprocess(_PARITY_SCRIPT)
    assert proc.returncode == 0 and "PARITY_OK 39" in proc.stdout, \
        (proc.stdout[-2000:], proc.stderr[-4000:])


# best match across shards: many rows tie, across shard boundaries too,
# and nv is not always a multiple of the bank axis; indices and mask stay
# bit-identical to the single-device reference, and the only collectives
# move the (banks, Q, k) candidates, never a (Q, K) block
_SHARD_TIES_SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import re
import jax, jax.numpy as jnp, numpy as np
from repro.core import (AppConfig, ArchConfig, CAMConfig, CircuitConfig,
                        DeviceConfig, FunctionalSimulator,
                        ShardedCAMSimulator)
from repro.launch.mesh import make_cam_mesh

R = 8
cfg = CAMConfig(
    app=AppConfig(distance="l2", match_type="best", match_param=5,
                  data_bits=3),
    arch=ArchConfig(h_merge="adder", v_merge="comparator"),
    circuit=CircuitConfig(rows=R, cols=8, cell_type="mcam", sensing="best"),
    device=DeviceConfig(device="fefet"))
n = 0
for K in (37, 64, 100):        # nv = 5, 8, 13: padded to 8, 8, 16 banks
    for use_kernel in (False, True):
        for banks, qs in ((4, 1), (2, 2)):
            sim_cfg = dict(use_kernel=use_kernel, c2c_fold="bank")
            ref = FunctionalSimulator(cfg.replace(sim=sim_cfg))
            qa = "query" if qs > 1 else None
            ssim = ShardedCAMSimulator(cfg.replace(sim=sim_cfg),
                                       make_cam_mesh(banks, qs),
                                       query_axis=qa)
            # few distinct rows repeated over every bank and device: most
            # distances tie, across shard boundaries too
            base = jax.random.uniform(jax.random.PRNGKey(K), (7, 12))
            stored = jnp.tile(base, (-(-K // 7), 1))[:K]
            queries = jax.random.uniform(jax.random.PRNGKey(K + 1), (8, 12))
            state = ssim.write(stored)
            want = ref.query(ref.write(stored), queries)
            got = ssim.query(state, queries)
            tag = f"K={K} kernel={use_kernel} mesh={banks}x{qs}"
            np.testing.assert_array_equal(np.asarray(want.indices),
                                          np.asarray(got.indices), tag)
            np.testing.assert_array_equal(np.asarray(want.mask),
                                          np.asarray(got.mask), tag)
            assert got.mask.shape == (8, state.spec.padded_K), tag
            text = type(ssim)._query_jit.lower(
                ssim, state, queries, jax.random.PRNGKey(1),
                None).compile().as_text()
            for line in text.splitlines():
                if re.search(r"= \S+ all-gather\(", line):
                    shape = re.search(r"= \w+\[([\d,]*)\]", line).group(1)
                    assert np.prod([int(x) for x in shape.split(",")]) \
                        <= banks * 8 * 5, (tag, line[:160])
            n += 1
print(f"SHARD_TIES_OK {n}")
'''


@pytest.mark.multidevice
def test_best_match_ties_across_shards_4_devices():
    proc = _run_subprocess(_SHARD_TIES_SCRIPT, timeout=600)
    assert proc.returncode == 0 and "SHARD_TIES_OK 12" in proc.stdout, \
        (proc.stdout[-2000:], proc.stderr[-4000:])


# the serve engine over the sharded backend: a best-match step copies only
# the (Q, k) indices, and each served request's mask, rebuilt from them,
# equals the row the sharded query returns, bit for bit; bank-padded grids
# (nv = 5 on 4 devices), k > padded_K, voting and C2C included
_SHARD_SERVE_SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp, numpy as np
from repro.core import (AppConfig, ArchConfig, CAMASim, CAMConfig,
                        CircuitConfig, DeviceConfig, ShardedCAMSimulator)
from repro.launch.mesh import make_cam_mesh
from repro.runtime import CAMSearchServer

base = CAMConfig(
    app=AppConfig(distance="l2", match_type="best", match_param=5,
                  data_bits=3),
    arch=ArchConfig(h_merge="adder", v_merge="comparator"),
    circuit=CircuitConfig(rows=8, cols=8, cell_type="mcam", sensing="best"),
    device=DeviceConfig(device="fefet", variation_std=0.8)).replace(
        sim=dict(c2c_fold="bank"))
cases = {
    "adder": base,
    "voting": base.replace(arch=dict(h_merge="voting")),
    "k_over_padded_K": base.replace(app=dict(match_param=50)),
    "c2c": base.replace(device=dict(variation="c2c")),
    "kernel": base.replace(sim=dict(use_kernel=True)),
    "facade": base.replace(sim=dict(backend="sharded", devices=4)),
}
stored = jax.random.uniform(jax.random.PRNGKey(0), (37, 16))
queries = np.asarray(jax.random.uniform(jax.random.PRNGKey(1), (9, 16)))
batch = 4
n = 0
for tag, cfg in cases.items():
    sim = (CAMASim(cfg) if tag == "facade"
           else ShardedCAMSimulator(cfg, make_cam_mesh(4)))
    state = sim.write(stored)
    assert state.spec.padded_K == 40 and state.grid.shape[0] == 8, tag
    srv = CAMSearchServer(sim, state, batch=batch)
    reqs = [srv.submit(q) for q in queries]
    srv.run()
    k = reqs[0].indices.shape[0]
    assert srv.counters["mask_steps"] == 0, tag
    assert srv.counters["fetch_bytes"] == 3 * batch * k * 4, tag
    for step in range(3):
        rs = [r for r in reqs if r.step == step]
        qs = np.zeros((batch, 16), np.float32)
        qs[:len(rs)] = np.stack([r.query for r in rs])
        want = sim.query(state, jnp.asarray(qs),
                         key=jax.random.fold_in(srv.key, step),
                         valid_count=len(rs))
        for i, r in enumerate(rs):
            row = np.asarray(want.mask[i])
            np.testing.assert_array_equal(r.indices,
                                          np.asarray(want.indices[i]), tag)
            assert r.mask.dtype == row.dtype, tag
            assert r.mask.shape == row.shape == (40,), tag
            assert np.array_equal(r.mask, row), tag
    if tag == "k_over_padded_K":
        assert k == 50 and all((r.indices == -1).any() for r in reqs)
    n += 1
print(f"SHARD_SERVE_OK {n}")
'''


@pytest.mark.multidevice
def test_served_best_match_mask_rebuilt_on_sharded_backend_4_devices():
    proc = _run_subprocess(_SHARD_SERVE_SCRIPT, timeout=600)
    assert proc.returncode == 0 and "SHARD_SERVE_OK 6" in proc.stdout, \
        (proc.stdout[-2000:], proc.stderr[-4000:])


# ---------------------------------------------------------------------------
# merge invariants (pure functions — no devices needed)
# ---------------------------------------------------------------------------
def _merge_candidates(values: np.ndarray, splits, k: int, largest: bool):
    """Reference two-level comparator: local top-k per shard (global row
    indices tracked), concat in shard order, stable re-rank."""
    vals, idxs = [], []
    offset = 0
    for block in np.split(values, splits, axis=-2):
        v, i = merge.local_topk_candidates(
            jnp.asarray(block), k, largest=largest,
            row_offset=offset)
        vals.append(np.asarray(v))
        idxs.append(np.asarray(i))
        offset += block.shape[-2] * block.shape[-1]
    av = np.concatenate(vals, axis=-1)
    ai = np.concatenate(idxs, axis=-1)
    bv, bi = merge.rerank_candidates(jnp.asarray(av), jnp.asarray(ai), k,
                                     largest=largest)
    return np.asarray(bv), np.asarray(bi)


@given(st.integers(0, 10 ** 6), st.integers(1, 4), st.integers(1, 5))
@settings(max_examples=10, deadline=None)
def test_comparator_merge_split_invariant(seed, n_shards, k):
    """Local-k + gathered re-rank == global comparator, for ANY nv split
    (1 shard == the unsharded path), both directions."""
    rng = np.random.default_rng(seed)
    nv, R = 8, 5
    values = rng.standard_normal((3, nv, R)).astype(np.float32)
    splits = np.cumsum([nv // n_shards] * (n_shards - 1)).tolist()
    for largest in (False, True):
        gv, gi = merge.v_merge_comparator_topk(
            jnp.asarray(values), k, largest=largest)
        sv, si = _merge_candidates(values, splits, k, largest)
        np.testing.assert_array_equal(np.asarray(gi), si)
        np.testing.assert_allclose(np.asarray(gv), sv, rtol=0, atol=0)


@given(st.integers(0, 10 ** 6))
@settings(max_examples=8, deadline=None)
def test_comparator_merge_associative(seed):
    """Tree-reducing candidate lists == flat re-rank (associativity):
    rerank(rerank(A ++ B) ++ C) == rerank(A ++ B ++ C), ties included."""
    rng = np.random.default_rng(seed)
    k = 3
    # quantized values force ties across shards
    blocks = [np.round(rng.standard_normal((2, 4, 4)) * 2) / 2 for _ in
              range(3)]
    cands = []
    offset = 0
    for b in blocks:
        v, i = merge.local_topk_candidates(jnp.asarray(b.astype(np.float32)),
                                           k, largest=False,
                                           row_offset=offset)
        cands.append((np.asarray(v), np.asarray(i)))
        offset += b.shape[-2] * b.shape[-1]
    flat_v = jnp.asarray(np.concatenate([c[0] for c in cands], axis=-1))
    flat_i = jnp.asarray(np.concatenate([c[1] for c in cands], axis=-1))
    fv, fi = merge.rerank_candidates(flat_v, flat_i, k, largest=False)
    # tree: (A ++ B) first, then ++ C
    ab_v = jnp.asarray(np.concatenate([cands[0][0], cands[1][0]], axis=-1))
    ab_i = jnp.asarray(np.concatenate([cands[0][1], cands[1][1]], axis=-1))
    tv, ti = merge.rerank_candidates(ab_v, ab_i, k, largest=False)
    tv2 = jnp.concatenate([tv, jnp.asarray(cands[2][0])], axis=-1)
    ti2 = jnp.concatenate([ti, jnp.asarray(cands[2][1])], axis=-1)
    tv3, ti3 = merge.rerank_candidates(tv2, ti2, k, largest=False)
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(ti3))
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(tv3))


@given(st.integers(0, 10 ** 6), st.sampled_from((2, 4, 8)))
@settings(max_examples=8, deadline=None)
def test_comparator_merge_shard_order_permutation_invariant(seed, n_shards):
    """With continuous (tie-free) scores the merged winner set does not
    depend on the order shards contribute their candidates.  Shard counts
    are the divisors of nv, as on a bank mesh (nv is padded to a bank-axis
    multiple before sharding)."""
    rng = np.random.default_rng(seed)
    nv, R, k = 8, 4, 4
    values = rng.standard_normal((nv, R)).astype(np.float32)
    splits = np.split(np.arange(nv), n_shards)
    cands = []
    for shard in splits:
        v, i = merge.local_topk_candidates(
            jnp.asarray(values[shard]), k, largest=False,
            row_offset=int(shard[0]) * R)
        cands.append((np.asarray(v), np.asarray(i)))
    perm = rng.permutation(n_shards)
    v0 = jnp.asarray(np.concatenate([cands[j][0] for j in range(n_shards)]))
    i0 = jnp.asarray(np.concatenate([cands[j][1] for j in range(n_shards)]))
    vp = jnp.asarray(np.concatenate([cands[j][0] for j in perm]))
    ip = jnp.asarray(np.concatenate([cands[j][1] for j in perm]))
    bv0, bi0 = merge.rerank_candidates(v0, i0, k, largest=False)
    bvp, bip = merge.rerank_candidates(vp, ip, k, largest=False)
    np.testing.assert_array_equal(np.asarray(bi0), np.asarray(bip))
    np.testing.assert_allclose(np.asarray(bv0), np.asarray(bvp), atol=0)


@given(st.integers(0, 10 ** 6), st.integers(1, 4))
@settings(max_examples=8, deadline=None)
def test_gather_merge_split_invariant(seed, n_shards):
    """Concatenating per-shard match-line blocks in bank order == the
    unsharded gather, and first-k indices agree for every k."""
    rng = np.random.default_rng(seed)
    nv, R = 8, 5
    rows = (rng.random((2, nv, R)) < 0.3).astype(np.float32)
    full = merge.v_merge_gather(jnp.asarray(rows))
    splits = np.cumsum([nv // n_shards] * (n_shards - 1)).tolist()
    parts = [np.asarray(merge.v_merge_gather(jnp.asarray(b)))
             for b in np.split(rows, splits, axis=-2)]
    np.testing.assert_array_equal(np.asarray(full),
                                  np.concatenate(parts, axis=-1))
    for k in (1, 3, nv * R):
        ia = merge.first_k_indices(jnp.asarray(full), k)
        ib = merge.first_k_indices(
            jnp.asarray(np.concatenate(parts, axis=-1)), k)
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))


def test_first_k_indices_ignores_trailing_zero_banks():
    """Bank padding appends always-zero match lines; indices must not
    move (the sharded simulator slices the mask but reuses the indices)."""
    mask = jnp.asarray([[0.0, 1.0, 0.0, 1.0, 1.0, 0.0]])
    padded = jnp.pad(mask, ((0, 0), (0, 10)))
    for k in (1, 2, 4):
        np.testing.assert_array_equal(
            np.asarray(merge.first_k_indices(mask, k)),
            np.asarray(merge.first_k_indices(padded, k)))


# ---------------------------------------------------------------------------
# sense monotonicity: loosening the sensing limit never removes a match
# ---------------------------------------------------------------------------
@given(st.integers(0, 10 ** 6))
@settings(max_examples=10, deadline=None)
def test_sense_monotone_in_sensing_limit(seed):
    rng = np.random.default_rng(seed)
    dist = jnp.asarray(rng.random((2, 3, 2, 8)).astype(np.float32) * 4)
    row_valid = jnp.asarray((rng.random((3, 8)) < 0.9).astype(np.float32))
    limits = sorted(rng.random(4) * 3)
    for sensing in ("exact", "best", "threshold"):
        prev = None
        for sl in limits:
            m = np.asarray(subarray.sense(dist, sensing, float(sl),
                                          threshold=1.0,
                                          row_valid=row_valid))
            if prev is not None:
                assert (m >= prev).all(), (sensing, sl)
            prev = m

"""Deeper property tests on the CAM functional simulator's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import (AppConfig, ArchConfig, CAMASim, CAMConfig,
                        CircuitConfig, DeviceConfig)


def cfg_best(h_merge, bits=0, rows=8, cols=8, sl=0.0, k=1):
    cell = "acam" if bits == 0 else "mcam"
    return CAMConfig(
        app=AppConfig(distance="l2", match_type="best", match_param=k,
                      data_bits=bits),
        arch=ArchConfig(h_merge=h_merge, v_merge="comparator"),
        circuit=CircuitConfig(rows=rows, cols=cols, cell_type=cell,
                              sensing="best", sensing_limit=sl),
        device=DeviceConfig(device="fefet"))


# ---------------------------------------------------------------------------
# voting is an APPROXIMATION of adder: agreement high, never better recall
# of the true argmin than the lossless merge
# ---------------------------------------------------------------------------
@given(st.integers(0, 10 ** 6))
@settings(max_examples=15, deadline=None)
def test_adder_exact_where_voting_approximate(seed):
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    stored = jax.random.uniform(k1, (24, 32))
    q = jax.random.uniform(k2, (8, 32))
    d = np.square(np.asarray(stored)[None] - np.asarray(q)[:, None]
                  ).sum(-1)
    true_nn = d.argmin(1)

    sim_a = CAMASim(cfg_best("adder"))
    idx_a, _ = sim_a.query(sim_a.write(stored), q)
    # adder merge is lossless: always the true argmin (mod fp ties)
    for i, g in enumerate(np.asarray(idx_a[:, 0])):
        assert d[i, g] == pytest.approx(d[i, true_nn[i]], rel=1e-5,
                                        abs=1e-6)

    sim_v = CAMASim(cfg_best("voting"))
    idx_v, _ = sim_v.query(sim_v.write(stored), q)
    # voting is approximate but must return valid indices
    got = np.asarray(idx_v[:, 0])
    assert ((got >= 0) & (got < 24)).all()


# ---------------------------------------------------------------------------
# quantization monotonicity: more bits never hurts the retrieved distance
# (on average over queries)
# ---------------------------------------------------------------------------
def test_more_bits_better_retrieval():
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    stored = jax.random.uniform(k1, (64, 64))
    q = jax.random.uniform(k2, (32, 64))
    d = np.square(np.asarray(stored)[None] - np.asarray(q)[:, None]
                  ).sum(-1)

    def mean_retrieved_distance(bits):
        sim = CAMASim(cfg_best("adder", bits=bits, rows=16, cols=16))
        idx, _ = sim.query(sim.write(stored), q)
        return float(np.mean([d[i, g] for i, g in
                              enumerate(np.asarray(idx[:, 0]))]))

    d2, d3, d5 = (mean_retrieved_distance(b) for b in (2, 3, 5))
    assert d5 <= d3 + 1e-3
    assert d3 <= d2 + 1e-3


# ---------------------------------------------------------------------------
# duplicates: exact match must return ALL duplicates (gather completeness)
# ---------------------------------------------------------------------------
@given(st.integers(1, 6), st.integers(0, 10 ** 6))
@settings(max_examples=15, deadline=None)
def test_exact_match_finds_all_duplicates(n_dup, seed):
    key = jax.random.PRNGKey(seed)
    base = (jax.random.uniform(key, (20, 16)) > 0.5).astype(jnp.float32)
    row = base[3]
    stored = jnp.concatenate([base, jnp.tile(row[None], (n_dup, 1))])
    cfg = CAMConfig(
        app=AppConfig(distance="hamming", match_type="exact",
                      match_param=8, data_bits=1),
        arch=ArchConfig(h_merge="and", v_merge="gather"),
        circuit=CircuitConfig(rows=8, cols=8, cell_type="tcam",
                              sensing="exact"),
        device=DeviceConfig(device="cmos"))
    sim = CAMASim(cfg)
    _, mask = sim.query(sim.write(stored), row[None])
    found = set(np.where(np.asarray(mask[0]) > 0)[0].tolist())
    expected = {i for i in range(stored.shape[0])
                if (np.asarray(stored[i]) == np.asarray(row)).all()}
    assert found == expected


# ---------------------------------------------------------------------------
# C2C noise statistics: fraction of flipped best-matches grows with STD
# ---------------------------------------------------------------------------
def test_c2c_flip_rate_increases_with_std():
    key = jax.random.PRNGKey(1)
    stored = jax.random.uniform(key, (40, 32))
    q = jnp.tile(stored[7][None], (32, 1))

    def flips(std):
        cfg = cfg_best("adder", bits=3, rows=8, cols=8)
        cfg = cfg.replace(device=dict(variation="c2c",
                                      variation_std=std))
        sim = CAMASim(cfg)
        idx, _ = sim.query(sim.write(stored), q,
                           key=jax.random.PRNGKey(2))
        return float(np.mean(np.asarray(idx[:, 0]) != 7))

    f0, f1, f2 = flips(0.0), flips(1.0), flips(4.0)
    assert f0 == 0.0
    assert f2 >= f1 - 0.05
    assert f2 > 0.1


# ---------------------------------------------------------------------------
# kernel-backed functional sim == pure-jnp functional sim
# ---------------------------------------------------------------------------
@given(st.integers(0, 10 ** 6))
@settings(max_examples=10, deadline=None)
def test_kernel_backend_equivalence(seed):
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    stored = jax.random.uniform(k1, (30, 40))
    q = jax.random.uniform(k2, (4, 40))
    cfg = cfg_best("adder", bits=3, rows=8, cols=8, k=3)
    a = CAMASim(cfg, use_kernel=False)
    b = CAMASim(cfg, use_kernel=True)
    ia, _ = a.query(a.write(stored), q)
    ib, _ = b.query(b.write(stored), q)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))


# ---------------------------------------------------------------------------
# hierarchical CAM merge == global merge (multi-device, subprocess)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_hierarchical_merge_equals_global():
    import os
    import subprocess
    import sys
    script = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"   # host-device trick needs the CPU backend
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models.cam_attention import (cam_decode_attention,
                                        cam_decode_attention_hierarchical)
from repro.runtime import sharding_ctx
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
B, S, H, KVH, D = 4, 64, 6, 2, 16
cfg = get_config("chameleon-34b").reduced().replace(cam_topk=8)
k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(k1, (B, H, D))
kc = jax.random.normal(k2, (B, S, KVH, D))
vc = jax.random.normal(k3, (B, S, KVH, D))
pos = jnp.asarray([63, 40, 17, 5], jnp.int32)
ref = cam_decode_attention(q, kc, vc, pos, cfg)
with sharding_ctx(mesh):
    hier = jax.jit(lambda *a: cam_decode_attention_hierarchical(*a, cfg))(
        q, kc, vc, pos)
err = float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                            - hier.astype(jnp.float32))))
assert err < 2e-2, err
print("HIER_OK")
'''
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0 and "HIER_OK" in proc.stdout, \
        proc.stderr[-2000:]

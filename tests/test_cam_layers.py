"""CAM-integrated LM layers: retrieval attention, CAM MoE router, CAM
episodic memory."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.attention import decode_attention
from repro.models.cam_attention import (cam_decode_attention,
                                        cam_decode_attention_pallas,
                                        cam_select_scores)

KEY = jax.random.PRNGKey(0)


def _setup(B=2, S=64, H=4, KVH=2, D=16, pos=None):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (B, H, D))
    kc = jax.random.normal(k2, (B, S, KVH, D))
    vc = jax.random.normal(k3, (B, S, KVH, D))
    pos = jnp.full((B,), S - 1, jnp.int32) if pos is None else pos
    return q, kc, vc, pos


def test_cam_attention_full_topk_equals_dense():
    """With k >= S the CAM retrieval set is everything -> exact match with
    dense decode attention."""
    q, kc, vc, pos = _setup()
    cfg = get_config("granite-8b").reduced().replace(cam_topk=64)
    a = cam_decode_attention(q, kc, vc, pos, cfg)
    b = decode_attention(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=2e-2,
                               atol=2e-2)


def test_cam_attention_respects_pos_mask():
    q, kc, vc, _ = _setup()
    pos = jnp.asarray([3, 10], jnp.int32)
    cfg = get_config("granite-8b").reduced().replace(cam_topk=8)
    # poison the cache beyond pos: results must not change
    kc2 = kc.at[0, 5:].set(1e3)
    vc2 = vc.at[0, 5:].set(1e3)
    a = cam_decode_attention(q, kc, vc, pos, cfg)
    b = cam_decode_attention(q, kc2, vc2, pos, cfg)
    np.testing.assert_allclose(np.asarray(a[0], np.float32),
                               np.asarray(b[0], np.float32), atol=1e-4)


def test_cam_attention_retrieves_strong_match():
    """A planted high-similarity key must dominate the output."""
    B, S, H, KVH, D = 1, 32, 2, 1, 8
    q = jnp.ones((B, H, D)) * 2.0
    kc = jax.random.normal(KEY, (B, S, KVH, D)) * 0.01
    kc = kc.at[0, 17].set(5.0)               # strong match at position 17
    vc = jnp.zeros((B, S, KVH, D)).at[0, 17].set(7.0)
    cfg = get_config("granite-8b").reduced().replace(cam_topk=4)
    out = cam_decode_attention(q, kc, vc,
                               jnp.asarray([S - 1], jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(out, np.float32), 7.0, atol=0.1)


def test_cam_attention_pallas_matches_xla():
    q, kc, vc, pos = _setup(S=128)
    cfg = get_config("granite-8b").reduced().replace(cam_topk=16,
                                                     cam_chunk=32)
    a = cam_decode_attention(q, kc, vc, pos, cfg)
    b = cam_decode_attention_pallas(q, kc, vc, pos, cfg)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=2e-2,
                               atol=2e-2)


def test_cam_attention_quantized_still_works():
    q, kc, vc, pos = _setup()
    cfg = get_config("granite-8b").reduced().replace(cam_topk=8,
                                                     cam_attn_bits=3)
    out = cam_decode_attention(q, kc, vc, pos, cfg)
    assert bool(jnp.isfinite(out).all())


def test_cam_select_scores_mla():
    s = jax.random.normal(KEY, (2, 4, 32))
    cfg = get_config("minicpm3-4b").reduced().replace(cam_topk=5)
    pos = jnp.asarray([31, 15], jnp.int32)
    out = cam_select_scores(s, pos, cfg)
    kept = np.isfinite(np.asarray(out)) & (np.asarray(out) > -1e29)
    assert (kept.sum(-1) <= 5).all()
    # batch 1: nothing beyond pos 15 survives
    assert not kept[1, :, 16:].any()


# ---------------------------------------------------------------------------
# CAM MoE router
# ---------------------------------------------------------------------------
def test_cam_router_topk_shape_and_validity():
    from repro.models import moe as M
    from repro.models import layers as L
    cfg = get_config("deepseek-moe-16b").reduced().replace(
        cam_router=True, cam_router_bits=3)
    params = L.init_params(KEY, M.moe_spec(cfg))
    x = jax.random.normal(KEY, (10, cfg.d_model)).astype(jnp.bfloat16)
    idx, w = M.route(params, cfg, x)
    assert idx.shape == (10, cfg.moe_top_k)
    assert ((np.asarray(idx) >= 0)
            & (np.asarray(idx) < cfg.n_experts)).all()
    np.testing.assert_allclose(np.asarray(w.sum(-1), np.float32), 1.0,
                               atol=1e-2)
    # top-k distinct per token
    for row in np.asarray(idx):
        assert len(set(row.tolist())) == cfg.moe_top_k


def test_cam_router_quantization_changes_routing_somewhere():
    from repro.models import moe as M
    from repro.models import layers as L
    cfg_fp = get_config("deepseek-moe-16b").reduced().replace(
        cam_router=True, cam_router_bits=0)
    cfg_q = cfg_fp.replace(cam_router_bits=2)
    params = L.init_params(KEY, M.moe_spec(cfg_fp))
    x = jax.random.normal(KEY, (64, cfg_fp.d_model)).astype(jnp.bfloat16)
    i1, _ = M.route(params, cfg_fp, x)
    i2, _ = M.route(params, cfg_q, x)
    assert (np.asarray(i1) != np.asarray(i2)).any()


def test_moe_ep_mode_matches_tp_single_device():
    """EP and TP shard_map modes agree on a 1-device mesh (no drops)."""
    from repro.models import moe as M
    from repro.models import layers as L
    from repro.runtime import sharding_ctx
    cfg = get_config("deepseek-moe-16b").reduced()
    params = L.init_params(KEY, M.moe_spec(cfg))
    x = jax.random.normal(KEY, (8, cfg.d_model)).astype(jnp.bfloat16)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with sharding_ctx(mesh):
        tp = M.moe_block(params, cfg, x, mode="tp")
        ep = M.moe_block(params, cfg, x, mode="ep")
    np.testing.assert_allclose(np.asarray(tp, np.float32),
                               np.asarray(ep, np.float32), rtol=5e-2,
                               atol=5e-2)


# ---------------------------------------------------------------------------
# CAM episodic memory
# ---------------------------------------------------------------------------
def test_cam_memory_classification():
    from repro.core import (AppConfig, ArchConfig, CAMConfig,
                            CircuitConfig, DeviceConfig)
    from repro.models.cam_memory import CAMMemory, accuracy
    cfg = CAMConfig(
        app=AppConfig(distance="l2", match_type="best", match_param=3,
                      data_bits=3),
        arch=ArchConfig(h_merge="voting", v_merge="comparator"),
        circuit=CircuitConfig(rows=16, cols=32, cell_type="mcam",
                              sensing="best"),
        device=DeviceConfig(device="fefet"))
    mem = CAMMemory(cfg)
    protos = jax.random.normal(KEY, (4, 64))
    keys = jnp.repeat(protos, 8, axis=0) + 0.05 * jax.random.normal(
        jax.random.PRNGKey(1), (32, 64))
    labels = jnp.repeat(jnp.arange(4), 8)
    mem.write(keys, labels)
    queries = protos + 0.05 * jax.random.normal(jax.random.PRNGKey(2),
                                                (4, 64))
    acc = accuracy(mem, queries, jnp.arange(4))
    assert acc == 1.0
    perf = mem.perf()
    assert perf["latency_ns"] > 0 and perf["energy_pj"] > 0


def test_moe_a2a_mode_matches_reference():
    """a2a expert parallelism == local reference (ample capacity)."""
    import subprocess, sys, os
    script = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import moe as M
from repro.models import layers as L
from repro.runtime import sharding_ctx
cfg = get_config("deepseek-moe-16b").reduced().replace(moe_capacity_factor=8.0)
params = L.init_params(jax.random.PRNGKey(0), M.moe_spec(cfg))
x = (0.5*jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))).astype(jnp.bfloat16)
ref = M.moe_block(params, cfg, x)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with sharding_ctx(mesh):
    a = jax.jit(lambda p, x: M.moe_block(p, cfg, x, mode="a2a"))(params, x)
err = float(jnp.max(jnp.abs(ref.astype(jnp.float32)-a.astype(jnp.float32))))
assert err < 0.05, err
print("A2A_TEST_OK")
'''
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0 and "A2A_TEST_OK" in proc.stdout, \
        proc.stderr[-2000:]

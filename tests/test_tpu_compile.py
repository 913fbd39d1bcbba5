"""The main path's Pallas kernels compile for a TPU v5e.

Each test compiles one kernel at the on-chip smoke's geometry — a
1,048,576-row x 128-dim store in R = C = 128 subarrays, nv = 8192 banks
(8224 with the smoke's 4096 spare rows), a 256-query batch — for a
described (not attached) ``v5e:2x2`` topology, and checks that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).  Nothing
runs: this catches what interpret mode cannot (block tiling, VMEM limits,
ops Mosaic cannot lower) at no chip time.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU compiler library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import cam_search, hamming_pack

R = C = 128
Q = 256
BANKS = (8192, 8224)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def v5e_model(monkeypatch):
    """Size kernel blocks with the v5e's entry of the kernel model (the
    process's own devices are CPUs)."""
    monkeypatch.setattr(cam_search, "_device_kind", lambda: "TPU v5 lite")
    jax.clear_caches()
    yield cam_search.device_model()
    jax.clear_caches()


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _point_args(one_chip, nv, dtype):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return (sds((nv, 1, R, C), dtype), sds((Q, 1, C), dtype),
            sds((1, C), jnp.float32), sds((nv, R), jnp.float32))


@pytest.mark.parametrize("nv", BANKS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8],
                         ids=["f32", "int8"])
def test_fused_point_kernel_compiles(one_chip, v5e_model, nv, dtype):
    """The served l2 best-match kernel: f32 codes plus device noise, and
    the int8 codes of a noise-free store."""
    def search(s, q, cv, rv):
        return cam_search.cam_search_fused_pallas(
            s, q, cv, rv, distance="l2", sensing="best", want_dist=True,
            interpret=False)
    _compile(search, *_point_args(one_chip, nv, dtype))


@pytest.mark.parametrize("nv", BANKS)
def test_fused_point_kernel_per_tile_grid_compiles(one_chip, v5e_model, nv):
    """``pipeline=False``: the per-(R, C)-tile grid."""
    def search(s, q, cv, rv):
        return cam_search.cam_search_fused_pallas(
            s, q, cv, rv, distance="l2", sensing="best", want_dist=True,
            interpret=False, pipeline=False)
    _compile(search, *_point_args(one_chip, nv, jnp.float32))


@pytest.mark.parametrize("nv", BANKS)
def test_fused_range_kernel_compiles(one_chip, v5e_model, nv):
    """The ACAM [lo, hi] range kernel, exact match, match lines only."""
    def search(lo, hi, q, cv, rv):
        return cam_search.cam_range_fused_pallas(
            lo, hi, q, cv, rv, sensing="exact", want_dist=False,
            interpret=False)
    s, q, cv, rv = _point_args(one_chip, nv, jnp.float32)
    _compile(search, s, s, q, cv, rv)


@pytest.mark.parametrize("nv", BANKS)
def test_packed_hamming_prefilter_kernel_compiles(one_chip, v5e_model, nv):
    """The cascade's bank prefilter: 128-bit row signatures packed into
    four uint32 words, XOR + popcount against the query batch."""
    def scores(s, q):
        return hamming_pack.hamming_packed_batched_pallas(
            s, q, tile_r=256, interpret=False)
    _compile(scores,
             jax.ShapeDtypeStruct((nv * R, 4), jnp.uint32,
                                  sharding=one_chip),
             jax.ShapeDtypeStruct((Q, 4), jnp.uint32, sharding=one_chip))


@pytest.mark.parametrize("nv", BANKS)
def test_vmem_model_fits_the_v5e_budget(v5e_model, nv):
    """The block and Q-tile the driver picks at this geometry fit the
    budget it passes to Mosaic as ``vmem_limit_bytes``."""
    budget = v5e_model["vmem_budget_bytes"]
    for itemsize in (4, 1):
        vb = cam_search.resident_banks(nv, 1, R, C, itemsize=itemsize,
                                       budget_bytes=budget)
        qt = cam_search.choose_q_tile(R, C, banks=nv, segs=1,
                                      itemsize=itemsize,
                                      budget_bytes=budget)
        assert vb and nv % vb == 0 and qt % 8 == 0
        assert cam_search.fused_vmem_bytes(
            vb, qt, 1, R, C, itemsize=itemsize) <= budget

"""``chip_smoke.py`` at a tiny size on the CPU, in interpret mode.

The smoke's phase functions run in-process with the same checks they make
on the chip — jnp-reference agreement, deleted ids absent, inserted rows
found — so the script cannot rot between chip runs.  The platform gate
stays in ``main()``: on the CPU it refuses to run and prints no result.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_without_a_tpu(capsys):
    assert _smoke().main([]) != 0
    assert capsys.readouterr().out == ""


def test_serve_phase_tiny_passes_every_check():
    out = _smoke().serve_phase(rows_total=600, seed=3, dims=40, rows=16,
                               cols=16, batch=8, n_batches=4, n_insert=12,
                               n_clusters=16, check_kernel_program=False)
    assert out["batches"] == 6          # 4 before the mutations, 2 after
    assert sum(c["queries"] for c in out["checks"]) == 6 * 8
    assert all(c["mismatches"] == 0 for c in out["checks"])


def test_search_program_text_lowers_the_served_step():
    import jax
    import jax.numpy as jnp

    from repro.core import CAMASim
    smoke = _smoke()
    cam = CAMASim(smoke.serve_config(64, rows=8, cols=8, batch=4))
    state = cam.write(jax.random.uniform(jax.random.PRNGKey(0), (40, 12)))
    text = smoke.search_program_text(
        cam, state, jnp.zeros((4, 12)), jax.random.PRNGKey(1))
    assert "HloModule" in text


SHARDED = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
out = smoke.sharded_phase(rows_per_chip=256, seed=5, chips=4, dims=40,
                          rows=16, cols=16, n_queries=8, n_clusters=16)
print("SHARDED_OK", out["query_s"] > 0)
"""


@pytest.mark.multidevice
def test_sharded_phase_tiny_on_four_host_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable, "-c", SHARDED, os.path.join(ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED_OK True" in proc.stdout


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set;
    without it the cache goes to the repository's git-ignored
    ``.jax_cache``."""
    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(os.path.abspath(ROOT), ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == (
            want if env_dir is None else before)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()

"""Dry-run machinery smoke test.

Runs in a SUBPROCESS because the dry-run forces 512 host devices via
XLA_FLAGS before jax initializes (the main pytest process stays at 1
device).  One small cell per step-kind proves lower+compile+probe works;
the full 40-cell x 2-mesh sweep is executed by ``python -m
repro.launch.dryrun --all --mesh both`` (see EXPERIMENTS.md).
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"   # host-device trick needs the CPU backend
import json
import jax
from repro.launch.dryrun import lower_kind, probe_costs
from repro.configs import get_config
from repro.runtime import ShardingRules

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = ShardingRules()
out = {}
cfg = get_config("qwen2-1.5b").replace(n_layers=2, d_model=256,
                                       n_heads=4, n_kv_heads=2, d_head=64,
                                       d_ff=512, vocab_size=2048)
for kind, batch, seq in (("train", 8, 256), ("prefill", 4, 256),
                         ("decode", 8, 256)):
    lowered = lower_kind(cfg, kind, batch, seq, mesh, rules)
    compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    costs, colls = probe_costs(cfg, kind, batch, seq, mesh, rules, "tp")
    out[kind] = {
        "flops": float(cost.get("flops", 0.0)),
        "probe_flops": costs["flops"],
        "arg_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "collective_ops": sorted(colls),
    }
print("RESULT:" + json.dumps(out))
"""


@pytest.mark.slow
def test_dryrun_lowers_all_step_kinds():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT:")][-1]
    out = json.loads(line[len("RESULT:"):])
    for kind in ("train", "prefill", "decode"):
        assert out[kind]["probe_flops"] > 0, out[kind]
        assert out[kind]["arg_bytes"] > 0
    # probe-corrected flops exceed the scanned artifact's body-once count
    assert out["train"]["probe_flops"] > out["train"]["flops"] * 1.2
    # sharded compute must induce collectives
    assert out["train"]["collective_ops"], out["train"]


def test_collective_parser():
    from repro.roofline import parse_collectives
    hlo = """
  %ar = f32[16,128]{1,0} all-reduce(f32[16,128]{1,0} %x), replica_groups={{0,1,2,3}}
  %ag.1 = bf16[4,256]{1,0} all-gather(bf16[1,256]{1,0} %y), replica_groups=[2,4]<=[8]
  %rs = f32[8]{0} reduce-scatter(f32[32]{0} %z), replica_groups={{0,1,2,3}}
  %cp = f32[64]{0} collective-permute(f32[64]{0} %w)
  %dot = f32[8,8]{1,0} dot(f32[8,8]{1,0} %a, f32[8,8]{1,0} %b)
"""
    stats = parse_collectives(hlo, 8)
    assert set(stats.ops) == {"all-reduce", "all-gather",
                              "reduce-scatter", "collective-permute"}
    ar = stats.ops["all-reduce"]
    assert ar["result_bytes"] == 16 * 128 * 4
    assert ar["wire_bytes"] == pytest.approx(2 * 16 * 128 * 4 * 3 / 4)
    ag = stats.ops["all-gather"]
    assert ag["result_bytes"] == 4 * 256 * 2
    rs = stats.ops["reduce-scatter"]
    assert rs["wire_bytes"] == pytest.approx(8 * 4 * 3)


def test_roofline_terms():
    from repro.roofline import Roofline
    r = Roofline(arch="x", shape="train_4k", mesh="single", chips=256,
                 flops_per_device=197e12 * 0.01,       # 10 ms compute
                 bytes_per_device=819e9 * 0.002,       # 2 ms memory
                 wire_bytes_per_device=50e9 * 0.02,    # 20 ms collective
                 model_flops_global=197e12 * 0.01 * 256 * 0.5)
    assert r.bottleneck == "collective"
    assert r.t_bound == pytest.approx(0.02)
    assert r.useful_flops_ratio == pytest.approx(0.5)
    assert r.roofline_fraction == pytest.approx(0.01 * 0.5 / 0.02)

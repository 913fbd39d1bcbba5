import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # fake host devices need CPU

"""Multi-pod dry-run: lower + compile every (arch x shape) on the
production meshes, prove memory fits, and extract roofline inputs.

    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch granite-8b --shape train_4k --mesh single

Cost accounting: XLA's cost_analysis counts a lax.scan body ONCE, so a
scanned L-layer stack under-reports by ~L.  Each cell therefore runs:

  1. the FULL config (flash attention, scanned, microbatched) — this is the
     artifact that must compile and fit memory (memory_analysis), and
  2. two cheap cost PROBES at L1/L2 layers with attn_impl='naive' (identical
     FLOPs to our flash, but no inner scans) — per-layer costs are the
     (L2-L1) delta, extrapolated to the real depth; constant-in-L terms
     (embeddings, loss, optimizer intercept) live in the intercept.

Results are cached incrementally under experiments/dryrun/<tag>/ as JSON;
EXPERIMENTS.md §Dry-run / §Roofline and the perf loop read from there.
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import models
from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.configs.base import ModelConfig
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import batch_specs, decode_input_specs, input_specs
from repro.optim import AdamW, constant
from repro.roofline import Roofline, model_flops, parse_collectives
from repro.runtime import (ShardingRules, abstract_state, make_train_step,
                           sharding_ctx, state_axes, tree_shardings)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")

# per-(arch, shape) microbatch so the big dense archs fit HBM at train_4k
MICROBATCH: Dict[tuple, int] = {
    ("chameleon-34b", "train_4k"): 4,
    ("granite-20b", "train_4k"): 4,
    ("zamba2-7b", "train_4k"): 2,
    ("granite-8b", "train_4k"): 2,
    ("minicpm3-4b", "train_4k"): 2,
}


# ---------------------------------------------------------------------------
# Lowering one step function for an explicit config
# ---------------------------------------------------------------------------
def lower_kind(cfg: ModelConfig, kind: str, batch: int, seq: int, mesh,
               rules: ShardingRules, moe_mode: str = "tp",
               microbatch: Optional[int] = None):
    with sharding_ctx(mesh, rules):
        if kind == "train":
            inputs, axes = batch_specs(cfg, batch, seq)
            opt = AdamW(lr=constant(1e-4))
            step = make_train_step(cfg, opt, moe_mode=moe_mode,
                                   microbatch=microbatch)
            state = abstract_state(cfg)
            st_sh = tree_shardings(state_axes(cfg), state, mesh, rules,
                                   fsdp=True)
            in_sh = tree_shardings(axes, inputs, mesh, rules, fsdp=False)
            return jax.jit(
                step, in_shardings=(st_sh, in_sh),
                out_shardings=(st_sh, None), donate_argnums=(0,),
            ).lower(state, inputs)
        if kind == "prefill":
            inputs, axes = batch_specs(cfg, batch, seq)
            inputs.pop("labels"), axes.pop("labels")
            params = models.abstract_params(cfg)
            p_sh = tree_shardings(models.param_axes(cfg), params, mesh,
                                  rules, fsdp=True)
            in_sh = tree_shardings(axes, inputs, mesh, rules, fsdp=False)
            cache_s, cache_axes = models.cache_specs(cfg, batch, seq)
            c_sh = tree_shardings(cache_axes, cache_s, mesh, rules,
                                  fsdp=False)

            def prefill(params, b):
                return models.forward_prefill(params, cfg, b,
                                              moe_mode=moe_mode)

            return jax.jit(prefill, in_shardings=(p_sh, in_sh),
                           out_shardings=(None, c_sh)
                           ).lower(params, inputs)
        # decode
        inputs, axes = decode_input_specs(cfg, batch, seq)
        params = models.abstract_params(cfg)
        p_sh = tree_shardings(models.param_axes(cfg), params, mesh, rules,
                              fsdp=False)
        tok_sh = tree_shardings(axes["inputs"], inputs["inputs"], mesh,
                                rules, fsdp=False)
        pos_sh = tree_shardings({"p": axes["pos"]}, {"p": inputs["pos"]},
                                mesh, rules, fsdp=False)["p"]
        c_sh = tree_shardings(axes["cache"], inputs["cache"], mesh, rules,
                              fsdp=False)

        def serve(params, cache, inp, pos):
            return models.forward_decode(params, cfg, inp, pos, cache,
                                         moe_mode=moe_mode)

        return jax.jit(serve, in_shardings=(p_sh, c_sh, tok_sh, pos_sh),
                       out_shardings=(None, c_sh), donate_argnums=(1,),
                       ).lower(params, inputs["cache"], inputs["inputs"],
                               inputs["pos"])


# ---------------------------------------------------------------------------
# Cost probes (scan-body correction)
# ---------------------------------------------------------------------------
def _extract_costs(compiled, chips: int) -> Dict[str, float]:
    cost = compiled.cost_analysis() or {}
    coll = parse_collectives(compiled.as_text(), chips)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "wire_bytes": coll.wire_bytes,
        "collectives": coll.ops,
    }


def probe_costs(cfg: ModelConfig, kind: str, batch: int, seq: int, mesh,
                rules: ShardingRules, moe_mode: str
                ) -> Tuple[Dict[str, float], Dict]:
    """Two-point UNROLLED probe -> per-layer extrapolation to real depth.

    FLOPs + collectives come from attn_impl='naive' probes (identical
    FLOPs to flash, no inner scans to undercount); bytes come from
    attn_impl='flash' probes (no fake S^2 HBM traffic).  Scanned configs
    can't be probed directly: XLA counts a while body once regardless of
    trip count (verified empirically — see EXPERIMENTS.md §Dry-run).
    """
    if cfg.family == "hybrid":
        L1, L2 = cfg.hybrid_attn_every, 2 * cfg.hybrid_attn_every
    else:
        L1, L2 = 1, 2
    chips = mesh.devices.size
    Lfull = cfg.n_layers
    scale = (Lfull - L1) / (L2 - L1)

    def extrap(a, b):
        return max(0.0, a + (b - a) * scale)

    def probe_pair(attn_impl: str):
        out = []
        for L in (L1, L2):
            pcfg = cfg.replace(n_layers=L, attn_impl=attn_impl,
                               scan_layers=False, moe_probe_balanced=True)
            lowered = lower_kind(pcfg, kind, batch, seq, mesh, rules,
                                 moe_mode=moe_mode, microbatch=None)
            out.append(_extract_costs(lowered.compile(), chips))
        return out

    # naive probes are honest for BOTH flops and bytes: the pure-JAX flash
    # path spills its score tiles to HBM between ops, so its true traffic
    # matches the naive S^2 count (the Pallas fused-attention §Perf change
    # is what cuts it — measured there with its own probe).
    flop_probes = probe_pair(cfg.attn_impl if cfg.attn_impl != "flash"
                             else "naive")  # 'skip' passes through

    out = {
        "flops": extrap(flop_probes[0]["flops"], flop_probes[1]["flops"]),
        "bytes": extrap(flop_probes[0]["bytes"], flop_probes[1]["bytes"]),
        "wire_bytes": extrap(flop_probes[0]["wire_bytes"],
                             flop_probes[1]["wire_bytes"]),
    }
    colls = {}
    ops = set(flop_probes[0]["collectives"]) | set(
        flop_probes[1]["collectives"])
    for op in ops:
        e1 = flop_probes[0]["collectives"].get(
            op, {"count": 0, "result_bytes": 0.0, "wire_bytes": 0.0})
        e2 = flop_probes[1]["collectives"].get(
            op, {"count": 0, "result_bytes": 0.0, "wire_bytes": 0.0})
        colls[op] = {k: extrap(e1[k], e2[k]) for k in e1}
    return out, colls


# ---------------------------------------------------------------------------
# One full cell
# ---------------------------------------------------------------------------
def run_cell(arch: str, shape: str, mesh_kind: str = "single",
             moe_mode: str = "tp", microbatch: Optional[int] = None,
             rules: Optional[ShardingRules] = None,
             cfg_override=None, fused_attn: bool = False,
             tag: str = "baseline", save: bool = True,
             verbose: bool = True, probe: bool = True) -> dict:
    cell = input_specs(arch, shape)
    cfg = cfg_override(cell.cfg) if cfg_override else cell.cfg
    rules = rules or ShardingRules()
    if microbatch is None:
        microbatch = MICROBATCH.get((arch, shape))
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size

    # 1) the full artifact: must lower, compile, and fit
    t0 = time.time()
    lowered = lower_kind(cfg, cell.kind, cell.batch, cell.seq, mesh, rules,
                         moe_mode=moe_mode, microbatch=microbatch)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes",
                                  None),
        }
    except Exception as e:  # pragma: no cover
        mem_d = {"error": str(e)}

    # 2) probe-corrected costs
    if probe:
        pcfg = cfg.replace(attn_impl="skip") if fused_attn else cfg
        costs, colls = probe_costs(pcfg, cell.kind, cell.batch, cell.seq,
                                   mesh, rules, moe_mode)
        if fused_attn:
            inj = fused_attention_cost(cfg, cell.kind, cell.batch,
                                       cell.seq, mesh)
            costs["flops"] += inj["flops"]
            costs["bytes"] += inj["bytes"]
    else:
        costs = _extract_costs(compiled, chips)
        colls = costs.pop("collectives")

    mf = model_flops(cfg, cell.kind, cell.tokens_per_step)
    roof = Roofline(
        arch=arch, shape=shape, mesh=mesh_kind, chips=chips,
        flops_per_device=costs["flops"],
        bytes_per_device=costs["bytes"],
        wire_bytes_per_device=costs["wire_bytes"],
        model_flops_global=mf,
        collectives=colls,
        memory_per_device=mem_d,
    )
    out = {
        "tag": tag, "arch": arch, "shape": shape, "mesh": mesh_kind,
        "chips": chips, "kind": cell.kind, "moe_mode": moe_mode,
        "microbatch": microbatch,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "roofline": roof.to_dict(),
    }
    if save:
        d = os.path.join(RESULTS_DIR, tag, mesh_kind)
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, f"{arch}__{shape}.json"), "w") as f:
            json.dump(out, f, indent=1)
    if verbose:
        r = roof
        mem_gb = (mem_d.get("argument_bytes") or 0) / 2 ** 30
        print(f"[{tag}/{mesh_kind}] {arch} x {shape} ({cell.kind}): OK "
              f"lower={t_lower:.0f}s compile={t_compile:.0f}s "
              f"args={mem_gb:.2f}GiB/dev | "
              f"t_comp={r.t_compute*1e3:.2f}ms t_mem={r.t_memory*1e3:.2f}ms "
              f"t_coll={r.t_collective*1e3:.2f}ms -> {r.bottleneck} "
              f"useful={r.useful_flops_ratio:.2f} "
              f"frac={r.roofline_fraction:.3f}", flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--moe-mode", default="tp", choices=["tp", "ep"])
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-probe", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for mesh_kind in meshes:
        for arch in archs:
            for shape in shapes:
                path = os.path.join(RESULTS_DIR, args.tag, mesh_kind,
                                    f"{arch}__{shape}.json")
                if args.skip_existing and os.path.exists(path):
                    print(f"skip {arch} x {shape} ({mesh_kind})",
                          flush=True)
                    continue
                try:
                    run_cell(arch, shape, mesh_kind,
                             moe_mode=args.moe_mode,
                             microbatch=args.microbatch, tag=args.tag,
                             probe=not args.no_probe)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape, mesh_kind, str(e)[:200]))
                    print(f"[{mesh_kind}] {arch} x {shape}: FAIL {e}",
                          flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nALL DRY-RUNS PASSED")


if __name__ == "__main__":
    main()


# ---------------------------------------------------------------------------
# Fused-attention cost injection (§Perf: the Pallas flash kernel)
# ---------------------------------------------------------------------------
def fused_attention_cost(cfg: ModelConfig, kind: str, batch: int, seq: int,
                         mesh) -> Dict[str, float]:
    """Per-device flops/bytes of kernels/flash_attention.py, injected when
    probes run attn_impl='skip' (the kernel is a custom call XLA cannot
    cost).  Causal tiles above the diagonal are skipped by the kernel
    (0.5x), K/V restream once per q tile, and train counts fwd + remat
    re-fwd + bwd(~2x fwd).
    """
    if cfg.n_heads == 0 or kind == "decode":
        return {"flops": 0.0, "bytes": 0.0}
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    m = sizes.get("model", 1)
    dp = 1
    for a in ("pod", "data"):
        dp *= sizes.get(a, 1)
    B = batch // dp if batch % dp == 0 else batch
    H = cfg.n_heads // m if cfg.n_heads % m == 0 else cfg.n_heads
    KVH = (cfg.n_kv_heads // m if cfg.n_kv_heads % m == 0
           else cfg.n_kv_heads)
    if cfg.attention == "mla":
        Dk = cfg.qk_nope_dim + cfg.qk_rope_dim
        Dv = cfg.v_head_dim
        KVH = H
    else:
        Dk = Dv = cfg.head_dim
    S = seq
    n_attn = (cfg.n_layers // cfg.hybrid_attn_every
              if cfg.family == "hybrid" else cfg.n_layers)
    fwd_flops = 2.0 * B * S * S * (H * Dk + H * Dv) * 0.5   # qk + pv, causal
    mult_f = 4.0 if kind == "train" else 1.0                # fwd+refwd+2bwd
    q_tile = 512
    nq = max(1, S // q_tile)
    qkvo = B * S * (2 * H * Dk + KVH * (Dk + Dv)) * 2.0     # q,o + k,v HBM
    restream = nq * B * S * KVH * (Dk + Dv) * 2.0           # k,v per q tile
    mult_b = 3.0 if kind == "train" else 1.0
    return {"flops": n_attn * fwd_flops * mult_f,
            "bytes": n_attn * (qkvo + restream) * mult_b}

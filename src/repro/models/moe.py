"""Mixture-of-Experts block (deepseek-moe / moonshot style: shared experts +
fine-grained routed experts, top-k).

Grouped GEMMs use ``lax.ragged_dot`` after an argsort dispatch (dropless).
Two distribution modes, both implemented with ``jax.shard_map``:

  * 'tp' (baseline): experts replicated, every expert's hidden dim sharded
    over the model axis — no load imbalance, no token dropping, combine is
    the same psum as a dense TP MLP.
  * 'ep' (§Perf optimization): experts sharded over the model axis; each
    shard compacts the assignments that target its local experts into a
    capacity buffer (capacity factor 1.25, overflow dropped) — compute per
    shard falls by ~n_shards vs 'tp' at small-expert widths where 'tp'
    under-utilizes the MXU.

Routing is either softmax-logits top-k or — the paper's technique — a CAM
best-match search over expert prototype keys (``cam_router``), with MCAM
quantization + D2D variation non-idealities from the functional simulator.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.quantize import linear_quantize
from repro.runtime import sharding as sh

from .layers import P, mlp, mlp_spec


def moe_spec(cfg: ModelConfig) -> Dict:
    d, E, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    fs = cfg.n_shared_experts * f
    return {
        "router": P((d, E), ("embed", "experts"), dtype=jnp.float32),
        "wi_gate": P((E, d, f), ("experts", "embed", "moe_mlp")),
        "wi_up": P((E, d, f), ("experts", "embed", "moe_mlp")),
        "wo": P((E, f, d), ("experts", "moe_mlp", "embed")),
        "shared": mlp_spec(d, fs),
    }


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------
def route(params, cfg: ModelConfig, x: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """x (T, d) -> (expert_idx (T, k), weights (T, k))."""
    k = cfg.moe_top_k
    if cfg.cam_router:
        # CAM best-match routing: expert prototype keys are the router
        # columns; the search is a quantized dot-distance best match.
        keys = params["router"].T                       # (E, d)
        qx = x.astype(jnp.float32)
        if cfg.cam_router_bits > 0:
            lo = jnp.minimum(jnp.min(keys), jnp.min(qx))
            hi = jnp.maximum(jnp.max(keys), jnp.max(qx))
            qx, _, _ = linear_quantize(qx, cfg.cam_router_bits, lo, hi)
            keys, _, _ = linear_quantize(keys.astype(jnp.float32),
                                         cfg.cam_router_bits, lo, hi)
        scores = qx @ keys.T                            # (T, E), -distance
        scores = scores / jnp.maximum(
            jnp.linalg.norm(keys, axis=-1)[None, :], 1e-6)
    else:
        scores = x.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(scores, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    weights = topv / jnp.maximum(jnp.sum(topv, axis=-1, keepdims=True),
                                 1e-9)
    return topi, weights.astype(x.dtype)


# ---------------------------------------------------------------------------
# Local grouped-GEMM expert compute (shared by both modes)
# ---------------------------------------------------------------------------
def _expert_gemm(xs: jax.Array, gs: jax.Array, wg, wu, wo,
                 balanced: bool = False) -> jax.Array:
    if balanced:
        return _expert_gemm_balanced(xs, wg, wu, wo)
    g = jax.lax.ragged_dot(xs, wg, gs)
    u = jax.lax.ragged_dot(xs, wu, gs)
    h = (jax.nn.silu(g.astype(jnp.float32)) *
         u.astype(jnp.float32)).astype(xs.dtype)
    return jax.lax.ragged_dot(h, wo, gs)


def _expert_gemm_balanced(xs: jax.Array, wg, wu, wo) -> jax.Array:
    """Balanced grouped GEMM (batched einsum), PROBE-ONLY compute model.

    XLA's cost model counts ragged_dot as a dense (m, k) x (g, k, n) — a gx
    FLOP overcount vs the real grouped GEMM a TPU executes.  For dry-run
    cost probes we assume balanced expert loads (what the EP capacity
    buffer enforces in expectation) and compute each expert on an equal
    m/g slice via a batched einsum, which the cost model counts correctly.
    NOT routing-exact for unbalanced loads — never used in training runs
    (cfg.moe_probe_balanced gates it).
    """
    m, d = xs.shape
    g = wg.shape[0]
    cap = max(1, -(-m // g))          # ceil: every row gets a slot
    used = cap * g
    xp = jnp.pad(xs, ((0, used - m), (0, 0))) if used > m else xs[:used]
    xe = xp.reshape(g, cap, d)
    gg = jnp.einsum("ecd,edf->ecf", xe, wg)
    uu = jnp.einsum("ecd,edf->ecf", xe, wu)
    h = (jax.nn.silu(gg.astype(jnp.float32)) *
         uu.astype(jnp.float32)).astype(xs.dtype)
    y = jnp.einsum("ecf,efd->ecd", h, wo).reshape(used, d)
    return y[:m]


def _moe_dispatch_compute(x, topi, weights, wg, wu, wo, n_experts: int,
                          balanced: bool = False):
    """Dropless local MoE: sort assignments by expert, grouped GEMM,
    weighted scatter-add back. x (T,d) -> (T,d)."""
    T, d = x.shape
    k = topi.shape[-1]
    flat_e = topi.reshape(-1)                       # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), k)           # token of each assignment
    flat_w = weights.reshape(-1)
    order = jnp.argsort(flat_e)                     # stable
    xs = jnp.take(x, flat_t[order], axis=0)         # (T*k, d)
    gs = jnp.bincount(flat_e, length=n_experts)     # group sizes
    ys = _expert_gemm(xs, gs, wg, wu, wo, balanced)  # (T*k, d)
    inv = jnp.argsort(order)
    y = jnp.take(ys, inv, axis=0) * flat_w[:, None]
    return jax.ops.segment_sum(y, flat_t, num_segments=T).astype(x.dtype)


def _moe_ep_compute(x, topi, weights, wg, wu, wo, *, n_experts: int,
                    n_shards: int, shard_idx, capacity: int,
                    balanced: bool = False):
    """Expert-parallel local compute: keep only assignments targeting this
    shard's experts, compact into a capacity buffer, grouped GEMM."""
    T, d = x.shape
    k = topi.shape[-1]
    e_local = n_experts // n_shards
    flat_e = topi.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = weights.reshape(-1)
    mine = (flat_e // e_local) == shard_idx
    # compact: sort not-mine last (stable), take first `capacity`
    order = jnp.argsort(jnp.where(mine, 0, 1), stable=True)
    sel = order[:capacity]
    valid = jnp.take(mine, sel)
    sel_e = jnp.where(valid, jnp.take(flat_e, sel) - shard_idx * e_local, 0)
    sel_t = jnp.take(flat_t, sel)
    sel_w = jnp.where(valid, jnp.take(flat_w, sel), 0.0)
    # sort the buffer by local expert for the grouped GEMM
    order2 = jnp.argsort(jnp.where(valid, sel_e, e_local), stable=True)
    sel_e = jnp.take(sel_e, order2)
    sel_t = jnp.take(sel_t, order2)
    sel_w = jnp.take(sel_w, order2)
    valid = jnp.take(valid, order2)
    xs = jnp.take(x, sel_t, axis=0)
    gs = jnp.bincount(jnp.where(valid, sel_e, e_local),
                      length=e_local + 1)[:e_local]
    ys = _expert_gemm(xs, gs, wg, wu, wo, balanced) * sel_w[:, None]
    return jax.ops.segment_sum(ys, sel_t, num_segments=T).astype(x.dtype)


def _moe_a2a_body(cfg: ModelConfig, n_model: int, capacity: int):
    """Expert-parallel all-to-all MoE (the production pattern; §Perf).

    Tokens are sharded over (data x model); experts over model.  Each shard
    routes locally, packs per-destination capacity buffers, exchanges them
    with one all-to-all, runs its experts' grouped GEMM on what it
    received, and all-to-alls the results back — wire bytes per device are
    O(T_local * topk * d), not O(T * d) all-reduces like 'tp' mode.
    """
    E, k, d = cfg.n_experts, cfg.moe_top_k, cfg.d_model
    e_local = E // n_model

    def body(xl, router, wg, wu, wo, sg, su, so):
        T_l = xl.shape[0]
        topi, w = route({"router": router}, cfg, xl)     # (T_l, k)
        flat_e = topi.reshape(-1)                        # (T_l*k,)
        flat_t = jnp.repeat(jnp.arange(T_l), k)
        flat_w = w.reshape(-1)
        dest = flat_e // e_local                         # target shard

        # ---- pack per-destination capacity buffers ----------------------
        order = jnp.argsort(dest, stable=True)
        dsort = jnp.take(dest, order)
        rank = jnp.arange(T_l * k) - jnp.searchsorted(dsort, dsort,
                                                      side="left")
        ok = rank < capacity
        slot = dsort * capacity + rank                   # (T_l*k,)
        nbuf = n_model * capacity
        safe_slot = jnp.where(ok, slot, nbuf)            # drop -> scratch
        xs = jnp.take(xl, jnp.take(flat_t, order), axis=0)
        send_x = jnp.zeros((nbuf + 1, d), xl.dtype
                           ).at[safe_slot].set(xs)[:nbuf]
        meta_e = jnp.full((nbuf + 1,), e_local, jnp.int32
                          ).at[safe_slot].set(
            jnp.take(flat_e, order) % e_local)[:nbuf]
        # remember where each buffered assignment came from
        src_slot = jnp.full((nbuf + 1,), T_l * k, jnp.int32
                            ).at[safe_slot].set(order)[:nbuf]

        # ---- exchange ----------------------------------------------------
        recv_x = jax.lax.all_to_all(
            send_x.reshape(n_model, capacity, d), "model", 0, 0,
            tiled=False).reshape(nbuf, d)
        recv_e = jax.lax.all_to_all(
            meta_e.reshape(n_model, capacity), "model", 0, 0,
            tiled=False).reshape(nbuf)

        # ---- local experts' grouped GEMM ---------------------------------
        order2 = jnp.argsort(recv_e, stable=True)
        xs2 = jnp.take(recv_x, order2, axis=0)
        gs = jnp.bincount(recv_e, length=e_local + 1)[:e_local]
        ys2 = _expert_gemm(xs2, gs, wg, wu, wo,
                           cfg.moe_probe_balanced)
        ys = jnp.zeros_like(recv_x).at[order2].set(
            ys2.astype(recv_x.dtype))

        # ---- return + combine --------------------------------------------
        back = jax.lax.all_to_all(
            ys.reshape(n_model, capacity, d), "model", 0, 0,
            tiled=False).reshape(nbuf, d)
        y_assign = jnp.zeros((T_l * k + 1, d), xl.dtype
                             ).at[src_slot].set(back)[:T_l * k]
        y = y_assign * flat_w[:, None]
        out = jax.ops.segment_sum(y, flat_t, num_segments=T_l)

        # shared experts: tokens differ across model shards here, so the
        # shared weights are REPLICATED and applied fully locally (a psum
        # would sum different tokens)
        shared = mlp({"wi_gate": sg, "wi_up": su, "wo": so}, xl)
        return out.astype(xl.dtype) + shared.astype(xl.dtype)

    return body


# ---------------------------------------------------------------------------
# Public block
# ---------------------------------------------------------------------------
def moe_block(params, cfg: ModelConfig, x: jax.Array,
              mode: str = "tp") -> jax.Array:
    """x (B, S, d) or (B, d) -> same shape."""
    shape = x.shape
    d = shape[-1]
    xf = x.reshape(-1, d)
    ctx = sh._ctx.get()
    if ctx is None or "model" not in ctx.mesh.axis_names:
        topi, w = route(params, cfg, xf)
        y = _moe_dispatch_compute(xf, topi, w, params["wi_gate"],
                                  params["wi_up"], params["wo"],
                                  cfg.n_experts, cfg.moe_probe_balanced)
        y = y + mlp(params["shared"], xf)
        return y.reshape(shape)

    mesh = ctx.mesh
    dp = tuple(a for a in mesh.axis_names if a != "model")
    n_model = mesh.devices.shape[mesh.axis_names.index("model")]
    Psp = jax.sharding.PartitionSpec
    # batch=1 decode can't shard tokens over data: replicate instead
    dp_size = _prod_axis(mesh, dp)
    dp_ok = xf.shape[0] % dp_size == 0 and xf.shape[0] >= dp_size
    x_spec = Psp(dp) if dp_ok else Psp()

    if mode == "a2a" and cfg.n_experts % n_model == 0 \
            and xf.shape[0] % (dp_size * n_model) == 0:
        T_l = xf.shape[0] // (dp_size * n_model)
        capacity = max(1, int(cfg.moe_capacity_factor * T_l
                              * cfg.moe_top_k / n_model) + 1)
        body = _moe_a2a_body(cfg, n_model, capacity)
        Pall = Psp(dp + ("model",))
        yf = jax.shard_map(
            body, mesh=mesh,
            in_specs=(Pall, Psp(), Psp("model"), Psp("model"),
                      Psp("model"), Psp(), Psp(), Psp()),
            out_specs=Pall)(
            xf, params["router"], params["wi_gate"], params["wi_up"],
            params["wo"], params["shared"]["wi_gate"],
            params["shared"]["wi_up"], params["shared"]["wo"])
        return yf.reshape(shape)

    if mode == "ep" and cfg.n_experts % n_model == 0:
        T_local = xf.shape[0] // dp_size if dp_ok else xf.shape[0]
        capacity = max(cfg.moe_top_k, int(
            cfg.moe_capacity_factor * T_local * cfg.moe_top_k
            / n_model + 1))

        def body(xl, router, wg, wu, wo, sg, su, so):
            topi, w = route({"router": router}, cfg, xl)
            sidx = jax.lax.axis_index("model")
            y = _moe_ep_compute(xl, topi, w, wg, wu, wo,
                                n_experts=cfg.n_experts, n_shards=n_model,
                                shard_idx=sidx, capacity=capacity,
                                balanced=cfg.moe_probe_balanced)
            y = y + mlp({"wi_gate": sg, "wi_up": su, "wo": so}, xl)
            return jax.lax.psum(y, "model")

        yf = jax.shard_map(
            body, mesh=mesh,
            in_specs=(x_spec, Psp(), Psp("model"), Psp("model"),
                      Psp("model"), Psp(None, "model"), Psp(None, "model"),
                      Psp("model")),
            out_specs=x_spec)(
            xf, params["router"], params["wi_gate"], params["wi_up"],
            params["wo"], params["shared"]["wi_gate"],
            params["shared"]["wi_up"], params["shared"]["wo"])
        return yf.reshape(shape)

    # 'tp' baseline: expert hidden dim sharded over model
    def body(xl, router, wg, wu, wo, sg, su, so):
        topi, w = route({"router": router}, cfg, xl)
        y = _moe_dispatch_compute(xl, topi, w, wg, wu, wo, cfg.n_experts,
                                  cfg.moe_probe_balanced)
        y = y + mlp({"wi_gate": sg, "wi_up": su, "wo": so}, xl)
        return jax.lax.psum(y, "model")

    yf = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, Psp(), Psp(None, None, "model"),
                  Psp(None, None, "model"), Psp(None, "model"),
                  Psp(None, "model"), Psp(None, "model"), Psp("model")),
        out_specs=x_spec)(
        xf, params["router"], params["wi_gate"], params["wi_up"],
        params["wo"], params["shared"]["wi_gate"],
        params["shared"]["wi_up"], params["shared"]["wo"])
    return yf.reshape(shape)


def _prod_axis(mesh, axes) -> int:
    out = 1
    for a in axes:
        out *= mesh.devices.shape[mesh.axis_names.index(a)]
    return out

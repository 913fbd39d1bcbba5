"""CAM-retrieval attention: the paper's best-match CAM search as an LM layer.

At decode time the KV cache plays the role of the CAM stored data; the query
performs a *best-match with sensing limit* search (top-k) over the keys and
attention is computed only over the retrieved entries — the direct LM
transliteration of the paper's MANN application, and what makes the
long_500k shape sub-quadratic in bytes for attention archs (DESIGN.md §3).

Non-idealities from the paper's functional simulator are available:
``cam_attn_bits`` applies MCAM linear quantization to keys and query before
the distance pass (Fig. 4's accuracy knob).  Two backends:

  * 'xla'    — shardable jnp ops (used under pjit / for the dry-run)
  * 'pallas' — the cam_topk streaming kernel (single-device TPU hot path)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.quantize import linear_quantize

NEG_INF = -1e30


def _maybe_quantize(q: jax.Array, k: jax.Array, bits: int):
    """MCAM quantization of the retrieval operands (shared scale)."""
    if bits <= 0:
        return q, k
    lo = jnp.minimum(jnp.min(k), jnp.min(q))
    hi = jnp.maximum(jnp.max(k), jnp.max(q))
    qq, _, _ = linear_quantize(q.astype(jnp.float32), bits, lo, hi)
    kq, _, _ = linear_quantize(k.astype(jnp.float32), bits, lo, hi)
    return qq, kq


def cam_topk_scores(scores: jax.Array, k: int):
    """Best-match-with-SL selection: keep top-k scores, mask the rest."""
    S = scores.shape[-1]
    k = min(k, S)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx


def cam_decode_attention(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, pos: jax.Array,
                         cfg: ModelConfig,
                         backend: str = "xla") -> jax.Array:
    """GQA decode via CAM retrieval.

    q (B,H,Dh); k_cache/v_cache (B,S,KVH,D*); pos (B,).
    Returns (B,H,Dv).
    """
    B, H, Dk = q.shape
    _, S, KVH, Dv = v_cache.shape
    G = H // KVH
    scale = Dk ** -0.5
    topk = min(cfg.cam_topk, S)

    qq, kk = _maybe_quantize(q, k_cache, cfg.cam_attn_bits)
    qg = qq.reshape(B, KVH, G, Dk)
    kc = kk.transpose(0, 2, 1, 3)                      # (B,KVH,S,Dk)

    # CAM distance pass (dot distance == best-match over inner product)
    s = jnp.einsum("bhgd,bhsd->bhgs", qg, kc,
                   preferred_element_type=jnp.float32) * scale
    valid = (jnp.arange(S)[None, :] <= pos[:, None])   # (B,S)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)

    # winner-take-all sensing: top-k selection
    vals, idx = cam_topk_scores(s, topk)               # (B,KVH,G,k)

    # gather retrieved values only — the bytes win vs full attention
    vc = v_cache.transpose(0, 2, 1, 3)                 # (B,KVH,S,Dv)
    vg = jnp.take_along_axis(
        vc[:, :, None], idx[..., None].clip(0), axis=-2)  # (B,KVH,G,k,Dv)

    w = jax.nn.softmax(vals, axis=-1)                  # over retrieved set
    out = jnp.einsum("bhgk,bhgkd->bhgd", w.astype(vg.dtype), vg,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, Dv).astype(q.dtype)


def cam_select_scores(s: jax.Array, pos: jax.Array,
                      cfg: ModelConfig) -> jax.Array:
    """MLA variant: mask all but the CAM-retrieved top-k of the latent
    scores (B,H,S) — retrieval happens in the compressed latent space."""
    S = s.shape[-1]
    topk = min(cfg.cam_topk, S)
    valid = jnp.arange(S)[None, None, :] <= pos[:, None, None]
    s = jnp.where(valid, s, NEG_INF)
    kth = jax.lax.top_k(s, topk)[0][..., -1:]
    return jnp.where(s >= kth, s, NEG_INF)


def cam_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
               pos: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Dispatch between merge strategies.

    'hierarchical' engages when a model mesh axis exists, the cache's seq
    dim shards over it (kv_heads didn't divide), and the seq length splits
    evenly; otherwise falls back to the global top-k."""
    from repro.runtime import sharding as shmod
    if cfg.cam_merge == "hierarchical":
        m = shmod.model_axis_size()
        S, KVH = k_cache.shape[1], k_cache.shape[2]
        if m > 1 and KVH % m != 0 and S % m == 0 and (S // m) >= 1:
            return cam_decode_attention_hierarchical(q, k_cache, v_cache,
                                                     pos, cfg)
    return cam_decode_attention(q, k_cache, v_cache, pos, cfg)


def cam_decode_attention_hierarchical(q: jax.Array, k_cache: jax.Array,
                                      v_cache: jax.Array, pos: jax.Array,
                                      cfg: ModelConfig) -> jax.Array:
    """CAM retrieval with the paper's partition-and-merge over a
    seq-sharded cache (Fig. 3: per-subarray best match + comparator-style
    vertical merge), as a shard_map.

    Each model shard = one vertical CAM partition holding S/m cache rows:
      1. local distance pass + local top-k (the subarray winner set);
      2. all-gather only the (m x k) winner SCORES (bytes ~ m*k*4, vs the
         full cache for the global variant) and derive the global k-th
         score (the comparator tree);
      3. each shard computes exp-weighted partial sums over its local
         winners that clear the global threshold; psum merges them.

    Exact w.r.t. the global variant (same retrieved set; ties at the k-th
    score may admit extras — precisely the paper's sensing-limit
    semantics).
    """
    from repro.runtime import sharding as shmod
    ctx = shmod._ctx.get()
    B, H, Dk = q.shape
    _, S, KVH, Dv = v_cache.shape
    G = H // KVH
    scale = Dk ** -0.5
    mesh = ctx.mesh
    m = shmod.model_axis_size()
    dp = tuple(a for a in mesh.axis_names if a != "model")
    dp_ok = B % max(1, _prod(mesh, dp)) == 0
    Psp = jax.sharding.PartitionSpec
    b_spec = Psp(dp) if dp_ok else Psp()
    S_l = S // m
    topk = min(cfg.cam_topk, S_l)

    def body(qb, kb, vb, posb):
        sidx = jax.lax.axis_index("model")
        qq, kk = _maybe_quantize(qb, kb, cfg.cam_attn_bits)
        qg = qq.reshape(-1, KVH, G, Dk)
        kc = kk.transpose(0, 2, 1, 3)                  # (b,KVH,S_l,Dk)
        s = jnp.einsum("bhgd,bhsd->bhgs", qg, kc,
                       preferred_element_type=jnp.float32) * scale
        gpos = sidx * S_l + jnp.arange(S_l)            # global positions
        valid = gpos[None, :] <= posb[:, None]
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        # 1. local winner set (the subarray best-match outputs)
        vals, idx = jax.lax.top_k(s, topk)             # (b,KVH,G,k)
        # 2. comparator merge: gather only winner scores, global k-th
        allv = jax.lax.all_gather(vals, "model")       # (m,b,KVH,G,k)
        allv = jnp.moveaxis(allv, 0, -2).reshape(
            *vals.shape[:-1], m * topk)
        kth = jax.lax.top_k(allv, topk)[0][..., -1:]   # global threshold
        mx = jnp.max(allv, axis=-1, keepdims=True)
        # 3. local partial attention over winners clearing the threshold
        keep = vals >= kth
        p = jnp.where(keep, jnp.exp(vals - mx), 0.0)   # (b,KVH,G,k)
        vloc = vb.transpose(0, 2, 1, 3)                # (b,KVH,S_l,Dv)
        vg = jnp.take_along_axis(vloc[:, :, None],
                                 idx[..., None].clip(0), axis=-2)
        num = jnp.einsum("bhgk,bhgkd->bhgd", p.astype(vg.dtype), vg,
                         preferred_element_type=jnp.float32)
        den = jnp.sum(p, axis=-1, keepdims=True)
        num = jax.lax.psum(num, "model")
        den = jax.lax.psum(den, "model")
        out = num / jnp.maximum(den, 1e-30)
        return out.reshape(-1, H, Dv).astype(qb.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(b_spec, Psp(b_spec[0] if dp_ok else None, "model"),
                  Psp(b_spec[0] if dp_ok else None, "model"), b_spec),
        out_specs=b_spec)(q, k_cache, v_cache, pos)


def _prod(mesh, axes) -> int:
    out = 1
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    for a in axes:
        out *= sizes[a]
    return out


def cam_decode_attention_pallas(q: jax.Array, k_cache: jax.Array,
                                v_cache: jax.Array, pos: jax.Array,
                                cfg: ModelConfig) -> jax.Array:
    """Kernel-backed variant: streaming cam_topk over the cache (per
    (batch, head)); single-device TPU path, validated against the xla
    backend in tests."""
    from repro.kernels import ops as kops
    B, H, Dk = q.shape
    _, S, KVH, Dv = v_cache.shape
    G = H // KVH
    scale = Dk ** -0.5
    topk = min(cfg.cam_topk, S)
    qg = q.reshape(B, KVH, G, Dk)
    kc = jnp.broadcast_to(k_cache.transpose(0, 2, 1, 3)[:, :, None],
                          (B, KVH, G, S, Dk))
    vals, idx = kops.cam_topk(
        kc.reshape(-1, S, Dk) * scale,
        qg.reshape(-1, Dk),
        k=topk, chunk=min(cfg.cam_chunk, S), distance="dot")
    vals = vals.reshape(B, KVH, G, topk)
    idx = idx.reshape(B, KVH, G, topk)
    # mask entries beyond pos (cache not yet written)
    written = idx <= pos[:, None, None, None]
    vals = jnp.where(written, vals, NEG_INF)
    vc = v_cache.transpose(0, 2, 1, 3)
    vg = jnp.take_along_axis(vc[:, :, None], idx[..., None].clip(0),
                             axis=-2)
    w = jax.nn.softmax(vals, axis=-1)
    out = jnp.einsum("bhgk,bhgkd->bhgd", w.astype(vg.dtype), vg,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, Dv).astype(q.dtype)

"""Jit'd public wrappers for the Pallas kernels.

Each op compiles its Mosaic kernel on a TPU backend and runs it in Pallas
interpret mode on the CPU backend (how the test suite runs); any other
backend raises.  ``tests/test_tpu_compile.py`` compiles the fused search
kernels (``cam_search_fused*``, whose driver ``cam_search`` shares) and
the packed-hamming prefilter kernel for a described TPU v5e.  The ops
pad inputs to kernel-friendly shapes.  Query batches dispatch to the
query-batched kernels (one HBM pass over the stored grid per batch);
``cam_search_vmap`` keeps the old per-query vmap path as a baseline.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .cam_search import (SMALL_Q_CROSSOVER, cam_fused_reference,
                         cam_range_fused_pallas, cam_search_batched_pallas,
                         cam_search_fused_pallas, cam_search_pallas,
                         default_q_tile)
from .cam_topk import cam_topk_pallas
from .hamming_pack import hamming_packed_batched_pallas, hamming_packed_pallas


def _interpret() -> bool:
    """Compiled Mosaic on TPU, interpret mode on CPU, nothing else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels run compiled on TPU or interpreted on CPU; "
        f"backend {backend!r} has neither (set sim.use_kernel=False)")


# --------------------------------------------------------------------------
# cam_search: subarray-grid distances
# --------------------------------------------------------------------------
def cam_search(stored: jax.Array, query: jax.Array, *, distance: str = "l2",
               col_valid: Optional[jax.Array] = None,
               q_tile: Optional[int] = None,
               interpret: Optional[bool] = None,
               pipeline: bool = True) -> jax.Array:
    """stored (nv, nh, R, C); query (..., nh, C) -> dist (..., nv, nh, R).

    Batched queries go through the query-batched kernel, which streams the
    stored grid from HBM once for the whole batch (``pipeline=True``
    upgrades it to the bank-blocked double-buffered schedule); a single
    (nh, C) query uses the resident single-query kernel.
    """
    nv, nh, R, C = stored.shape
    if col_valid is None:
        col_valid = jnp.ones((nh, C), jnp.float32)
    itp = _interpret() if interpret is None else interpret
    if query.ndim == 2:
        return cam_search_pallas(stored, query, col_valid,
                                 distance=distance, interpret=itp)
    batch = query.reshape(-1, nh, C)
    out = cam_search_batched_pallas(stored, batch, col_valid,
                                    distance=distance, q_tile=q_tile,
                                    interpret=itp, pipeline=pipeline)
    return out.reshape(*query.shape[:-2], nv, nh, R)


def cam_search_vmap(stored: jax.Array, query: jax.Array, *,
                    distance: str = "l2",
                    col_valid: Optional[jax.Array] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Per-query vmap over the single-query kernel (the pre-batching hot
    path).  Kept as the benchmark baseline and numerical cross-check: it
    re-streams the stored grid once per query."""
    nv, nh, R, C = stored.shape
    if col_valid is None:
        col_valid = jnp.ones((nh, C), jnp.float32)
    itp = _interpret() if interpret is None else interpret
    call = functools.partial(cam_search_pallas, distance=distance,
                             interpret=itp)
    if query.ndim == 2:
        return call(stored, query, col_valid)
    batch = query.reshape(-1, nh, C)
    out = jax.vmap(lambda q: call(stored, q, col_valid))(batch)
    return out.reshape(*query.shape[:-2], nv, nh, R)


def _int_cast(stored: jax.Array, queries: jax.Array, col_valid: jax.Array,
              *, distance: str, int_codes: int):
    """Lower noise-free integral point codes onto the narrow-int / packed
    fast paths of ``_dist_block_batched``.

    ``int_codes`` is the code width in bits (``app.data_bits``), asserted
    by the caller to describe a grid of exact small integers (no device
    noise).  1-bit hamming codes bit-pack into uint32 words with
    ``col_valid`` folded in as the care mask (both operands masked, so XOR
    contributes 0 on don't-care columns); wider codes up to 7 bits cast
    to int8 (8-bit codes overflow int8 and keep the f32 path).  Returns
    the (possibly transformed)
    ``(stored, queries, col_valid)`` triple — unchanged when no fast path
    applies.  Every path is bit-exact vs f32: the distances are sums of
    exact small-integer products.
    """
    if not int_codes or stored.ndim != 4:
        return stored, queries, col_valid
    if distance == "hamming" and int_codes == 1:
        # care mask broadcast over (nv, nh, R, C) / (Q, nh, C); the packed
        # word count W replaces C and the mask is already folded in
        nh = col_valid.shape[0]
        sp = pack_bits(stored, col_valid[None, :, None, :])
        qp = pack_bits(queries, col_valid[None])
        return sp, qp, jnp.ones((nh, sp.shape[-1]), jnp.float32)
    if distance in ("hamming", "l1", "l2", "dot") and int_codes <= 7:
        return stored.astype(jnp.int8), queries.astype(jnp.int8), col_valid
    return stored, queries, col_valid


def _fused_call(stored: jax.Array, queries: jax.Array,
                col_valid: jax.Array, row_valid: jax.Array, *,
                distance: str, sensing: str, sensing_limit: float,
                threshold: float, q_tile: Optional[int], want_dist: bool,
                interpret: bool, pipeline: bool = True, int_codes: int = 0):
    """Shape-dispatched fused kernel call (shared with the sharded wrapper).

    5-D stored grids are ACAM [lo, hi] ranges and require
    ``distance='range'``; the trailing dim is split into two dense (R, C)
    planes before ``pallas_call`` (see ``cam_range_fused_pallas``).

    Batches below ``SMALL_Q_CROSSOVER`` route to ``cam_fused_reference`` —
    the jnp twin built from the same tile functions — on BOTH the interpret
    and compiled paths: per-grid-step dispatch (emulated or Mosaic launch)
    dominates tiny batches either way (BENCH: q1 kernel at 0.92x of jnp
    even with the fused epilogue), and the twin is bit-identical by
    construction.

    ``pipeline``/``int_codes`` select the bank-blocked double-buffered
    schedule and the narrow-int/bit-packed distance paths; the fast paths
    only rewrite dtypes/schedules, never values — ``pipeline=False``
    reproduces the historical kernels bit-for-bit and skips the int
    lowering entirely.
    """
    if (stored.ndim == 5) != (distance == "range"):
        raise ValueError(
            f"distance='range' needs a 5-D [lo, hi] grid and vice versa; "
            f"got distance={distance!r} with stored.ndim={stored.ndim}")
    if pipeline:
        stored, queries, col_valid = _int_cast(
            stored, queries, col_valid, distance=distance,
            int_codes=int_codes)
    if queries.shape[0] < SMALL_Q_CROSSOVER:
        planes = ((stored[..., 0], stored[..., 1]) if stored.ndim == 5
                  else (stored,))
        return cam_fused_reference(
            planes, queries, col_valid, row_valid, distance=distance,
            sensing=sensing, sensing_limit=float(sensing_limit),
            threshold=float(threshold), want_dist=want_dist)
    if stored.ndim == 5:
        return cam_range_fused_pallas(
            stored[..., 0], stored[..., 1], queries, col_valid, row_valid,
            sensing=sensing, sensing_limit=float(sensing_limit),
            threshold=float(threshold), q_tile=q_tile, want_dist=want_dist,
            interpret=interpret, pipeline=pipeline)
    return cam_search_fused_pallas(
        stored, queries, col_valid, row_valid, distance=distance,
        sensing=sensing, sensing_limit=float(sensing_limit),
        threshold=float(threshold), q_tile=q_tile, want_dist=want_dist,
        interpret=interpret, pipeline=pipeline)


def cam_search_fused(stored: jax.Array, queries: jax.Array, *,
                     distance: str, sensing: str, sensing_limit: float = 0.0,
                     threshold: float = 0.0,
                     col_valid: Optional[jax.Array] = None,
                     row_valid: Optional[jax.Array] = None,
                     q_tile: Optional[int] = None, want_dist: bool = True,
                     interpret: Optional[bool] = None,
                     pipeline: bool = True, int_codes: int = 0):
    """Batched search with the sense-and-reduce epilogue fused in-kernel.

    stored (nv, nh, R, C) point codes, or (nv, nh, R, C, 2) ACAM [lo, hi]
    ranges with ``distance='range'`` (dispatched to the range kernel).
    queries (Q, nh, C) -> (dist, match) each (Q, nv, nh, R), or match alone
    when ``want_dist=False`` (the distance tensor then never leaves VMEM).

    ``pipeline`` toggles the bank-blocked double-buffered schedule
    (``sim.pipeline``; off-switch is bit- and schedule-identical to the
    historical kernels).  ``int_codes`` (code width in bits) opts
    noise-free integral point codes onto the narrow-int / bit-packed
    distance fast paths — the caller asserts integrality; results stay
    bit-exact.
    """
    nv, nh, R, C = stored.shape[:4]
    if col_valid is None:
        col_valid = jnp.ones((nh, C), jnp.float32)
    if row_valid is None:
        row_valid = jnp.ones((nv, R), jnp.float32)
    itp = _interpret() if interpret is None else interpret
    return _fused_call(
        stored, queries, col_valid, row_valid, distance=distance,
        sensing=sensing, sensing_limit=float(sensing_limit),
        threshold=float(threshold), q_tile=q_tile, want_dist=want_dist,
        interpret=itp, pipeline=pipeline, int_codes=int_codes)


def cam_search_fused_sharded(stored: jax.Array, queries: jax.Array, *,
                             mesh, bank_axis: str = "bank",
                             distance: str, sensing: str,
                             sensing_limit: float = 0.0,
                             threshold: float = 0.0,
                             col_valid: Optional[jax.Array] = None,
                             row_valid: Optional[jax.Array] = None,
                             q_tile: Optional[int] = None,
                             want_dist: bool = True,
                             interpret: Optional[bool] = None,
                             pipeline: bool = True, int_codes: int = 0):
    """``cam_search_fused`` with the stored grid's nv axis sharded over
    ``bank_axis`` of ``mesh``: each device streams only its local
    (nv/n_banks, nh, R, C) shard — the kernel-layer unit the sharded
    simulator (and the weak-scaling benchmark) builds on.  ACAM
    (nv, nh, R, C, 2) range grids take the same route with
    ``distance='range'`` (the trailing [lo, hi] dim is shard-local).

    Outputs keep the bank sharding on their nv axis ((Q, nv, nh, R),
    sharded on dim 1); the cross-device merge lives one layer up in
    ``core.sharded``, which consumes these shard-local results.  nv must
    divide the bank-axis size (``core.sharded`` handles padding).
    """
    from jax.sharding import PartitionSpec as P

    nv, nh, R, C = stored.shape[:4]
    n_banks = dict(zip(mesh.axis_names, mesh.axis_sizes))[bank_axis]
    if nv % n_banks:
        raise ValueError(f"nv={nv} must be a multiple of the bank axis "
                         f"size {n_banks}")
    if col_valid is None:
        col_valid = jnp.ones((nh, C), jnp.float32)
    if row_valid is None:
        row_valid = jnp.ones((nv, R), jnp.float32)
    itp = _interpret() if interpret is None else interpret

    def body(s, rv, cv, q):
        return _fused_call(
            s, q, cv, rv, distance=distance, sensing=sensing,
            sensing_limit=float(sensing_limit), threshold=float(threshold),
            q_tile=q_tile, want_dist=want_dist, interpret=itp,
            pipeline=pipeline, int_codes=int_codes)

    out_spec = P(None, bank_axis)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bank_axis), P(bank_axis), P(), P()),
        out_specs=(out_spec, out_spec) if want_dist else out_spec,
        check_vma=False)(
        stored, row_valid, col_valid, queries)


# --------------------------------------------------------------------------
# cam_topk: streaming best-match top-k (CAM-retrieval attention hot loop)
# --------------------------------------------------------------------------
def cam_topk(keys: jax.Array, query: jax.Array, *, k: int, chunk: int = 512,
             distance: str = "dot", valid_len: Optional[int] = None,
             interpret: Optional[bool] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """keys (S, D) or (..., S, D); query (D,) or (..., D).

    Returns (scores, indices) of shape (..., k); scores are -distance,
    descending.  Rows at index >= valid_len are excluded.
    """
    itp = _interpret() if interpret is None else interpret
    S, D = keys.shape[-2:]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    k = min(k, S)

    limit = S if valid_len is None else valid_len

    def one(kv: jax.Array, q: jax.Array):
        x = kv
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
        vals, idx = cam_topk_pallas(x, q, k=k, chunk=chunk,
                                    distance=distance, valid_len=limit,
                                    interpret=itp)
        bad = idx >= limit
        vals = jnp.where(bad, -jnp.inf, vals)
        idx = jnp.where(bad, -1, idx)
        return vals, idx

    if keys.ndim == 2:
        return one(keys, query)
    bk = keys.reshape(-1, S, D)
    bq = query.reshape(-1, D)
    vals, idx = jax.vmap(one)(bk, bq)
    lead = keys.shape[:-2]
    # explicit (*lead, k): reshape(-1) would mis-fold the batch axes back
    # into the top-k axis for keys.ndim > 2
    return vals.reshape(*lead, k), idx.reshape(*lead, k)


# --------------------------------------------------------------------------
# hamming_packed: bit-packed TCAM search
# --------------------------------------------------------------------------
def pack_bits(bits: jax.Array,
              care: Optional[jax.Array] = None) -> jax.Array:
    """Pack 0/1 (optionally ternary via ``care`` mask) into uint32 words.

    Don't-care columns are zeroed in the packed word (mask both operands
    with the same ``care`` mask so XOR contributes nothing there).
    """
    x = bits
    if care is not None:
        x = x * care
    return ref.pack_bits_ref(x)


def hamming_packed(stored_packed: jax.Array, query_packed: jax.Array, *,
                   n_valid_bits: int, tile_r: int = 256,
                   q_tile: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """stored (R, W) uint32, query (W,) or (Q, W) uint32 -> dist (R,) or
    (Q, R).  Batched queries share each resident stored tile; the default
    Q-tile comes from the same VMEM working-set helper as the float
    kernels (``cam_search.default_q_tile``)."""
    itp = _interpret() if interpret is None else interpret
    R, W = stored_packed.shape
    tr = tile_r
    while R % tr and tr > 1:
        tr //= 2
    if query_packed.ndim == 2:
        return hamming_packed_batched_pallas(
            stored_packed, query_packed, tile_r=tr, q_tile=q_tile,
            interpret=itp)
    return hamming_packed_pallas(stored_packed, query_packed, tile_r=tr,
                                 interpret=itp)

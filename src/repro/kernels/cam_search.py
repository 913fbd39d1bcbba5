"""Pallas TPU kernels: tiled CAM subarray search with a fused sense amplifier.

TPU adaptation of the CAM array (DESIGN.md §2): a (R, C) subarray tile sits
in VMEM — the analogue of the data resident in a physical CAM array — and is
searched by a whole query tile at once; the grid walks the (nv, nh)
subarray mesh produced by the mapping submodule.

One kernel body (``_fused_kernel``) serves every entry point.  Per tile it
computes the distance block (``_dist_block_batched`` / the ACAM
``_range_block_batched``), masks padding rows to +inf and runs the
sense-amplifier model of ``core.subarray.sense`` (exact / best / threshold,
with the intra-subarray winner-take-all for best) while the block is still
in VMEM (``_tile_fused``).  ``cam_fused_reference`` is its pure-jnp twin,
built from the same tile function.

Distance formulation: ``l2``/``dot`` are shaped for the MXU — the cross
term is a (Qt, C) x (C, R) matmul and the column mask is folded into the
row/query norms (‖s‖² − 2·S·Qᵀ + ‖q‖²).  ``l1``/``hamming``/``range`` have
no matmul form and compare a (Qt, R, C) block on the VPU.  Noise-free
integral codes run on int8 operands (int32 MXU accumulation) and 1-bit
hamming codes bit-packed into uint32 words (XOR + popcount).

Grids (``_fused_driver``), both with the Q-tile axis innermost so the
stored block stays resident while every query tile passes over it:

    bank-blocked (default)   grid (nv/vb, Q/Qt)
        stored     (vb, nh, R, C)  <- HBM bank block b, once per batch
        queries    (nh, Qt, C)     <- Q-tile k
        col_valid  (nh, 1, C)
        row_valid  (vb, 1, R)
        dist/match (vb, nh, Qt, R) -> bank-major (nv, nh, Q, R) outputs

    per-tile (pipeline=False, or one bank over budget)  grid (nv, nh, Q/Qt)
        the same blocks with vb = nh = 1

The last two dims of every block are whole array dims or (8, 128)-aligned,
which Mosaic requires, and no block carries a size-1 sublane dim.  The
body walks its vb·nh tiles in a ``fori_loop``, so compile time and live
temporaries do not grow with the block.  ``fused_vmem_bytes`` counts what a
grid step holds in VMEM; ``resident_banks``/``choose_q_tile`` size the
blocks against the device's budget (``DEVICE_MODELS``), which the compiled
kernel passes to Mosaic as ``vmem_limit_bytes``.

``cam_search_fused_pallas`` (point codes) and ``cam_range_fused_pallas``
(ACAM [lo, hi] planes, split by the caller so each plane keeps a dense
lane dim) return ``(dist, match)`` each (Q, nv, nh, R), or match alone
with ``want_dist=False``.  ``cam_search_batched_pallas`` /
``cam_search_pallas`` return the distance output alone.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INF = float("inf")

# Conservative per-step VMEM budget for the default Q-tile derivation: well
# under the ~16 MiB physical budget so double-buffered pipelines and the
# (Qt, R, C) register blocks of the VPU distances still fit.
VMEM_BUDGET_BYTES = 4 * 1024 * 1024

# Pipelined-driver VMEM budget in interpret mode (the "cpu" entry of
# DEVICE_MODELS below, and the default of the model functions): what one
# grid step may hold — stored bank block, query/output tiles, temporaries.
RESIDENT_BUDGET_BYTES = 12 * 1024 * 1024

# Per-grid-step dispatch overhead (seconds) for the measured-model Q-tile
# choice.  Interpret mode pays this in host dispatch per step; compiled
# Mosaic pays a (much smaller) scalar-core cost — either way the model only
# RANKS ladder rungs, and kernel_bench.py validates the ranking against
# wall clock.  Overridable at import via CAMASIM_STEP_OVERHEAD_S (or at
# runtime via set_kernel_model / sim.step_overhead_s; see
# benchmarks/calibrate_kernel_model.py for a fitting script).
STEP_OVERHEAD_S = float(os.environ.get("CAMASIM_STEP_OVERHEAD_S", 2e-4))

# Nominal HBM bandwidth for the traffic term of the Q-tile model; the same
# constant plan.autotune.simulated_qps uses (bytes/s).
HBM_BYTES_PER_S = 819e9

# Kernel-model rates per device, keyed by ``jax.Device.device_kind``:
# ``hbm_bytes_per_s`` feeds the Q-tile model's traffic term and
# ``vmem_budget_bytes`` bounds what one grid step of the fused kernels may
# hold in VMEM (every double-buffered block plus the per-tile temporaries,
# ``fused_vmem_bytes``); the compiled kernels pass it on as Mosaic's
# ``vmem_limit_bytes``.  TPU v5e ("TPU v5 lite"): 819 GB/s HBM (Google
# Cloud documentation, "TPU v5e"); the 32 MiB budget is a quarter of the
# chip's VMEM.  The "cpu" entry serves interpret mode, where both numbers
# only rank schedules.  A device kind missing here is an error, not a
# default (``device_model``).
DEVICE_MODELS = {
    "cpu": {"hbm_bytes_per_s": HBM_BYTES_PER_S,
            "vmem_budget_bytes": RESIDENT_BUDGET_BYTES},
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9,
                    "vmem_budget_bytes": 32 * 1024 * 1024},
}


def _device_kind() -> str:
    return jax.devices()[0].device_kind


def device_model(kind: Optional[str] = None) -> dict:
    """Kernel-model rates for ``kind`` (default: the first local device)."""
    kind = _device_kind() if kind is None else kind
    try:
        return DEVICE_MODELS[kind]
    except KeyError:
        raise ValueError(
            f"no kernel model for device kind {kind!r}; add its HBM rate "
            f"and VMEM budget to cam_search.DEVICE_MODELS "
            f"(known: {sorted(DEVICE_MODELS)})") from None

# Ceiling on the per-step VPU broadcast block (qt, vb·segs·R, C) that the
# no-matmul distances (l1 / unpacked hamming / ACAM range) materialize while
# comparing every query lane against every cell.  The MXU distances
# (l2 / dot) and the bit-packed hamming path never build this block, so the
# cap binds only where the block is real — measured on the ACAM Q-sweep
# geometry (8 banks x 512 x 128): rungs past this cliff run ~4x slower and
# non-monotonically (kernel_bench.py qps_monotone contract).  Overridable
# at import via CAMASIM_BCAST_BUDGET_BYTES (or at runtime via
# set_kernel_model / sim.bcast_budget_bytes).
BCAST_BUDGET_BYTES = int(float(
    os.environ.get("CAMASIM_BCAST_BUDGET_BYTES", 24 * 1024 * 1024)))

# Interpret-mode grids pay per-step dispatch overhead; below this batch size
# the identical jnp tile math wins (BENCH: kernel_acam_range_q1 at 0.18x).
SMALL_Q_CROSSOVER = 4

# The power-of-two Q-tile ladder (what SimConfig.q_tile validates against).
Q_TILES = (1, 2, 4, 8, 16, 32, 64, 128, 256)


def set_kernel_model(step_overhead_s: Optional[float] = None,
                     bcast_budget_bytes: Optional[int] = None) -> None:
    """Override the measured-model constants at runtime.

    ``None`` leaves a constant untouched.  The constants only RANK
    ladder rungs; re-fit them on new hardware with
    ``benchmarks/calibrate_kernel_model.py`` and pin the results via the
    ``CAMASIM_STEP_OVERHEAD_S`` / ``CAMASIM_BCAST_BUDGET_BYTES``
    environment variables or the ``sim.step_overhead_s`` /
    ``sim.bcast_budget_bytes`` config fields (which call this).
    """
    global STEP_OVERHEAD_S, BCAST_BUDGET_BYTES
    if step_overhead_s is not None:
        if step_overhead_s <= 0:
            raise ValueError("step_overhead_s must be > 0")
        STEP_OVERHEAD_S = float(step_overhead_s)
    if bcast_budget_bytes is not None:
        if bcast_budget_bytes <= 0:
            raise ValueError("bcast_budget_bytes must be > 0")
        BCAST_BUDGET_BYTES = int(bcast_budget_bytes)


def kernel_model() -> dict:
    """The active measured-model constants (after env/config overrides)."""
    return {"step_overhead_s": STEP_OVERHEAD_S,
            "bcast_budget_bytes": BCAST_BUDGET_BYTES,
            "hbm_bytes_per_s": HBM_BYTES_PER_S}


def default_q_tile(rows: int, cols: int, planes: int = 1, *,
                   budget_bytes: int = VMEM_BUDGET_BYTES) -> int:
    """Default fused-kernel Q-tile from the VMEM working-set formula.

    The module docstring's per-step working set is
    4·(planes·R·C + Qt·C + C + Qt·R) bytes (f32), and past the point where
    the (Qt, C) query tile / (Qt, R) output tile approach the stored tile
    in size the kernel stops being stored-stream-bound — so the tile is
    sized to the stored planes (``stream``), clamped to what the budget
    allows (``cap``), floored at 8 (sublane granularity) and capped at 256,
    then rounded down to a power of two for friendly grid divisions.
    ``planes`` is 1 for point-code grids, 2 for ACAM [lo, hi] grids.

    This is the UNPIPELINED drivers' formula (the ``pipeline=False``
    off-switch keeps it so that path stays bit- and schedule-identical to
    the historical kernels); the pipelined drivers use the measured-model
    ``choose_q_tile`` hook instead.
    """
    words = budget_bytes // 4
    stream = (planes * rows * cols) // (rows + cols)
    cap = (words - planes * rows * cols - cols) // (rows + cols)
    qt = min(max(stream, 8), max(cap, 1), 256)
    return max(1, 1 << (int(qt).bit_length() - 1))


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def fused_vmem_bytes(vb: int, qt: int, segs: int, rows: int, cols: int,
                     planes: int = 1, *, itemsize: int = 4,
                     out_planes: int = 2, bcast_cols: int = 0) -> int:
    """VMEM one grid step of the fused kernels holds, in bytes.

    Counts every block Pallas double-buffers — the (vb, segs, R, C)
    stored planes, the (segs, qt, C) query tile, the (segs, 1, C)
    col_valid and (vb, 1, R) row_valid masks and the (vb, segs, qt, R)
    output tiles — each padded to the (sublane, 128-lane) VMEM tiling
    (8 sublanes for 32-bit, 32 for int8), plus the temporaries of the one
    (R, C) tile the body evaluates at a time: the f32 copy of the stored
    tile, a few (qt, R) distance/match blocks and, for distances without
    a matmul form, the (qt, R, bcast_cols) compare block.
    """
    sub = 8 * max(1, 4 // itemsize)
    lanes = 128
    stored = (planes * vb * segs * _ceil_to(rows, sub) * _ceil_to(cols, lanes)
              * itemsize)
    query = segs * _ceil_to(qt, sub) * _ceil_to(cols, lanes) * itemsize
    masks = 4 * 8 * (segs * _ceil_to(cols, lanes) + vb * _ceil_to(rows, lanes))
    out = out_planes * vb * segs * _ceil_to(qt, 8) * _ceil_to(rows, lanes) * 4
    tile = 4 * (planes * _ceil_to(rows, 8) * _ceil_to(cols, lanes)
                + 6 * _ceil_to(qt, 8) * _ceil_to(rows, lanes)
                + (qt * _ceil_to(rows, 8) * _ceil_to(bcast_cols, lanes)
                   if bcast_cols else 0))
    return 2 * (stored + query + masks + out) + tile


def resident_banks(banks: int, segs: int, rows: int, cols: int,
                   planes: int = 1, *, itemsize: int = 4,
                   budget_bytes: int = RESIDENT_BUDGET_BYTES) -> int:
    """Bank-block size for the pipelined driver's VMEM-resident fast path.

    Returns the largest divisor ``vb`` of ``banks`` whose double-buffered
    (vb, segs, rows, cols) stored planes and row masks fit a quarter of
    the budget (the rest holds the (vb, segs, qt, R) output tiles, which
    outweigh the stored block from qt > C/2 on, and the query tile and
    tile temporaries; ``choose_q_tile`` sizes those against the whole
    ``fused_vmem_bytes`` count).  ``vb == banks`` means the WHOLE store
    stays on-chip and is streamed from HBM once total; smaller ``vb``
    still streams the store exactly once per batch (block axis outermost)
    while Pallas prefetches the next bank block during the current
    block's distance math.  0 = not even one bank fits; the caller then
    runs the per-(R, C)-tile grid.
    """
    sub = 8 * max(1, 4 // itemsize)
    # double-buffered stored planes + (1, R) row mask, per bank
    per_bank = 2 * (planes * segs * _ceil_to(rows, sub)
                    * _ceil_to(cols, 128) * itemsize
                    + 4 * 8 * _ceil_to(rows, 128))
    share = budget_bytes // 4
    if banks < 1 or per_bank > share:
        return 0
    return max(v for v in range(1, banks + 1)
               if banks % v == 0 and v * per_bank <= share)


def choose_q_tile(rows: int, cols: int, planes: int = 1, *, banks: int = 1,
                  segs: int = 1, want_dist: bool = True, itemsize: int = 4,
                  bcast_cols: int = 0,
                  budget_bytes: int = RESIDENT_BUDGET_BYTES,
                  hbm_bytes_per_s: float = HBM_BYTES_PER_S,
                  step_overhead_s: Optional[float] = None) -> int:
    """Measured-model Q-tile autotune hook for the pipelined drivers.

    Walks the power-of-two ladder and scores every rung with the same
    HBM-traffic proxy ``plan.autotune.simulated_qps`` bills (stored-plane
    stream + query stream + output write-back over ``hbm_bytes_per_s``)
    PLUS a per-grid-step dispatch term — the cost interpret mode actually
    pays and the fixed formula ignored; ``benchmarks/kernel_bench.py``
    validates the ranking against wall clock.  Rungs whose VMEM count
    (``fused_vmem_bytes``: resident bank block, query and output tiles,
    tile temporaries) blows the budget are infeasible.  The choice is per
    GEOMETRY, not per batch: the runtime clamp ``qt = min(qt, Q)`` then
    makes per-call fixed overhead amortize monotonically in Q (larger
    batches reuse the same block schedule over more queries, which is the
    monotone-qps contract the Q-sweep rows assert).

    ``bcast_cols`` declares the lane width of the per-step VPU broadcast
    block for no-matmul distances (0 = no block: l2/dot run on the MXU and
    packed hamming reduces (Qt, R, W) with W = C/32 words).  When nonzero,
    rungs whose (qt, R, bcast_cols) compare block blows
    ``BCAST_BUDGET_BYTES`` are infeasible — the block dwarfs every streamed
    operand and growing it past the cache cliff is what made large-Q
    batches SLOWER per query (the throughput collapse this driver fixes).
    """
    if step_overhead_s is None:     # resolve at call time, not def time,
        step_overhead_s = STEP_OVERHEAD_S   # so set_kernel_model applies
    vb = resident_banks(banks, segs, rows, cols, planes, itemsize=itemsize,
                        budget_bytes=budget_bytes)
    out_planes = 2 if want_dist else 1
    stored = float(planes * banks * segs * rows * cols * itemsize)
    Q = 256.0          # reference batch: the ladder's top rung
    best, best_t = 1, None
    for qt in Q_TILES:
        nq = -(-int(Q) // qt)
        if vb:
            need = fused_vmem_bytes(vb, qt, segs, rows, cols, planes,
                                    itemsize=itemsize, out_planes=out_planes,
                                    bcast_cols=bcast_cols)
            steps = (banks // vb) * nq
            stream = stored                       # store on-chip once
            q_bytes = itemsize * Q * segs * cols * (banks // vb)
        else:
            need = fused_vmem_bytes(1, qt, 1, rows, cols, planes,
                                    itemsize=itemsize, out_planes=out_planes,
                                    bcast_cols=bcast_cols)
            steps = banks * segs * nq
            stream = stored * nq                  # re-streamed per Q-tile
            q_bytes = itemsize * Q * segs * cols * banks
        bcast_bytes = 4 * qt * rows * bcast_cols
        if need > budget_bytes or bcast_bytes > BCAST_BUDGET_BYTES:
            continue
        out_bytes = 4.0 * Q * banks * segs * rows * out_planes
        t = ((stream + q_bytes + out_bytes) / hbm_bytes_per_s
             + steps * step_overhead_s)
        if best_t is None or t < best_t:
            best, best_t = qt, t
    return best


@functools.partial(jax.jit,
                   static_argnames=("distance", "interpret"))
def cam_search_pallas(stored: jax.Array, query: jax.Array,
                      col_valid: jax.Array, *, distance: str = "l2",
                      interpret: bool = False) -> jax.Array:
    """stored (nv, nh, R, C), query (nh, C), col_valid (nh, C)
    -> dist (nv, nh, R): the batched kernel at Q = 1."""
    nv, nh, R, C = stored.shape
    assert query.shape == (nh, C), (query.shape, (nh, C))
    return cam_search_batched_pallas(stored, query[None], col_valid,
                                     distance=distance,
                                     interpret=interpret)[0]


# ---------------------------------------------------------------------------
# Query-batched kernel
# ---------------------------------------------------------------------------
def packed_hamming_block(stored, q) -> jax.Array:
    """stored (R, W) uint32, q (Qt, W) uint32 -> XOR+popcount (Qt, R) int32.

    The bit-packed TCAM match line (``kernels.hamming_pack``) as a tile
    function: don't-care/padded columns are zeroed in BOTH operands at pack
    time (``ops.pack_bits``), so XOR contributes nothing there and the
    count equals the col_valid-masked unpacked hamming distance exactly.
    """
    x = jnp.bitwise_xor(stored[None, :, :], q[:, None, :])
    return jnp.sum(jax.lax.population_count(x), axis=-1, dtype=jnp.int32)


def _row_sq_norms(stored, valid) -> jax.Array:
    """(R, C) f32 tile -> (R,) column-masked squared row norms.

    Taken as an MXU product with a mask row rather than a lane reduction:
    the reduction leaves the norms along sublanes, and broadcasting them
    across the query rows of the distance block then costs Mosaic a
    (Qt, R, C) VMEM temporary (16 MiB at Qt = 256, R = C = 128)."""
    w = jnp.broadcast_to(valid[None, :], (8, stored.shape[1]))
    return jax.lax.dot_general(
        w, stored * stored, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[0]


def _dist_block_batched(stored, q, valid, distance: str) -> jax.Array:
    """stored (R, C), q (Qt, C), valid (C,) -> dist (Qt, R).

    Integer dtypes select the exact quantized-code fast paths (only safe —
    and only requested by ``ops._fused_call`` — when the grid holds
    noise-free integral codes): uint32 operands are bit-packed 1-bit codes
    (XOR + popcount, ``valid`` already folded in at pack time), int8
    operands run the distances on narrow integers — the l2/dot cross term
    becomes an int8 MXU matmul accumulated in int32, at a quarter of the
    f32 HBM bandwidth.  Every int path produces the same f32 values as the
    float path: all products/sums are exact small integers.

    The f32 cross term runs at ``Precision.HIGHEST``: the TPU's default
    single bf16 pass would round 3-bit codes plus device noise to 8
    mantissa bits, far outside what the l2 norm expansion can absorb.
    """
    if stored.dtype == jnp.uint32 and distance == "hamming":
        return packed_hamming_block(stored, q).astype(jnp.float32)
    integer = jnp.issubdtype(stored.dtype, jnp.integer)
    if distance in ("l2", "dot"):
        # MXU formulation: fold the column mask into one operand so the
        # cross term is a plain (Qt, C) x (C, R) matmul.
        if integer:
            # the mask multiply runs in int32: TPUs have no int8 VPU
            # arithmetic, only the int8 MXU matmul
            qv = (q.astype(jnp.int32) * valid.astype(jnp.int32)[None, :]
                  ).astype(q.dtype)
            cross = jax.lax.dot_general(
                qv, stored, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32).astype(jnp.float32)
        else:
            qv = q * valid[None, :]
            cross = jax.lax.dot_general(
                qv, stored, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)     # (Qt, R)
        if distance == "dot":
            return -cross
        if integer:
            sf = stored.astype(jnp.float32)
            qf = q.astype(jnp.float32)
            sn = _row_sq_norms(sf, valid)
            qn = jnp.sum(qf * qf * valid[None, :], axis=-1)
        else:
            sn = _row_sq_norms(stored, valid)                        # (R,)
            qn = jnp.sum(q * qv, axis=-1)                            # (Qt,)
        return sn[None, :] - 2.0 * cross + qn[:, None]
    # VPU broadcast path: (Qt, R, C) block in registers (narrow ints
    # widen to int32 first, for the same reason as above).
    if integer:
        stored, q = stored.astype(jnp.int32), q.astype(jnp.int32)
    s = stored[None, :, :]
    qq = q[:, None, :]
    if distance == "hamming":
        d = (s != qq).astype(jnp.float32)
    elif distance == "l1":
        d = jnp.abs(s - qq)
    else:
        raise ValueError(distance)
    return jnp.sum(d * valid[None, None, :], axis=-1)


@functools.partial(jax.jit,
                   static_argnames=("distance", "q_tile", "interpret",
                                    "pipeline"))
def cam_search_batched_pallas(stored: jax.Array, queries: jax.Array,
                              col_valid: jax.Array, *,
                              distance: str = "l2",
                              q_tile: Optional[int] = None,
                              interpret: bool = False,
                              pipeline: bool = True) -> jax.Array:
    """stored (nv, nh, R, C), queries (Q, nh, C), col_valid (nh, C)
    -> dist (Q, nv, nh, R).

    The distance output of the fused kernel (``_fused_driver``) with no
    padding rows; its match output is discarded.  Same grids, schedule and
    ``pipeline`` / ``q_tile`` knobs as ``cam_search_fused_pallas``.
    """
    nv, R = stored.shape[0], stored.shape[2]
    dist, _ = _fused_driver((stored,), queries, col_valid,
                            jnp.ones((nv, R), jnp.float32),
                            distance=distance, sensing="exact",
                            sensing_limit=0.0, threshold=0.0, q_tile=q_tile,
                            want_dist=True, interpret=interpret,
                            pipeline=pipeline,
                            name="cam_search_batched_pallas")
    return dist


# ---------------------------------------------------------------------------
# Batched search with fused sense-and-reduce epilogue
# ---------------------------------------------------------------------------
def _sense_block(d: jax.Array, rv: jax.Array, sensing: str,
                 sensing_limit: float, threshold: float) -> jax.Array:
    """d (Qt, R) distances (inf on invalid rows), rv (R,) -> match (Qt, R)."""
    if sensing == "exact":
        m = d <= sensing_limit
    elif sensing == "best":
        # intra-subarray winner-take-all: min over the R match lines while
        # the distance block is still in VMEM
        m = d <= (jnp.min(d, axis=-1, keepdims=True) + sensing_limit)
    elif sensing == "threshold":
        m = d <= (threshold + sensing_limit)
    else:
        raise ValueError(sensing)
    return m.astype(jnp.float32) * rv[None, :]


def _tile_fused(tile_planes, qseg, valid, rowv, *, distance: str,
                sensing: str, sensing_limit: float, threshold: float):
    """One (R, C) tile end-to-end: distance, padding-row inf mask, sense.
    Shared verbatim by the kernel body and the jnp reference twin — the
    bit-identity of the kernel path is by construction."""
    if distance == "range":
        d = _range_block_batched(tile_planes[0], tile_planes[1], qseg, valid)
    else:
        d = _dist_block_batched(tile_planes[0], qseg, valid, distance)
    d = jnp.where(rowv[None, :] > 0, d, _INF)
    m = _sense_block(d, rowv, sensing, sensing_limit, threshold)
    return d, m


def _fused_kernel(*refs, n_planes: int, distance: str, sensing: str,
                  sensing_limit: float, threshold: float, want_dist: bool):
    """Fused kernel body, shared by both grids.

    Per grid step the refs hold a (vb, nh, R, C) bank block per stored
    plane, the (nh, qt, C) query tile, (nh, 1, C) col_valid, (vb, 1, R)
    row_valid and (vb, nh, qt, R) out tiles (the per-tile grid passes
    vb = nh = 1).  A ``fori_loop`` walks the block's vb·nh tiles one at a
    time, so neither compile time nor the live temporaries grow with the
    block size; each tile runs the same ``_tile_fused`` as the jnp twin."""
    plane_refs = refs[:n_planes]
    query_ref, valid_ref, rowv_ref = refs[n_planes:n_planes + 3]
    out_refs = refs[n_planes + 3:]
    vb, nh = plane_refs[0].shape[:2]

    def tile(t, carry):
        v, j = t // nh, t % nh
        d, m = _tile_fused(tuple(r[v, j] for r in plane_refs), query_ref[j],
                           valid_ref[j][0], rowv_ref[v][0],
                           distance=distance, sensing=sensing,
                           sensing_limit=sensing_limit, threshold=threshold)
        if want_dist:
            out_refs[0][v, j] = d
            out_refs[1][v, j] = m
        else:
            out_refs[0][v, j] = m
        return carry

    jax.lax.fori_loop(0, vb * nh, tile, 0)


def _content_dtype(stored_planes):
    """Kernel compute dtype: integer planes (the quantized-code / packed
    fast paths) keep their dtype; everything else runs the historical f32."""
    cdt = stored_planes[0].dtype
    if not jnp.issubdtype(cdt, jnp.integer):
        cdt = jnp.dtype(jnp.float32)
    return jnp.dtype(cdt)


def _fused_driver(stored_planes, queries: jax.Array,
                  col_valid: jax.Array, row_valid: jax.Array, *,
                  distance: str, sensing: str, sensing_limit: float,
                  threshold: float, q_tile: Optional[int], want_dist: bool,
                  interpret: bool, pipeline: bool, name: str):
    """Shared scaffolding for the fused batched kernels (point-code grids
    pass ``stored_planes=(stored,)`` with a real distance; ACAM range grids
    pass ``(lo, hi)`` with ``distance='range'``).

    ``pipeline=True`` (the default) runs the double-buffered bank-blocked
    schedule when ``resident_banks`` finds a block size: grid
    (nv/vb, Q/Qt) with the Q-tile axis innermost and a (vb, nh, R, C)
    stored BlockSpec indexed by the block axis alone — each stored byte
    crosses HBM once per BATCH (not once per Q-tile), Pallas prefetches
    block b+1 while block b computes, and ``vb == nv`` is the VMEM-resident
    fast path (whole store on-chip, grid (1, Q/Qt)).  ``q_tile=None`` is
    chosen per geometry by the measured-model ``choose_q_tile``.
    ``pipeline=False`` (and a bank too large for the budget) runs the
    (nv, nh, Q/Qt) per-tile grid with ``default_q_tile``.  Both grids run
    the same kernel body, so they compute identical tile math.

    Block layout: queries enter as (nh, Q, C), col_valid as (nh, 1, C),
    row_valid as (nv, 1, R), and the kernel writes bank-major
    (nv, nh, Q, R) outputs, so the last two dims of every block are a full
    array dim or an (8, 128)-aligned tile — Mosaic's tiling rule — and no
    block carries a size-1 sublane dim that the (8, 128) tiling would pad
    8x.  The result is transposed back to (Q, nv, nh, R) outside the
    kernel.  A Q-tile below the 8-row sublane tile rounds up to 8 (on
    both paths, so interpret mode runs the compiled schedule); the compiled
    path passes the VMEM count (``fused_vmem_bytes``) as Mosaic's
    ``vmem_limit_bytes``, and sizes blocks with the device's entry of
    ``DEVICE_MODELS``.

    The ``pallas_call`` runs under the named scope ``cam.kernel``, the
    trace's handle on the kernel (its ops' scope path holds it).  A custom
    call takes the innermost scope's name as its HLO instruction name, so
    ``name`` (the calling wrapper's) is restated inside that scope and the
    instruction keeps it: ``%cam_search_fused_pallas.N``."""
    from jax.experimental.pallas import tpu as pltpu

    nv, nh, R, C = stored_planes[0].shape
    Q = queries.shape[0]
    n_planes = len(stored_planes)
    assert queries.shape == (Q, nh, C), (queries.shape, (Q, nh, C))
    assert row_valid.shape == (nv, R), (row_valid.shape, (nv, R))
    cdt = _content_dtype(stored_planes)
    model = device_model()
    budget = model["vmem_budget_bytes"]
    vb = (resident_banks(nv, nh, R, C, n_planes, itemsize=cdt.itemsize,
                         budget_bytes=budget)
          if pipeline else 0)
    # l2/dot take the MXU matmul form; everything else broadcasts a
    # (Qt, R, C) compare block on the VPU (for packed hamming C is
    # already the packed word width, so the cap never binds)
    bcast = 0 if distance in ("l2", "dot") else C
    if q_tile is None:
        if vb:
            q_tile = choose_q_tile(R, C, n_planes, banks=nv, segs=nh,
                                   want_dist=want_dist,
                                   itemsize=cdt.itemsize, bcast_cols=bcast,
                                   budget_bytes=budget,
                                   hbm_bytes_per_s=model["hbm_bytes_per_s"])
        else:
            q_tile = default_q_tile(R, C, n_planes)
    qt = max(1, min(q_tile, Q))
    if qt < Q and qt % 8:           # Mosaic's 8-row sublane tile
        qt = min(8, Q)
    pad = (-Q) % qt
    if pad:
        queries = jnp.pad(queries, ((0, pad), (0, 0), (0, 0)))
    nq = (Q + pad) // qt
    shape = jax.ShapeDtypeStruct((nv, nh, Q + pad, R), jnp.float32)
    planes = tuple(p.astype(cdt) for p in stored_planes)
    qs = jnp.transpose(queries.astype(cdt), (1, 0, 2))        # (nh, Qp, C)
    cv = col_valid.astype(jnp.float32)[:, None, :]            # (nh, 1, C)
    rv = row_valid.astype(jnp.float32)[:, None, :]            # (nv, 1, R)
    body = functools.partial(
        _fused_kernel, n_planes=n_planes, distance=distance,
        sensing=sensing, sensing_limit=sensing_limit, threshold=threshold,
        want_dist=want_dist)
    out_planes = 2 if want_dist else 1

    def need(v):
        return fused_vmem_bytes(v, qt, nh if vb else 1, R, C, n_planes,
                                itemsize=cdt.itemsize,
                                out_planes=out_planes, bcast_cols=bcast)

    # an explicit q_tile can outgrow the block: shrink vb until it fits
    while vb > 1 and need(vb) > budget:
        vb = max(v for v in range(1, vb) if nv % v == 0)
    if vb:
        grid = (nv // vb, nq)
        stored_spec = pl.BlockSpec((vb, nh, R, C), lambda b, k: (b, 0, 0, 0))
        in_specs = [stored_spec] * n_planes + [
            pl.BlockSpec((nh, qt, C), lambda b, k: (0, k, 0)),
            pl.BlockSpec((nh, 1, C), lambda b, k: (0, 0, 0)),
            pl.BlockSpec((vb, 1, R), lambda b, k: (b, 0, 0)),
        ]
        spec = pl.BlockSpec((vb, nh, qt, R), lambda b, k: (b, 0, k, 0))
    else:
        grid = (nv, nh, nq)
        stored_spec = pl.BlockSpec((1, 1, R, C),
                                   lambda i, j, k: (i, j, 0, 0))
        in_specs = [stored_spec] * n_planes + [
            pl.BlockSpec((1, qt, C), lambda i, j, k: (j, k, 0)),
            pl.BlockSpec((1, 1, C), lambda i, j, k: (j, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda i, j, k: (i, 0, 0)),
        ]
        spec = pl.BlockSpec((1, 1, qt, R), lambda i, j, k: (i, j, k, 0))
    with jax.named_scope("cam.kernel"), jax.named_scope(name):
        out = pl.pallas_call(
            body,
            grid=grid,
            in_specs=in_specs,
            out_specs=(spec, spec) if want_dist else spec,
            out_shape=(shape, shape) if want_dist else shape,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=need(max(vb, 1)) + (4 << 20)),
            interpret=interpret,
        )(*planes, qs, cv, rv)

    def back(o):                        # (nv, nh, Qp, R) -> (Q, nv, nh, R)
        return jnp.transpose(o, (2, 0, 1, 3))[:Q]

    if want_dist:
        return back(out[0]), back(out[1])
    return back(out)


@functools.partial(jax.jit,
                   static_argnames=("distance", "sensing", "sensing_limit",
                                    "threshold", "q_tile", "want_dist",
                                    "interpret", "pipeline"))
def cam_search_fused_pallas(stored: jax.Array, queries: jax.Array,
                            col_valid: jax.Array, row_valid: jax.Array, *,
                            distance: str = "l2", sensing: str = "best",
                            sensing_limit: float = 0.0,
                            threshold: float = 0.0,
                            q_tile: Optional[int] = None,
                            want_dist: bool = True,
                            interpret: bool = False,
                            pipeline: bool = True):
    """Batched search + in-kernel sense amplifier.

    stored (nv, nh, R, C), queries (Q, nh, C), col_valid (nh, C),
    row_valid (nv, R).

    Returns ``(dist, match)`` each (Q, nv, nh, R) — or ``match`` alone when
    ``want_dist=False``, in which case the float distance tensor is never
    written to HBM (exact/threshold AND-merge path).  Distances on padding
    rows are +inf, matching ``core.subarray.subarray_query``.

    ``pipeline=True`` selects the bank-blocked double-buffered schedule
    (see ``_fused_driver``); ``pipeline=False`` runs the same kernel body
    on the per-tile grid, with bit-identical results.
    """
    return _fused_driver((stored,), queries, col_valid, row_valid,
                         distance=distance, sensing=sensing,
                         sensing_limit=float(sensing_limit),
                         threshold=float(threshold),
                         q_tile=q_tile, want_dist=want_dist,
                         interpret=interpret, pipeline=pipeline,
                         name="cam_search_fused_pallas")


# ---------------------------------------------------------------------------
# ACAM range match with fused sense-and-reduce epilogue
# ---------------------------------------------------------------------------
def _range_block_batched(lo, hi, q, valid) -> jax.Array:
    """lo/hi (R, C), q (Qt, C), valid (C,) -> violation counts (Qt, R).

    A cell votes a violation when the query value falls outside its stored
    closed interval [lo, hi]; padded columns are masked out.  Counts are
    small integers in f32, so the sum is exact in any reduction order."""
    qq = q[:, None, :]                                   # (Qt, 1, C)
    viol = ((qq < lo[None, :, :]) | (qq > hi[None, :, :])
            ).astype(jnp.float32)
    return jnp.sum(viol * valid[None, None, :], axis=-1)


@functools.partial(jax.jit,
                   static_argnames=("sensing", "sensing_limit", "threshold",
                                    "q_tile", "want_dist", "interpret",
                                    "pipeline"))
def cam_range_fused_pallas(stored_lo: jax.Array, stored_hi: jax.Array,
                           queries: jax.Array, col_valid: jax.Array,
                           row_valid: jax.Array, *, sensing: str = "exact",
                           sensing_limit: float = 0.0,
                           threshold: float = 0.0,
                           q_tile: Optional[int] = None,
                           want_dist: bool = True,
                           interpret: bool = False,
                           pipeline: bool = True):
    """Batched ACAM range search + in-kernel sense amplifier.

    stored_lo / stored_hi (nv, nh, R, C) — the two planes of a 5-D
    (nv, nh, R, C, 2) range grid, split by the caller so every BlockSpec
    keeps a dense lane dim; queries (Q, nh, C); col_valid (nh, C);
    row_valid (nv, R).

    Same contract as ``cam_search_fused_pallas``: returns ``(dist, match)``
    each (Q, nv, nh, R) — dist is the range-violation count, +inf on
    padding rows — or ``match`` alone when ``want_dist=False`` (the count
    tensor then never hits HBM; the ACAM exact-match AND-merge path).
    Both stored planes stream from HBM once per query batch, on the same
    grids as the point kernel (``_fused_driver``).
    """
    assert stored_hi.shape == stored_lo.shape, (stored_hi.shape,
                                                stored_lo.shape)
    return _fused_driver((stored_lo, stored_hi), queries, col_valid,
                         row_valid, distance="range", sensing=sensing,
                         sensing_limit=float(sensing_limit),
                         threshold=float(threshold),
                         q_tile=q_tile, want_dist=want_dist,
                         interpret=interpret, pipeline=pipeline,
                         name="cam_range_fused_pallas")


# ---------------------------------------------------------------------------
# jnp twin of the fused kernels (small-batch interpret-mode dispatch target)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=("distance", "sensing", "sensing_limit",
                                    "threshold", "want_dist"))
def cam_fused_reference(stored_planes, queries: jax.Array,
                        col_valid: jax.Array, row_valid: jax.Array, *,
                        distance: str, sensing: str,
                        sensing_limit: float = 0.0, threshold: float = 0.0,
                        want_dist: bool = True):
    """Pure-jnp twin of ``cam_search_fused_pallas`` / ``cam_range_fused_
    pallas``, built from the SAME per-tile function the kernel body calls
    (``_tile_fused``) and walking the (nv, nh) tiles one at a time as the
    body does — so its results are the kernels', by construction.  ``ops._fused_call`` dispatches here for interpret-mode
    batches below ``SMALL_Q_CROSSOVER``, where per-grid-step emulation
    overhead dominates (BENCH: kernel_acam_range_q1 ran at 0.18x of jnp).

    ``stored_planes``: (stored,) point grids or (lo, hi) for
    ``distance='range'``, each (nv, nh, R, C); same outputs as the kernels.
    """
    cdt = _content_dtype(stored_planes)
    planes = tuple(p.astype(cdt) for p in stored_planes)
    # pad the batch to the kernel's 8-row sublane tile, so each tile's
    # cross-term matmul has the kernel's shape (and rounding)
    Q = queries.shape[0]
    q = jnp.pad(queries.astype(cdt), ((0, (-Q) % 8), (0, 0), (0, 0)))
    cv = col_valid.astype(jnp.float32)
    rv = row_valid.astype(jnp.float32)
    nv, nh, R = planes[0].shape[:3]

    def tile(t):                 # the kernel body's walk, one tile a step
        v, j = t // nh, t % nh
        return _tile_fused(tuple(p[v, j] for p in planes), q[:, j], cv[j],
                           rv[v], distance=distance, sensing=sensing,
                           sensing_limit=float(sensing_limit),
                           threshold=float(threshold))

    d, m = jax.lax.map(tile, jnp.arange(nv * nh))        # (nv*nh, Q, R)

    def back(o):                 # -> (Q, nv, nh, R)
        return jnp.transpose(o.reshape(nv, nh, -1, R), (2, 0, 1, 3))[:Q]

    return (back(d), back(m)) if want_dist else back(m)

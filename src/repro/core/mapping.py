"""Mapping submodule (paper §III-C): partition stored data into subarrays.

Given stored data of K entries × N dims and a subarray of R rows × C cols,
partition into an (nv, nh) grid of (R, C) subarrays:

    nv = ceil(K / R)   vertical   blocks (entries split across subarrays)
    nh = ceil(N / C)   horizontal blocks (dimensions split across subarrays)

Padding cells/rows are tracked with masks so that search results are
identical to the unpartitioned reference (a property test asserts this).
The 2-D grid is then laid onto the bank-mat-array-subarray hierarchy by the
performance estimator.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class GridSpec:
    K: int           # entries
    N: int           # dims
    R: int           # rows / subarray
    C: int           # cols / subarray
    nv: int          # vertical blocks
    nh: int          # horizontal blocks

    @property
    def n_subarrays(self) -> int:
        return self.nv * self.nh

    @property
    def padded_K(self) -> int:
        return self.nv * self.R

    @property
    def padded_N(self) -> int:
        return self.nh * self.C


def grid_spec(K: int, N: int, R: int, C: int, capacity: int = 0) -> GridSpec:
    """``capacity`` reserves row head-room: the grid is sized for
    ``max(K, capacity)`` rows so online inserts find free slots, while
    ``K`` (and therefore ``row_valid_mask``) still describes the rows
    actually written."""
    return GridSpec(K=K, N=N, R=R, C=C,
                    nv=math.ceil(max(K, capacity) / R), nh=math.ceil(N / C))


def partition_stored(data: jax.Array, spec: GridSpec) -> jax.Array:
    """(K, N[, 2]) -> (nv, nh, R, C[, 2]) with zero padding.

    The optional trailing dim carries ACAM [lo, hi] ranges."""
    K, N = data.shape[:2]
    assert (K, N) == (spec.K, spec.N), (data.shape, spec)
    extra = data.shape[2:]
    pad = ((0, spec.padded_K - K), (0, spec.padded_N - N)) +         ((0, 0),) * len(extra)
    x = jnp.pad(data, pad)
    x = x.reshape(spec.nv, spec.R, spec.nh, spec.C, *extra)
    perm = (0, 2, 1, 3) + tuple(range(4, 4 + len(extra)))
    return x.transpose(*perm)  # (nv, nh, R, C[, 2])


def partition_rows(rows: jax.Array, spec: GridSpec) -> jax.Array:
    """(M, N[, 2]) -> (M, nh, C[, 2]) row segments (the per-row view of
    ``partition_stored``, for incremental writes into existing slots)."""
    M, N = rows.shape[:2]
    assert N == spec.N, (rows.shape, spec)
    extra = rows.shape[2:]
    pad = ((0, 0), (0, spec.padded_N - N)) + ((0, 0),) * len(extra)
    x = jnp.pad(rows, pad)
    return x.reshape(M, spec.nh, spec.C, *extra)


def partition_query(q: jax.Array, spec: GridSpec) -> jax.Array:
    """(..., N) -> (..., nh, C) query segments."""
    with jax.named_scope("cam.quantize"):
        pad = [(0, 0)] * (q.ndim - 1) + [(0, spec.padded_N - spec.N)]
        x = jnp.pad(q, pad)
        return x.reshape(*q.shape[:-1], spec.nh, spec.C)


def col_valid_mask(spec: GridSpec) -> jax.Array:
    """(nh, C) 1.0 where the column holds real data, 0.0 where padding."""
    idx = jnp.arange(spec.padded_N).reshape(spec.nh, spec.C)
    return (idx < spec.N).astype(jnp.float32)


def row_valid_mask(spec: GridSpec) -> jax.Array:
    """(nv, R) 1.0 where the row holds a real entry."""
    idx = jnp.arange(spec.padded_K).reshape(spec.nv, spec.R)
    return (idx < spec.K).astype(jnp.float32)


def global_row_index(spec: GridSpec) -> jax.Array:
    """(nv, R) global entry index of each subarray row."""
    return jnp.arange(spec.padded_K).reshape(spec.nv, spec.R)


# ---------------------------------------------------------------------------
# grouped row placement (query-compiler write planning)
# ---------------------------------------------------------------------------
def plan_group_offsets(group_sizes, R: int, align: bool = False):
    """Row offsets for placing consecutive row GROUPS (the query compiler's
    co-fired predicate sets — e.g. one tree of an ensemble) into one store.

    ``align=True`` rounds each group's start up to a subarray-row boundary
    (multiples of ``R``), so after ``partition_stored`` every group owns
    whole nv banks and co-fired predicates land in the same banks — no
    bank mixes rows of two groups (the gap rows are filler the compiler
    makes unmatchable).  ``align=False`` packs groups densely.

    Returns ``(offsets, total_rows)`` with ``offsets[i]`` the first row of
    group ``i``.
    """
    if R < 1:
        raise ValueError("R must be >= 1")
    offsets = []
    total = 0
    for s in group_sizes:
        if s < 1:
            raise ValueError("every group needs at least one row")
        if align and total % R:
            total += R - total % R
        offsets.append(total)
        total += int(s)
    return np.asarray(offsets, np.int64), total


# ---------------------------------------------------------------------------
# IVF-style clustered placement (search-cascade stage 1)
# ---------------------------------------------------------------------------
def cluster_permutation(values: jax.Array, nv: int, *, n_clusters: int = 0,
                        iters: int = 4, chunk: int = 65536) -> jax.Array:
    """Clustered row placement: k-means over the code rows, stable-sorted
    by cluster id, so similar entries land in contiguous row ranges — i.e.
    the same nv-bank after ``partition_stored``.  The bank prefilter can
    then prune whole banks without losing a query's near neighbours.

    values (K, D) code-domain rows (ACAM stores pass range midpoints).
    Deterministic (strided centroid init, fixed Lloyd iteration count) and
    jit-friendly; assignment is chunked over ``chunk``-row blocks so the
    (chunk, n_clusters) distance block — not (K, n_clusters) — bounds
    memory at millions of rows.

    Returns ``perm`` (K,) int32 with ``placed[i] = orig[perm[i]]``; the
    stable sort keeps original order within a cluster, so ``nv`` clusters
    of equal size reproduce identity placement on pre-sorted data.
    """
    K, D = values.shape
    nc = max(1, min(n_clusters or min(nv, 128), K))
    x = values.astype(jnp.float32)
    stride = max(1, K // nc)
    cent = x[::stride][:nc]
    nc = cent.shape[0]

    def assign(c):
        cn = jnp.sum(c * c, axis=-1)

        def one(block):
            # argmin ||b - c||^2 = argmin (||c||^2 - 2 b.c) — ||b||^2 is
            # constant per row and cannot change the argmin
            d = cn[None, :] - 2.0 * block @ c.T
            return jnp.argmin(d, axis=-1).astype(jnp.int32)

        if K <= chunk:
            return one(x)
        pad = (-K) % chunk
        xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, chunk, D)
        return jax.lax.map(one, xb).reshape(-1)[:K]

    a = assign(cent)
    for _ in range(iters):
        sums = jnp.zeros((nc, D), jnp.float32).at[a].add(x)
        counts = jnp.zeros((nc, 1), jnp.float32).at[a].add(1.0)
        cent = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), cent)
        a = assign(cent)
    return jnp.argsort(a, stable=True).astype(jnp.int32)


def placement_perm(values: jax.Array, spec: GridSpec) -> jax.Array:
    """(padded_K,) placement permutation: clustered on the real rows,
    identity on the padding rows (which stay at the end, so
    ``row_valid_mask`` is unchanged).  ``placed[i] = orig[perm[i]]``."""
    perm = cluster_permutation(values, spec.nv)
    return jnp.concatenate(
        [perm, jnp.arange(spec.K, spec.padded_K, dtype=jnp.int32)])

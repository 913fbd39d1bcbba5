"""Plain reference of a served best-match kNN search over a CAM store.

It follows the configuration's semantics and nothing of the program: the
stored rows are quantized to ``data_bits`` codes with the data's own range
(linear, round half to even, clipped), the device's D2D programming noise
is drawn as the configuration's per-row-slot draw defines it (slot ``s``
takes ``normal(fold_in(write_key, s), (ceil(N / C), C))``, first N values,
times ``variation_std``), queries are quantized with the store's range, and
a query's answer is the ``match_param`` rows of least distance (squared
l2, or negative inner product for ``dot``) over the N real columns.

Candidates come from an f32 pass on the device at ``HIGHEST`` precision
(query blocks by row blocks, two-stage exact top-k); they and the served
rows are then ranked again in float64 on the host, which is exact for
integer codes and far below the program's f32 rounding for noisy ones.

``gap`` is the comparison: per query, the served rows' float64 distances
sorted, less the reference's k smallest, at each rank; the largest such
excess over all queries compared.  Ties at rank k cost nothing (any of the
tied rows gives the same distances); an invalid or repeated id reads
``inf``.

The controls put a lowered-precision copy of this reference in the
program's place (``control_answers``): ``bf16x3`` takes the f32 cross term
in three bf16 passes (XLA's ``Precision.HIGH``, written out with explicit
roundings so that it means the same on every backend) and ranks by it
alone; ``bf16_quant``
quantizes the queries in bfloat16; ``int4`` casts the codes through int4.
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
import numpy as np

N_CAND_EXTRA = 22         # candidates beyond k, re-ranked in float64
ROW_BLOCK = 8192          # rows per first-stage top-k block
QUERY_BLOCK = 128         # queries per device pass


def supported(cam: dict) -> None:
    """Raise unless the reference covers this configuration."""
    app, arch, circ, dev = (cam["app"], cam["arch"], cam["circuit"],
                            cam.get("device", {}))
    sim = cam.get("sim", {})
    need = {
        "match_type best": app.get("match_type") == "best",
        "distance l2 or dot": app.get("distance") in ("l2", "dot"),
        "mcam cells": circ.get("cell_type") == "mcam",
        "h_merge adder": arch.get("h_merge") == "adder",
        "v_merge comparator": arch.get("v_merge") == "comparator",
        "no C2C noise": dev.get("variation", "none") in ("none", "d2d"),
        "stat noise": dev.get("variation_spec", "stat") == "stat",
        "D2D drawn per row slot": (dev.get("variation", "none") == "none"
                                   or sim.get("d2d_fold") == "row"),
        "no cascade": sim.get("prefilter", "off") == "off",
        "no head-room": sim.get("capacity", 0) == 0,
        "reliability off": not cam.get("reliability", {}).get("enabled",
                                                              False),
    }
    missing = [k for k, ok in need.items() if not ok]
    if missing:
        raise ValueError(f"the kNN reference does not cover: {missing}")


def quantize(x, lo, hi, bits: int, dtype=jnp.float32):
    levels = (1 << bits) - 1
    x, lo, hi = (jnp.asarray(v, dtype) for v in (x, lo, hi))
    scale = jnp.where(hi > lo, (hi - lo) / levels, jnp.ones((), dtype))
    return jnp.clip(jnp.round((x - lo) / scale), 0, levels).astype(
        jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "cols", "std"))
def _sensed(data, key, *, bits: int, cols: int, std: float):
    lo, hi = jnp.min(data), jnp.max(data)
    codes = quantize(data, lo, hi, bits)
    if std:
        K, N = data.shape
        nh = -(-N // cols)
        noise = jax.vmap(lambda s: jax.random.normal(
            jax.random.fold_in(key, s), (nh, cols), jnp.float32))(
                jnp.arange(K, dtype=jnp.int32))
        codes = codes + std * noise.reshape(K, nh * cols)[:, :N]
    return codes, lo, hi


def sensed_rows(cam: dict, data, write_key):
    """(K, N) stored values as the cells hold them, and the store's
    quantization range (lo, hi)."""
    dev = cam.get("device", {})
    std = (float(dev.get("variation_std", 0.0))
           if dev.get("variation", "none") == "d2d" else 0.0)
    return _sensed(data, write_key, bits=cam["app"]["data_bits"],
                   cols=cam["circuit"]["cols"], std=std)


def _bf16(x):
    """x rounded to bfloat16's 8 significant bits, kept in f32 (an explicit
    rounding XLA may not fold away, as it may a convert pair)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _cross(q, rows, how: str):
    """(B, N) x (K, N) -> (B, K) inner products."""
    dims = (((1,), (1,)), ((), ()))
    if how == "highest":
        return jax.lax.dot_general(q, rows, dims,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
    if how == "bf16x3":
        # Precision.HIGH written out: hi*hi + hi*lo + lo*hi of the bf16
        # splits, each product exact in f32, so it reads the same on the
        # CPU (which ignores HIGH) as on the TPU
        qh, rh = _bf16(q), _bf16(rows)
        ql, rl = _bf16(q - qh), _bf16(rows - rh)

        def mm(a, b):
            return jax.lax.dot_general(a, b, dims,
                                       precision=jax.lax.Precision.HIGHEST,
                                       preferred_element_type=jnp.float32)
        return mm(qh, rh) + (mm(qh, rl) + mm(ql, rh))
    raise ValueError(how)


@functools.partial(jax.jit, static_argnames=("distance", "n_cand", "how"))
def candidates(rows, qcodes, *, distance: str, n_cand: int,
               how: str = "highest"):
    """(B, n_cand) ids of the least distances, best first."""
    K = rows.shape[0]
    cross = _cross(qcodes, rows, how)
    if distance == "l2":
        rn = jnp.sum(rows * rows, axis=-1)
        qn = jnp.sum(qcodes * qcodes, axis=-1)
        d = rn[None, :] - 2.0 * cross + qn[:, None]
    else:
        d = -cross
    blk = min(ROW_BLOCK, K)
    nb = -(-K // blk)
    d = jnp.pad(d, ((0, 0), (0, nb * blk - K)), constant_values=jnp.inf)
    m = min(n_cand, blk)
    v, i = jax.lax.top_k(-d.reshape(d.shape[0], nb, blk), m)
    i = i + (jnp.arange(nb) * blk)[None, :, None]
    v, i = v.reshape(d.shape[0], -1), i.reshape(d.shape[0], -1)
    _, j = jax.lax.top_k(v, min(n_cand, v.shape[1]))
    return jnp.take_along_axis(i, j, axis=1)


def all_candidates(rows, qcodes, *, distance: str, n_cand: int,
                   how: str = "highest") -> np.ndarray:
    """``candidates`` over every query, in fixed-size blocks (one
    compiled shape; the last block padded)."""
    Q = qcodes.shape[0]
    out = []
    for s in range(0, Q, QUERY_BLOCK):
        blk = qcodes[s:s + QUERY_BLOCK]
        pad = QUERY_BLOCK - blk.shape[0]
        if pad:
            blk = jnp.pad(blk, ((0, pad), (0, 0)))
        ids = candidates(rows, blk, distance=distance, n_cand=n_cand,
                         how=how)
        out.append(np.asarray(ids)[:QUERY_BLOCK - pad])
    return np.concatenate(out)


def exact(rows, qcodes: np.ndarray, ids: np.ndarray, distance: str
          ) -> np.ndarray:
    """float64 distances of queries (S, N) to rows ``ids`` (S, m); inf for
    an id out of range or repeated within its query."""
    K = rows.shape[0]
    ids = np.asarray(ids, np.int64)
    ok = (ids >= 0) & (ids < K)
    dup = np.zeros(ids.shape, bool)
    for j in range(1, ids.shape[1]):
        dup[:, j] = (ids[:, :j] == ids[:, j:j + 1]).any(axis=1)
    safe = np.where(ok, ids, 0)
    g = np.asarray(jnp.take(rows, jnp.asarray(safe.reshape(-1)), axis=0),
                   np.float64).reshape(*ids.shape, -1)
    q = np.asarray(qcodes, np.float64)[:, None, :]
    d = ((g - q) ** 2).sum(-1) if distance == "l2" else -(g * q).sum(-1)
    return np.where(ok & ~dup, d, np.inf)


def gaps(rows, qcodes, cand: np.ndarray, qidx: np.ndarray,
         served: np.ndarray, distance: str) -> np.ndarray:
    """Per answer, the largest excess of the served rows' sorted distances
    over the reference's k least (0 where the served set is a best one).

    ``qcodes`` (U, N) are the distinct queries and ``cand`` (U, m) their
    reference candidates; answer ``i`` served ``served[i]`` for query
    ``qidx[i]``."""
    k = served.shape[1]
    qn = np.asarray(qcodes)
    d_ref = np.sort(exact(rows, qn, cand, distance), axis=1)[:, :k]
    d_srv = np.sort(exact(rows, qn[qidx], served, distance), axis=1)
    with np.errstate(invalid="ignore"):
        g = d_srv - d_ref[qidx]
    g = np.where(np.isnan(g), np.inf, g)
    return g.max(axis=1)


class Reference:
    """The reference store of one configuration, rebuilt from the data
    the benchmark generated (never from the program's state)."""

    def __init__(self, config: dict, data, write_key):
        cam = config["cam"]
        supported(cam)
        self.config = config
        self.distance = cam["app"]["distance"]
        self.bits = cam["app"]["data_bits"]
        self.k = cam["app"]["match_param"]
        self.rows, self.lo, self.hi = sensed_rows(cam, data, write_key)
        self.n_cand = self.k + N_CAND_EXTRA

    def query_codes(self, queries, dtype=jnp.float32):
        return quantize(queries, self.lo, self.hi, self.bits, dtype)

    def candidates(self, qcodes, how: str = "highest") -> np.ndarray:
        return all_candidates(self.rows, qcodes, distance=self.distance,
                              n_cand=self.n_cand, how=how)

    def gaps(self, queries, qidx: np.ndarray, served: np.ndarray
             ) -> np.ndarray:
        """``gaps`` of answers ``served`` (M, k) to the distinct
        ``queries`` (U, N), answer i to query ``qidx[i]``."""
        qc = self.query_codes(jnp.asarray(queries))
        return gaps(self.rows, qc, self.candidates(qc), np.asarray(qidx),
                    served, self.distance)

    def control_answers(self, queries, control: str) -> np.ndarray:
        """The answers a lowered-precision reference would serve."""
        q = jnp.asarray(queries)
        if control == "bf16x3":
            qc = self.query_codes(q)
            return self.candidates(qc, how="bf16x3")[:, :self.k]
        if control == "bf16_quant":
            qc = self.query_codes(q, jnp.bfloat16)
        elif control == "int4":
            qc = self.query_codes(q).astype(jnp.int4).astype(jnp.float32)
        else:
            raise ValueError(f"unknown control {control!r}")
        rows = (self.rows.astype(jnp.int4).astype(jnp.float32)
                if control == "int4" else self.rows)
        cand = all_candidates(rows, qc, distance=self.distance,
                              n_cand=self.n_cand)
        d = exact(rows, np.asarray(qc), cand, self.distance)
        order = np.argsort(d, axis=1, kind="stable")[:, :self.k]
        return np.take_along_axis(cand, order, axis=1)

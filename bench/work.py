"""Least work of one served search step, from the configuration alone.

The counts depend only on the cell's configuration and the step's padded
width, never on how the program implements the search, so a change of
kernel, merge or stored dtype cannot make them stale.  With Q the step's
padded width, K the padded row count, N the query dims and k the best-match
width:

    ops   = 2 * Q * K * N                      (one multiply-add per cell)
    bytes = K * N * w + Q * N * 4 + Q * k * 8  (stored codes once, f32
                                                queries in, (value, index)
                                                pairs out)

w is ``data_bits / 8`` for noise-free integer codes, and 4 for noisy analog
cells, whose stored values are f32 by the configuration.  Integer codes are
timed against the chip's int8 peak, float cells against its bf16 peak: the
TPU v5e publishes no f32 peak, so the f32 work is held to the bf16 figure
(a lower bound on the least time, never above it).
"""
from __future__ import annotations

import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(kind: str, path: str | None = None) -> dict:
    """Published peaks of ``kind`` (``jax.Device.device_kind``); a kind
    missing from ``peaks.json`` is an error, never a default."""
    with open(path or os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def int_codes(cam: dict) -> bool:
    """Stored cells hold exact small integers: quantized point codes with
    no device variation and reliability off."""
    app, dev = cam["app"], cam.get("device", {})
    rel = cam.get("reliability", {}).get("enabled", False)
    return (0 < app.get("data_bits", 0) <= 8
            and dev.get("variation", "none") == "none"
            and cam["circuit"].get("cell_type") != "acam" and not rel)


def search_work(config: dict, q: int) -> dict:
    """Operations and bytes of one search step of ``q`` padded queries."""
    cam = config["cam"]
    rows_per_sub = cam["circuit"]["rows"]
    K = math.ceil(config["rows"] / rows_per_sub) * rows_per_sub
    N = config["dims"]
    k = cam["app"]["match_param"]
    integer = int_codes(cam)
    w = cam["app"]["data_bits"] / 8 if integer else 4
    return {"ops": 2.0 * q * K * N,
            "bytes": K * N * w + q * N * 4.0 + q * k * 8.0,
            "peak_ops": "int8_ops_per_s" if integer else "bf16_flops_per_s",
            "K": K, "N": N, "Q": q, "k": k, "w": w}


def least_time(work: dict, peaks: dict) -> tuple[float, str]:
    """(seconds, binding bound): max(ops / peak ops, bytes / peak bw)."""
    t_ops = work["ops"] / peaks[work["peak_ops"]]
    t_mem = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")

"""Kernel, on a store sharded over the cell's chips: the least time of one
chip's share of a step's work as a share, in %, of the fused search
kernel's device time per step and per chip (``kernel_ms``).

``bench/work.py`` counts the whole store's work, K rows; each of the
``ctx.chips`` chips searches K / chips of them against the whole query
batch.  The chip's share is ``work.search_work`` with the row terms
divided by the chip count:

    ops   = 2 * Q * (K / c) * N
    bytes = (K / c) * N * w + Q * N * 4 + Q * k * 8

timed against the same peaks as ``cam_search_roofline``.  On one chip it
reads as ``cam_search_roofline`` does."""
from bench import work
from bench.metrics import kernel_ms


def chip_work(total: dict, chips: int) -> dict:
    """``work.search_work``'s counts for one of ``chips`` chips."""
    rows = total["K"] / chips
    q, n, k, w = total["Q"], total["N"], total["k"], total["w"]
    return dict(total, K=rows, ops=2.0 * q * rows * n,
                bytes=rows * n * w + q * n * 4.0 + q * k * 8.0)


def read(ctx):
    ms = kernel_ms.read(ctx)
    if not ms:
        return None
    least, _ = work.least_time(chip_work(ctx.work, ctx.chips),
                               work.load_peaks(ctx.device_kind))
    return 100.0 * least / (ms / 1e3)

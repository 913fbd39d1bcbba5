"""Kernel: the least time of one step's work (``bench/work.py``) as a
share, in %, of the fused search kernel's device time per step
(``kernel_ms``).  The kernel does every operation and reads every stored
byte the work counts, so a reading above 100% means the counts or the
kernel match are wrong."""
from bench import work
from bench.metrics import kernel_ms


def read(ctx):
    ms = kernel_ms.read(ctx)
    if not ms:
        return None
    least, _ = work.least_time(ctx.work, work.load_peaks(ctx.device_kind))
    return 100.0 * least / (ms / 1e3)

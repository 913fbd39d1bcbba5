"""Search program: the least time of one step's work (``bench/work.py``,
from the configuration alone) as a share, in %, of the search program's
device time per step (``search_device_ms``)."""
from bench import work
from bench.metrics import search_device_ms


def read(ctx):
    ms = search_device_ms.read(ctx)
    if not ms:
        return None
    least, _ = work.least_time(ctx.work, work.load_peaks(ctx.device_kind))
    return 100.0 * least / (ms / 1e3)

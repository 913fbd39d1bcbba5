"""Device: share of the measured window, in %, in which no operation ran
on the device (1 - union of op intervals / window), averaged over the
cell's chips."""


def read(ctx):
    window = ctx.hi - ctx.lo
    if window <= 0 or not ctx.ops:
        return None
    busy = sum(ctx.tr.busy_ns(ops) for ops in ctx.ops.values()) / ctx.chips
    return 100.0 * (1.0 - busy / window)

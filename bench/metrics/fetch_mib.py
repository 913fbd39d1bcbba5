"""Serve engine: MiB copied from the device to the host per search step:
the ``fetch_bytes`` stat of ``CAMSearchServer.step``'s ``cam.serve.fetch``
spans in the window (each step's addition to the server's
``counters["fetch_bytes"]``, the ``nbytes`` of the indices and mask it
fetched), averaged over those steps.  None where the program records no
such stat, or where the trace has no device plane (no device to copy
from)."""
from bench import program_trace

MIB = float(1 << 20)


def read(ctx):
    if not ctx.ops:
        return None
    v = program_trace.span_stat_mean(ctx, "cam.serve.fetch", "fetch_bytes")
    return None if v is None else v / MIB

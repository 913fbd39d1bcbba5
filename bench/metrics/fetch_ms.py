"""Serve engine: host time per step, in ms, of ``CAMSearchServer.step``'s
``cam.serve.fetch`` span: ``np.asarray`` of the step's indices and match
mask after the device has finished (``cam.serve.wait``), i.e. the copy of
the results from the device to the host.  Read from the host spans of the
profiler trace, clipped to the window.  None where the program records no
such span, or where the trace has no device plane (no device to copy
from)."""
from bench import program_trace


def read(ctx):
    if not ctx.ops:
        return None
    return program_trace.host_ms_per_step(ctx, "cam.serve.fetch")

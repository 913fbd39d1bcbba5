"""Kernel: device time per step, in ms, of the fused Pallas search kernel
(``kernels/cam_search._fused_driver``'s ``pallas_call``), from the trace.

The kernel carries no ``name=`` of its own.  In a trace read by hand on the
TPU v5e its events are the custom call named after the jitted wrapper,
``%cam_search_fused_pallas.1 = (f32[7813,1,256,128], ...) custom-call(...)``
in the search program's module ``jit__query_jit(...)``."""

KERNEL = r"^%cam_search_fused_pallas\b"


def read(ctx):
    return ctx.tr.per_step_ms(ctx, KERNEL)

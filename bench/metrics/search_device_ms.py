"""Search program: device time per step, in ms, of the ops of the jitted
search program (``FunctionalSimulator._query_jit``: quantize, partition,
fused kernel, merge, back-map), from the trace."""

SEARCH_MODULE = r"_query_jit"


def read(ctx):
    return ctx.tr.per_step_ms(ctx, SEARCH_MODULE, on_module=True)

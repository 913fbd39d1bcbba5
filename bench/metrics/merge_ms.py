"""Search program: device time per step, in ms, of the ops under the named
scope ``cam.merge`` (``FunctionalSimulator.merge_rows``: the h-merge, the
``lax.top_k`` over all rows and the match-mask build), as the union of
their intervals.  The scope is read from each op's ``op_name``
(``bench/program_trace.py``), where a transform may wrap it
(``vmap(cam.merge)``); None where no op carries it."""
from bench import program_trace

MERGE = r"(^|[/(])cam\.merge([/)]|$)"


def read(ctx):
    return program_trace.scoped_ms_per_step(ctx, MERGE)

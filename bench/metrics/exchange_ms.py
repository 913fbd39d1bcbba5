"""Sharded search: device time per step and per chip, in ms, of the ops
under the named scope ``cam.shard.exchange`` (``ShardedCAMSimulator``'s
cross-chip part of the vertical merge: the ``all_gather`` of each chip's
top-k candidates, the stable re-rank of the gathered lists, and the
``pmax`` of the voting normalizer where it runs), as the union of their
intervals.  The scope is read from each op's ``op_name``
(``bench/program_trace.py``), where a transform may wrap it
(``shard_map``, ``vmap``); None where no op carries it, as on a program
that has no such scope."""
from bench import program_trace

EXCHANGE = r"(^|[/(])cam\.shard\.exchange([/)]|$)"


def read(ctx):
    return program_trace.scoped_ms_per_step(ctx, EXCHANGE)

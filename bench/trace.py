"""Reduction of a JAX profiler trace to device intervals and host spans.

``capture`` wraps a window in ``jax.profiler`` tracing; ``load`` reads the
``.xplane.pb`` it wrote into plain lists:

* ``Trace.ops[plane]``: the device's XLA op events, each with its module
  (the enclosing event of the plane's "XLA Modules" line);
* ``Trace.host``: host events (``TraceAnnotation`` spans of the benchmark
  and the runtime's own), with their nesting depth.

Everything else here is arithmetic on those lists, so a recorded or a
synthetic trace checks it without a chip (``bench/tests/test_trace.py``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass(frozen=True)
class Event:
    name: str
    start: float          # ns, on the trace's common timeline
    end: float
    module: str = ""
    depth: int = 0

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)

    def span(self, name: str) -> Optional[Event]:
        """The first host span called ``name``."""
        return next((e for e in self.host if e.name == name), None)


def capture_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # per-call Python spans would slow
    opts.host_tracer_level = 2        # the loop; runtime spans suffice
    return opts


def _module_of(mods: List[Event], starts: List[float], t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i].start <= t <= mods[i].end:
        return mods[i].name
    return ""


def load(trace_dir: str) -> Trace:
    """Device ops and host spans of the newest ``.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out = Trace()
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted((Event(e.name, e.start_ns, e.start_ns
                                 + e.duration_ns)
                           for e in (lines[MODULES_LINE].events
                                     if MODULES_LINE in lines else ())),
                          key=lambda e: e.start)
            starts = [m.start for m in mods]
            ops = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         _module_of(mods, starts, e.start_ns))
                   for e in (lines[OPS_LINE].events if OPS_LINE in lines
                             else ())]
            out.ops[plane.name] = sorted(ops, key=lambda e: e.start)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                stack: List[float] = []
                for e in sorted(line.events, key=lambda e: e.start_ns):
                    end = e.start_ns + e.duration_ns
                    while stack and stack[-1] <= e.start_ns:
                        stack.pop()
                    out.host.append(Event(e.name, e.start_ns, end,
                                          depth=len(stack)))
                    stack.append(end)
    return out


# ---------------------------------------------------------------- arithmetic
def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to [lo, hi]; those wholly outside dropped."""
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t, e.module, e.depth))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Sorted, merged, non-overlapping intervals."""
    merged: List[List[float]] = []
    for s, t in sorted(intervals):
        if t <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_ns(events: Iterable[Event]) -> float:
    """Length of the union of the events' intervals."""
    return sum(t - s for s, t in union((e.start, e.end) for e in events))


def gaps(events: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi] that no event covers."""
    out, cur = [], lo
    for s, t in union((e.start, e.end) for e in events):
        s, t = max(s, lo), min(t, hi)
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        out.append((cur, hi))
    return out


def host_activity(host: Sequence[Event], s: float, t: float,
                  skip: Sequence[str] = ()) -> str:
    """Name of the deepest host event that overlaps [s, t] the most: what
    the host was doing while the device sat idle."""
    best, key = "(no host span)", (-1, -1.0)
    for e in host:
        if e.name in skip:
            continue
        ov = min(e.end, t) - max(e.start, s)
        if ov <= 0:
            continue
        k = (e.depth, ov) if ov >= 0.5 * (t - s) else (-1, ov)
        if k > key:
            best, key = e.name, k
    return best


def matching(events: Iterable[Event], pattern: str, *,
             on_module: bool = False) -> List[Event]:
    """Events whose name (or module name) matches ``pattern``."""
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.module if on_module
                                           else e.name)]


def top_by_name(events: Iterable[Event], n: int = 10, scale: float = 1.0
                ) -> List[List]:
    """[[name, seconds], ...] of the ``n`` names with the most total time
    (``scale`` divides, e.g. by the number of chips)."""
    tot: Dict[str, float] = {}
    for e in events:
        tot[e.name] = tot.get(e.name, 0.0) + e.dur
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / scale] for k, v in top]


def per_step_ms(ctx, pattern: str, *, on_module: bool = False
                ) -> Optional[float]:
    """Device time of the events matching ``pattern`` (by op name, or by
    module name), as the union of their intervals, per chip and per step
    of the window, in ms; None where the trace holds no such event."""
    busy, found = 0.0, False
    for ops in ctx.ops.values():
        hit = matching(ops, pattern, on_module=on_module)
        found = found or bool(hit)
        busy += busy_ns(hit)
    if not found:
        return None
    return busy / 1e6 / ctx.chips / ctx.n_steps

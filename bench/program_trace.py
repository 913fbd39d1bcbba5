"""The program's own marks in a profiler trace, read beside ``bench/trace.py``:
the stats its host spans carry, and the named-scope path of each device op.

``bench/trace.py`` reduces a trace to op and span intervals.  The program
also writes two things into the trace that those intervals leave out:

* the stats of its ``TraceAnnotation`` spans, e.g. ``fetch_bytes`` on
  ``cam.serve.fetch`` (``CAMSearchServer.step``);
* the ``jax.named_scope`` names of its search program (``cam.quantize``,
  ``cam.search``, ``cam.kernel``, ``cam.merge``, ``cam.backmap``), which
  live in each HLO instruction's ``op_name`` metadata, e.g.
  ``jit(_query_jit)/cam.merge/top_k``.

TPU op events carry no ``op_name`` stat.  The trace keeps each program's
HLO in its "/host:metadata" plane (a serialized ``HloProto`` per module),
and ``op_names`` reads the instructions' ``op_name`` from it, so an op
event is named back to its scope through (module, instruction name).

``of(ctx)`` loads both from the run's ``.xplane.pb`` once per reader
context; the readers in ``bench/metrics`` use the helpers below, each of
which returns None where the program records nothing of the kind.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from bench import trace as tr

TRACE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          ".cache", "trace")     # the harness's trace dirs
WINDOW = "bench.window"
PROGRAM_SPAN = "cam."
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
INSTRUCTION = re.compile(r"^%?([^\s=]+)")    # "%copy.5 = f32[...] ..."


@dataclass(frozen=True)
class Span:
    name: str
    start: float          # ns, on the trace's common timeline
    end: float
    stats: Dict[str, float] = field(default_factory=dict)


@dataclass
class ProgramTrace:
    spans: List[Span] = field(default_factory=list)
    # {module name: {HLO instruction name: op_name}}
    names: Dict[str, Dict[str, str]] = field(default_factory=dict)

    def scope_of(self, op: tr.Event) -> str:
        """The scope path (``op_name``) of the device op ``op``."""
        m = INSTRUCTION.match(op.name)
        return self.names.get(op.module, {}).get(m.group(1), "") if m \
            else ""


# ------------------------------------------------------- HLO op_name lookup
# The protobuf wire format, read just far enough to reach each module's
# instructions: XSpace.planes (1) -> XPlane.name (2), .event_metadata (4,
# map entries: value 2), .stat_metadata (5, map entries: value 2);
# XEventMetadata.name (2), .stats (5); XStatMetadata.id (1), .name (2);
# XStat.metadata_id (1), .bytes_value (6); HloProto.hlo_module (1) ->
# HloModuleProto.computations (3) -> HloComputationProto.instructions (2)
# -> HloInstructionProto.name (1), .metadata (7) -> OpMetadata.op_name (2).
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, memoryview
    slices for length-delimited fields; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _first(buf, number: int, default=b""):
    return next((v for f, v in _fields(buf) if f == number), default)


def _hlo_op_names(hlo_proto) -> Dict[str, str]:
    """{instruction name: op_name} of every instruction of every
    computation of one serialized ``HloProto``."""
    out = {}
    for comp in (v for f, v in _fields(_first(hlo_proto, 1)) if f == 3):
        for ins in (v for f, v in _fields(comp) if f == 2):
            name = meta = b""
            for f, v in _fields(ins):
                if f == 1:
                    name = v
                elif f == 7:
                    meta = v
            op_name = bytes(_first(meta, 2)).decode() if meta else ""
            out[bytes(name).decode()] = op_name
    return out


def op_names(path: str) -> Dict[str, Dict[str, str]]:
    """{module name: {instruction name: op_name}} from the HLO the trace
    at ``path`` keeps of each program it ran (empty where it keeps
    none)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for plane in (v for f, v in _fields(space) if f == 1):
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for f, v in fields if f == 2), "")
        if name != METADATA_PLANE:
            continue
        stat_names = {}
        for f, entry in fields:
            if f == 5:
                md = _first(entry, 2)
                stat_names[_first(md, 1, 0)] = bytes(_first(md, 2)).decode()
        for f, entry in fields:
            if f != 4:
                continue
            md = _first(entry, 2)
            module = bytes(_first(md, 2)).decode()
            for sf, stat in _fields(md):
                if (sf == 5 and stat_names.get(_first(stat, 1, 0))
                        == HLO_PROTO_STAT):
                    out.setdefault(module, {}).update(
                        _hlo_op_names(_first(stat, 6)))
    return out


# ------------------------------------------------------------------ loading
def host_spans(path: str) -> List[Span]:
    """The benchmark's window and the program's spans (names starting
    ``cam.``) on the host planes of the trace at ``path``, with their
    stats."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW or e.name.startswith(PROGRAM_SPAN):
                    out.append(Span(e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    {k: v for k, v in e.stats
                                     if isinstance(v, (int, float))}))
    return sorted(out, key=lambda s: s.start)


def load(path: str) -> ProgramTrace:
    return ProgramTrace(host_spans(path), op_names(path))


def newest(root: str = TRACE_ROOT) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def of(ctx, root: str = TRACE_ROOT) -> Optional[ProgramTrace]:
    """The program's marks in the run's trace: the newest ``.xplane.pb``
    under the harness's trace directories, taken only where its window
    span is the one the reader context holds (``ctx.lo``).  Loaded once
    per context."""
    if not hasattr(ctx, "program_trace"):
        path, pt = newest(root), None
        if path is not None:
            pt = load(path)
            if not any(s.name == WINDOW and s.start == ctx.lo
                       for s in pt.spans):
                pt = None
        ctx.program_trace = pt
    return ctx.program_trace


# --------------------------------------------------------------- arithmetic
def host_ms_per_step(ctx, name: str) -> Optional[float]:
    """Host time inside the spans called ``name``, clipped to the window
    [ctx.lo, ctx.hi] and as the union of their intervals, per step of the
    window, in ms; None where no such span falls in the window."""
    spans = tr.clip((e for e in ctx.trace.host if e.name == name),
                    ctx.lo, ctx.hi)
    if not spans:
        return None
    return tr.busy_ns(spans) / 1e6 / ctx.n_steps


def span_stat_mean(ctx, name: str, stat: str) -> Optional[float]:
    """Mean of the stat ``stat`` over the spans called ``name`` that start
    in the window; None where none of them carries it."""
    pt = of(ctx)
    vals = [s.stats[stat] for s in (pt.spans if pt else ())
            if s.name == name and ctx.lo <= s.start < ctx.hi
            and stat in s.stats]
    return sum(vals) / len(vals) if vals else None


def scoped_ms_per_step(ctx, pattern: str) -> Optional[float]:
    """Device time of the ops whose scope path matches ``pattern``, as the
    union of their intervals, per chip and per step of the window, in ms;
    None where no op carries such a scope."""
    pt = of(ctx) if ctx.ops else None
    if pt is None:
        return None
    rx = re.compile(pattern)
    busy, found = 0.0, False
    for ops in ctx.ops.values():
        hit = [e for e in ops if rx.search(pt.scope_of(e))]
        found = found or bool(hit)
        busy += tr.busy_ns(hit)
    if not found:
        return None
    return busy / 1e6 / ctx.chips / ctx.n_steps

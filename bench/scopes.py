"""The program's own names in a profiler trace (``.xplane.pb``): device self
time per ``csaw.*`` scope, host time per ``csaw.*`` span, and idle gaps
named by the program's spans.

An addition to ``tracefile``, which it leaves as it is: ``read`` returns the
same per-chip ops and the same busy intervals, plus what ``tracefile`` does
not read.

A device op belongs to the innermost ``csaw.`` scope on the ``op_name``
path XLA keeps in the op's metadata (``jit(f)/while/body/csaw.walk.select/
csaw.walk.window_hook/gather``), or to ``""`` when no scope is on it.  The
trace's op events are named by their HLO text, which carries no metadata;
the metadata is in the optimized HLO module each program registers on the
``/host:metadata`` plane (an ``Hlo Proto`` stat per program, named like the
program's event on the device's ``XLA Modules`` line; on a TPU v5e the op
events carry no ``tf_op`` or ``long_name`` stat).  ``jax.profiler`` does not
expose those stats, so this module reads them from the file with a small
protobuf wire-format reader; field numbers are those of XLA's
``xplane.proto`` and ``hlo.proto``.  Ops the compiler makes without
metadata (relayout copies, broadcasts of sunk constants) have an empty
``op_name`` and so no scope.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path

import tracefile

SCOPE_PREFIX = "csaw."
SPAN_PREFIXES = ("bench.", "csaw.")
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
_SCOPE = re.compile(r"csaw\.[\w.]+")


@dataclasses.dataclass
class Scoped:
    scope_s: dict  # {scope or "": device self seconds}, averaged over the chips
    span_s: dict  # {program span: [count, seconds]}
    gaps: list  # [(span name or "none", seconds)] longest idle gaps first
    found: dict  # {"hlo_proto": ops found in their program's HLO, "none": ops not found}


def innermost_scope(op_name: str) -> str:
    found = _SCOPE.findall(op_name)
    return found[-1] if found else ""


# ---------------------------------------------------------------------------
# Protobuf wire format
# ---------------------------------------------------------------------------


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of one message; a length-delimited value is
    a ``memoryview`` slice, a varint an ``int``, fixed-width values bytes."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = bytes(buf[i:i + size]), i + size
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield key >> 3, value


def _first(buf, number: int, default=None):
    for f, v in fields(buf):
        if f == number:
            return v
    return default


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def hlo_op_names(hlo_proto) -> dict[str, str]:
    """``{instruction name: op_name}`` of a serialized ``HloProto``
    (hlo_module 1 → computations 3 → instructions 2 → name 1, metadata 7 →
    op_name 2)."""
    module = _first(hlo_proto, 1, b"")
    names: dict[str, str] = {}
    for f, comp in fields(module):
        if f != 3:
            continue
        for g, inst in fields(comp):
            if g != 2:
                continue
            name = op_name = ""
            for h, v in fields(inst):
                if h == 1:
                    name = _text(v)
                elif h == 7:
                    op_name = _text(_first(v, 2, b""))
            names[name] = op_name
    return names


def _event_metadata(plane):
    """``(name, {stat name: value})`` of each event metadata entry of one
    XPlane (event_metadata 4, stat_metadata 5; XEventMetadata name 2,
    stats 5; XStat metadata_id 1, bytes_value 6)."""
    stat_names = {}
    for g, entry in fields(plane):
        if g == 5:
            md = _first(entry, 2, b"")
            stat_names[_first(md, 1, 0)] = _text(_first(md, 2, b""))
    for g, entry in fields(plane):
        if g != 4:
            continue
        md = _first(entry, 2, b"")
        stats = {}
        for h, stat in fields(md):
            if h == 5:
                values = dict(fields(stat))
                stats[stat_names.get(values.get(1, 0), "")] = values.get(6)
        yield _text(_first(md, 2, b"")), stats


def program_op_names(path: str | Path) -> dict[str, dict[str, str]]:
    """``{program name: {instruction: op_name}}`` from the ``Hlo Proto``
    stats of the trace's ``/host:metadata`` plane (XSpace planes 1; XPlane
    name 2)."""
    programs: dict[str, dict[str, str]] = {}
    for f, plane in fields(Path(path).read_bytes()):
        if f == 1 and _text(_first(plane, 2, b"")) == METADATA_PLANE:
            for name, stats in _event_metadata(plane):
                if stats.get(HLO_PROTO_STAT) is not None:
                    programs[name] = hlo_op_names(stats[HLO_PROTO_STAT])
    return programs


# ---------------------------------------------------------------------------
# The reduction
# ---------------------------------------------------------------------------


def read(path: str | Path) -> tuple[list, list, list, list]:
    """Per-chip ops (as ``tracefile.read_xplane``), each chip's program
    intervals ``[(start, end, name)]`` from its ``XLA Modules`` line, host
    spans whose name starts with ``bench.`` or ``csaw.``, and the per-chip
    scope of each op."""
    from jax.profiler import ProfileData

    chips, _ = tracefile.read_xplane(path)
    data = ProfileData.from_file(str(path))
    programs, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(tracefile.DEVICE_PREFIX):
            programs.append(sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for line in plane.lines if line.name == MODULES_LINE for ev in line.events))
        elif plane.name.startswith("/host:"):
            spans += [tracefile.Span(ev.name, ev.start_ns, ev.duration_ns)
                      for line in plane.lines for ev in line.events
                      if ev.name.startswith(SPAN_PREFIXES)]
    tables = program_op_names(path)
    scopes = [op_scopes(ops, progs, tables) for ops, progs in zip(chips, programs)]
    return chips, programs, spans, scopes


def op_scopes(ops: list, programs: list, tables: dict) -> list[tuple]:
    """``(scope, how)`` of each op: the innermost scope of its instruction's
    ``op_name`` in the program whose interval holds the op's start, with
    ``how`` ``"hlo_proto"``; ``("", "none")`` where that program's HLO
    has no such instruction."""
    starts = [p[0] for p in programs]
    out = []
    for o in ops:
        k = bisect.bisect_right(starts, o.start_ns) - 1
        table = tables.get(programs[k][2], {}) if k >= 0 and o.start_ns < programs[k][1] else {}
        if o.name in table:
            out.append((innermost_scope(table[o.name]), "hlo_proto"))
        else:
            out.append(("", "none"))
    return out


def summarize(chips: list, spans: list, scopes: list, top: int = 10) -> Scoped:
    """``scopes``: per chip, ``(scope, how)`` of each op (``op_scopes``)."""
    scope_s: dict[str, float] = {}
    found: dict[str, int] = {}
    raw_gaps = []
    for ops, names in zip(chips, scopes):
        for o, (s, how) in zip(ops, names):
            scope_s[s] = scope_s.get(s, 0.0) + o.self_ns
            found[how] = found.get(how, 0) + 1
        merged = tracefile.union_intervals([(o.start_ns, o.start_ns + o.dur_ns) for o in ops])
        raw_gaps += [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:])]
    n = max(len(chips), 1)
    span_s: dict[str, list] = {}
    for sp in spans:
        if sp.name.startswith(SCOPE_PREFIX):
            c = span_s.setdefault(sp.name, [0, 0.0])
            c[0] += 1
            c[1] += sp.dur_ns * 1e-9
    raw_gaps.sort(key=lambda g: g[0] - g[1])
    gaps = [(gap_label(spans, s, e), (e - s) * 1e-9) for s, e in raw_gaps[:top]]
    return Scoped({k: v * 1e-9 / n for k, v in scope_s.items()}, span_s, gaps, found)


def gap_label(spans: list, s: float, e: float) -> str:
    """The program span that covers most of ``[s, e)``, the innermost
    (shortest) of those that cover as much; else the harness's span that
    covers most of it (``tracefile``'s label), else ``"none"``."""
    best, key = "none", (0.0, float("-inf"))
    for sp in spans:
        if sp.name.startswith(SCOPE_PREFIX):
            cover = min(e, sp.start_ns + sp.dur_ns) - max(s, sp.start_ns)
            if cover > 0 and (cover, -sp.dur_ns) > key:
                best, key = sp.name, (cover, -sp.dur_ns)
    if best != "none":
        return best
    return tracefile._host_label([sp for sp in spans if not sp.name.startswith(SCOPE_PREFIX)],
                                 s, e)


def reduce(path: str | Path) -> Scoped:
    chips, _, spans, scopes = read(path)
    return summarize(chips, spans, scopes)

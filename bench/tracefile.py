"""Reduction of a profiler trace (``.xplane.pb``) to device busy time, op
times and idle gaps attributed to the host spans the harness records.

The device's operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, named by their HLO text (``%fusion.85 = ...
fusion(...)``).  A loop's event encloses its body's, so each operation counts
its self time: its duration less that of the events nested directly in it.
An operation is a Pallas kernel when its HLO text names ``tpu_custom_call``;
every other operation is XLA's.  Busy time is the union of the operations'
intervals; idle share is 1 - busy / window.  Host spans are the harness's
``bench.*`` ``TraceAnnotation`` events on the host plane, on the same clock.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
PALLAS_MARKER = "tpu_custom_call"


@dataclasses.dataclass
class Op:
    name: str  # the HLO op's name: "fusion.85", "reject_step_pallas.16"
    start_ns: float
    dur_ns: float
    pallas: bool
    self_ns: float = 0.0  # dur_ns less the ops nested directly in it


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class TraceSummary:
    chips: int
    busy_s: float  # union of op intervals, averaged over the chips
    pallas_s: float  # Pallas ops' self time, averaged over the chips
    xla_s: float  # other ops' self time, averaged over the chips
    ops: list  # [(name, seconds)] self time summed per name, largest first
    gaps: list  # [(host span or "none", seconds)] longest idle gaps first


def _is_pallas(text: str) -> bool:
    return PALLAS_MARKER in text


def op_name(text: str) -> str:
    """``%fusion.85 = s32[...] fusion(...)`` -> ``fusion.85``."""
    return text.split(" = ", 1)[0].lstrip("%")


def set_self_times(ops: list[Op]) -> None:
    """Self time of each op on one line, nested events subtracted."""
    stack: list[Op] = []
    for o in sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns)):
        o.self_ns = o.dur_ns
        while stack and stack[-1].start_ns + stack[-1].dur_ns <= o.start_ns:
            stack.pop()
        if stack:
            stack[-1].self_ns -= o.dur_ns
        stack.append(o)


def read_xplane(path: str | Path) -> tuple[list[list[Op]], list[Span]]:
    """Per-chip device ops and the harness's host spans of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    chips, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(op_name(ev.name), ev.start_ns, ev.duration_ns,
                                  _is_pallas(ev.name)))
            set_self_times(ops)
            chips.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        spans.append(Span(ev.name, ev.start_ns, ev.duration_ns))
    return chips, spans


def union_intervals(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def summarize(chips: list[list[Op]], spans: list[Span], top: int = 10) -> TraceSummary:
    """Busy, Pallas and XLA time over the ops, top ops and longest gaps."""
    if not chips or not any(chips):
        return TraceSummary(len(chips), 0.0, 0.0, 0.0, [], [])
    busy = pallas = xla = 0.0
    per_name: dict[str, float] = {}
    raw_gaps: list[tuple[float, float]] = []
    for ops in chips:
        merged = union_intervals([(o.start_ns, o.start_ns + o.dur_ns) for o in ops])
        busy += sum(e - s for s, e in merged)
        for o in ops:
            if o.pallas:
                pallas += o.self_ns
            else:
                xla += o.self_ns
            per_name[o.name] = per_name.get(o.name, 0.0) + o.self_ns
        raw_gaps += [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:])]
    n = len(chips)
    ops_top = sorted(((k, v * 1e-9 / n) for k, v in per_name.items()),
                     key=lambda kv: -kv[1])[:top]
    raw_gaps.sort(key=lambda g: g[0] - g[1])
    gaps = [(_host_label(spans, s, e), (e - s) * 1e-9) for s, e in raw_gaps[:top]]
    return TraceSummary(n, busy * 1e-9 / n, pallas * 1e-9 / n, xla * 1e-9 / n,
                        ops_top, gaps)


def _host_label(spans: list[Span], s: float, e: float) -> str:
    """The host span that covers most of [s, e)."""
    best, best_cover = "none", 0.0
    for sp in spans:
        cover = min(e, sp.start_ns + sp.dur_ns) - max(s, sp.start_ns)
        if cover > best_cover:
            best, best_cover = sp.name, cover
    return best


def find_xplane(trace_dir: str | Path) -> Path | None:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return found[-1] if found else None

"""Reduce a profiler trace of the window to what the per-layer metrics read.

The device planes (``/device:TPU:<n>``) hold two lines that matter here:
``XLA Modules``, one event per execution of a compiled program, and ``XLA
Ops``, one event per operation.  Busy time is the union of the operation
intervals; a program's device time is the duration of its module events.
The host plane carries the benchmark's own anchor annotation, which puts the
engine's clock on the trace's.

:func:`load` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``; the
rest works on plain ``(start_ns, duration_ns, name)`` tuples, so a small
recorded trace can be checked without a chip.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[int, int, str]  # (start_ns, duration_ns, name)

ANCHOR = "bench_window_anchor"


@dataclasses.dataclass
class Device:
    modules: List[Event]
    ops: List[Event]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over devices
    program_ns: Dict[str, List[int]]  # program name -> device time per execution
    top_ops: List[Tuple[str, float]]  # (op, seconds), most time first
    gaps: List[Tuple[float, float]]  # idle gaps (start on the engine clock, seconds)
    devices: int = 0


def load(path: str) -> Tuple[List[Device], Optional[int]]:
    """Device lines and the anchor's start (ns) from one xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, anchor = [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(
                Device(
                    modules=_events(lines.get("XLA Modules")),
                    ops=_events(lines.get("XLA Ops")),
                )
            )
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == ANCHOR:
                        anchor = int(ev.start_ns)
    return devices, anchor


def _events(line) -> List[Event]:
    if line is None:
        return []
    return [(int(e.start_ns), int(e.duration_ns), e.name) for e in line.events]


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def program_name(module: str) -> str:
    """``jit__decode(1556...)`` -> ``jit__decode``."""
    return re.sub(r"\(\d+\)$", "", module)


def op_name(op: str) -> str:
    """``%fusion.31 = s32[8,4096]{...} fusion(...)`` -> ``fusion``."""
    head = op.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def summarize(
    devices: List[Device],
    t0_ns: int,
    t1_ns: int,
    anchor_ns: Optional[int] = None,
    anchor_t: float = 0.0,
    top: int = 10,
) -> Summary:
    """Reduce events between ``t0_ns`` and ``t1_ns`` (the traced window).

    ``anchor_ns`` is where the anchor sits in the trace and ``anchor_t``
    where it sits on the engine's clock; gaps are reported on that clock.
    """
    window_ns = t1_ns - t0_ns
    busy_total = 0
    programs: Dict[str, List[int]] = {}
    op_time: Dict[str, int] = {}
    gaps: List[Tuple[float, float]] = []
    shift = anchor_t - (anchor_ns or 0) / 1e9
    for d in devices:
        spans = []
        for s, dur, name in d.ops:
            if s < t0_ns or s >= t1_ns:
                continue
            spans.append((s, min(s + dur, t1_ns)))
            key = op_name(name)
            op_time[key] = op_time.get(key, 0) + dur
        merged = union(spans)
        busy_total += sum(e - s for s, e in merged)
        edges = [(t0_ns, t0_ns)] + merged + [(t1_ns, t1_ns)]
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            if s1 > e0:
                gaps.append((e0 / 1e9 + shift, (s1 - e0) / 1e9))
        for s, dur, name in d.modules:
            if t0_ns <= s < t1_ns:
                programs.setdefault(program_name(name), []).append(dur)
    n = max(len(devices), 1)
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return Summary(
        window_s=window_ns / 1e9,
        busy_s=busy_total / 1e9 / n,
        program_ns=programs,
        top_ops=[(k, v / 1e9 / n) for k, v in ops],
        gaps=sorted(gaps, key=lambda g: -g[1]),
        devices=len(devices),
    )


def label_gap(start: float, length: float, requests, ticks) -> str:
    """What the engine was doing in an idle gap that starts at ``start``."""
    mid = start + length / 2
    for r in requests:
        if r.admitted is not None and r.first is not None and r.admitted <= mid <= r.first:
            return f"admission ({r.prompt_len}-token prefill, retrace, cache insert)"
    times = [t.t for t in ticks]
    if times and times[0] <= mid <= times[-1]:
        return "decode loop on the host (sampling, transfer, scheduling)"
    return "no request to serve"

"""Named scopes of the decode program and the engine's spans, from a trace.

The program names its device work with ``jax.named_scope``: each QMM site by
its ``QuantConfig`` name (``attn.q`` ... ``ffn.down``), the attention core
(``attn.core``, with ``attn.cache``, ``attn.qk`` and ``attn.av`` inside it)
and the final norm with the unembedding (``head``).  The path is each HLO
instruction's ``op_name`` metadata, ``jit(_decode)/while/body/closed_call/
attn.core/...``; the profiler keeps every program's optimized HLO in the
trace's metadata plane, and an ``XLA Ops`` event names its instruction.
``ServeEngine`` records its host work as spans in ``last_events`` and as
``serve.<kind>`` annotations on the host plane, on the device planes' clock.

What is read here:

* :func:`decode_scopes`: device time per ``jit__decode`` execution in the
  QMM sites, the attention core and the head.  Each leaf operation inside
  an execution goes to at most one group by the outermost named scope on
  its path; control flow (``while``, ``conditional``, ``call``), which
  spans its body, goes to none.  Each group is the union of its operations'
  intervals, and an instant two groups share counts for the first of
  :data:`GROUPS`, so the groups never overlap and sum to no more than the
  program's device time.
* :func:`label_gaps`: the innermost ``serve.*`` span over an idle gap's
  middle, with the admission it belongs to: ``serve.prefill (1373-token
  admit)``.
* :func:`tick_host_ms` and :func:`admit_dispatch_s`, from the engine's own
  spans: the host work of a tick that the device waits on, and the host
  time an admission spends in prefill dispatch and cache insert.

Like ``bench/trace.py``, everything but :func:`load` works on plain tuples,
so a small recorded trace can be checked without a chip.
"""

from __future__ import annotations

import bisect
import dataclasses
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace as T

#: the QMM sites of a dense GQA block, by their QuantConfig names
SITES = ("attn.q", "attn.k", "attn.v", "attn.o", "ffn.gate", "ffn.up", "ffn.down")
ATTN_PARTS = ("attn.cache", "attn.qk", "attn.av")
#: the groups of :func:`decode_scopes`, in the order that breaks ties
GROUPS = ("qmm", "attn", "head")
#: the finer parts, same order: each site, the attention core's scopes, head
PARTS = SITES + ATTN_PARTS + ("attn.core", "head")
PART_GROUP = {
    **{s: "qmm" for s in SITES},
    **{s: "attn" for s in ATTN_PARTS + ("attn.core",)},
    "head": "head",
}
CONTROL_FLOW = ("while", "conditional", "call")
DECODE_PROGRAM = "jit__decode"
SPAN_PREFIX = "serve."
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"

Span = Tuple[int, int, str, Dict]  # (start_ns, duration_ns, name, annotation fields)


@dataclasses.dataclass
class Trace:
    devices: List[T.Device]
    spans: List[Span]  # the host plane's serve.* annotations, by start
    anchor_ns: Optional[int] = None
    #: program (``jit__decode(<id>)``) -> instruction name -> its scope path
    scopes: Dict[str, Dict[str, str]] = dataclasses.field(default_factory=dict)

    def scope(self, module: str, hlo: str) -> str:
        """The scope path of an ``XLA Ops`` event of ``module``."""
        return self.scopes.get(module, {}).get(instruction(hlo), "")


def instruction(hlo: str) -> str:
    """``%fusion.31 = s32[8] fusion(...)`` -> ``fusion.31``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    """Device lines, the host spans, and the decode program's scope paths."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans, anchor = [], [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(T.Device(T._events(lines.get("XLA Modules")), T._events(lines.get("XLA Ops"))))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == T.ANCHOR:
                        anchor = int(e.start_ns)
                    elif e.name.startswith(SPAN_PREFIX):
                        spans.append((int(e.start_ns), int(e.duration_ns), e.name, dict(e.stats)))
    with open(path, "rb") as f:
        scopes = program_scopes(f.read(), DECODE_PROGRAM)
    return Trace(devices, sorted(spans, key=lambda sp: sp[0]), anchor, scopes)


# -- the scope paths: the optimized HLO of each program, as the profiler keeps
# it in the metadata plane (an ``XEventMetadata`` per program with an ``Hlo
# Proto`` stat).  ``jax.profiler.ProfileData`` does not expose it, so the few
# fields needed are read from the protobuf wire format (field numbers of
# tsl's xplane.proto and xla's hlo.proto).


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(number, value) of each field of one message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width fields skipped."""
    buf, i = memoryview(buf), 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _first(buf, number: int):
    return next((v for n, v in _fields(buf) if n == number), None)


def _all(buf, number: int):
    return [v for n, v in _fields(buf) if n == number]


def program_scopes(xspace: bytes, program: str) -> Dict[str, Dict[str, str]]:
    """Instruction name -> ``op_name`` metadata of every program named
    ``program`` in the metadata plane of a serialized ``XSpace``."""
    out: Dict[str, Dict[str, str]] = {}
    for plane in _all(xspace, 1):  # XSpace.planes
        if bytes(_first(plane, 2) or b"").decode() != METADATA_PLANE:  # XPlane.name
            continue
        stat_names = {}
        for entry in _all(plane, 5):  # XPlane.stat_metadata: id -> XStatMetadata
            meta = _first(entry, 2)
            stat_names[_first(meta, 1)] = bytes(_first(meta, 2) or b"").decode()
        for entry in _all(plane, 4):  # XPlane.event_metadata: id -> XEventMetadata
            meta = _first(entry, 2)
            name = bytes(_first(meta, 2) or b"").decode()
            if T.program_name(name) != program:
                continue
            for stat in _all(meta, 5):  # XEventMetadata.stats
                if stat_names.get(_first(stat, 1)) == HLO_PROTO_STAT:
                    out[name] = _instruction_scopes(_first(stat, 6))  # XStat.bytes_value
    return out


def _instruction_scopes(hlo_proto) -> Dict[str, str]:
    out = {}
    module = _first(hlo_proto, 1)  # HloProto.hlo_module
    for comp in _all(module, 3):  # HloModuleProto.computations
        for instr in _all(comp, 2):  # HloComputationProto.instructions
            meta = _first(instr, 7)  # HloInstructionProto.metadata
            op_name = _first(meta, 2) if meta is not None else None  # OpMetadata.op_name
            if op_name is not None:
                out[bytes(_first(instr, 1)).decode()] = bytes(op_name).decode()
    return out


def part_of(hlo: str, scope: str) -> Optional[str]:
    """The part of one operation: the outermost named scope on its path (an
    attention scope inside ``attn.core``), or None."""
    if T.op_name(hlo) in CONTROL_FLOW:
        return None
    path = scope.split("/")
    for i, name in enumerate(path):
        if name == "attn.core" and i + 1 < len(path) and path[i + 1] in ATTN_PARTS:
            return path[i + 1]
        if name in PART_GROUP:
            return name
    return None


def group_of(hlo: str, scope: str) -> Optional[str]:
    part = part_of(hlo, scope)
    return PART_GROUP[part] if part else None


def _subtract(spans: List[Tuple[int, int]], taken: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """``spans`` less ``taken``; both disjoint and sorted."""
    out, i = [], 0
    for s, e in spans:
        while i < len(taken) and taken[i][1] <= s:
            i += 1
        j = i
        while s < e and j < len(taken) and taken[j][0] < e:
            if taken[j][0] > s:
                out.append((s, taken[j][0]))
            s = max(s, taken[j][1])
            j += 1
        if s < e:
            out.append((s, e))
    return out


def decode_scopes(
    trace: Trace, t0_ns: int, t1_ns: int, by=group_of, order: Sequence[str] = GROUPS
) -> Dict[str, float]:
    """Mean device ms per ``jit__decode`` execution started in [t0, t1), per
    group of :data:`GROUPS` (or per part: ``by=part_of, order=PARTS``);
    empty where no execution was traced."""
    total = {g: 0 for g in order}
    runs = 0
    for d in trace.devices:
        ops = sorted(d.ops)
        starts = [op[0] for op in ops]
        for s, dur, name in d.modules:
            if T.program_name(name) != DECODE_PROGRAM or not t0_ns <= s < t1_ns:
                continue
            runs += 1
            end = s + dur
            per = {g: [] for g in order}
            for os_, odur, hlo in ops[bisect.bisect_left(starts, s):bisect.bisect_left(starts, end)]:
                g = by(hlo, trace.scope(name, hlo))
                if g is not None:
                    per[g].append((os_, min(os_ + odur, end)))
            taken: List[Tuple[int, int]] = []
            for g in order:
                mine = _subtract(T.union(per[g]), taken)
                total[g] += sum(e - b for b, e in mine)
                taken = T.union(taken + mine)
    if not runs:
        return {}
    return {g: total[g] / runs / 1e6 for g in order}


def label(start_ns: int, length_ns: int, spans: Sequence[Span]) -> Optional[str]:
    """The innermost ``serve.*`` span over the gap's middle, with the
    admission it belongs to; None where no span covers it."""
    mid = start_ns + length_ns / 2
    over = [sp for sp in spans if sp[0] <= mid <= sp[0] + sp[1]]
    if not over:
        return None
    inner = min(over, key=lambda sp: sp[1])
    admit = next((sp for sp in over if sp[2] == SPAN_PREFIX + "admit"), None)
    if admit is None or "prompt_len" not in admit[3]:
        return inner[2]
    return f"{inner[2]} ({admit[3]['prompt_len']}-token admit)"


def label_gaps(gaps, spans: Sequence[Span], to_ns, requests, ticks) -> List[str]:
    """Each idle gap (start and length in seconds on the engine's clock, as
    ``trace.summarize`` gives them) labelled by :func:`label`, ``to_ns``
    putting it on the trace's clock; or else by ``trace.label_gap``'s
    reconstruction from the request stamps, marked ``guessed``."""
    return [
        label(to_ns(start), length * 1e9, spans)
        or "guessed: " + T.label_gap(start, length, requests, ticks)
        for start, length in gaps
    ]


def _children(events: List[Dict], kind: str) -> Dict[int, float]:
    """Seconds of the spans of ``kind``, summed under each parent index."""
    out: Dict[int, float] = {}
    for e in events:
        if e["kind"] == kind and e.get("parent") is not None and "end" in e:
            out[e["parent"]] = out.get(e["parent"], 0.0) + e["end"] - e["t"]
    return out


def _window_spans(events: List[Dict], kind: str, open_: float, close: float):
    return [
        (i, e) for i, e in enumerate(events)
        if e["kind"] == kind and "end" in e and "parent" in e and open_ <= e["t"] < close
    ]


def tick_host_ms(events: List[Dict], open_: float, close: float) -> Optional[float]:
    """Median over the window's ``tick`` spans of their length less their
    ``fetch`` child: the host work of a tick that the device waits on."""
    fetch = _children(events, "fetch")
    ticks = _window_spans(events, "tick", open_, close)
    if not ticks:
        return None
    return statistics.median((e["end"] - e["t"] - fetch.get(i, 0.0)) * 1e3 for i, e in ticks)


def admit_dispatch_s(events: List[Dict], open_: float, close: float) -> Optional[float]:
    """Mean over the window's ``admit`` spans of their ``prefill`` and
    ``insert`` children: retrace, compile-cache load and eager dispatch."""
    prefill, insert = _children(events, "prefill"), _children(events, "insert")
    admits = _window_spans(events, "admit", open_, close)
    if not admits:
        return None
    return sum(prefill.get(i, 0.0) + insert.get(i, 0.0) for i, _ in admits) / len(admits)

"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

What is read, and from where:

* device ops: the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane. An
  event's name is the HLO instruction's text (``%grouped_gemm.46 = bf16[..]
  custom-call(...)``); the op's name is the part between ``%`` and `` =``.
  Its ``scope`` is the ``tf_op`` stat of its event metadata: the op-name
  path that tracing gave the instruction (``jit(step)/transpose(jvp())/
  while/body/...``), where ``jax.named_scope`` names land too. An op that
  encloses others on the line (``while``, ``conditional``, ``call``) is
  counted by its self time (:func:`self_ns`).
  The ``Async XLA Ops`` line is left out: an async copy's span covers the
  compute it overlaps and would count it as busy twice.
* host spans: events named ``bench.*`` (the harness's own
  ``jax.profiler.TraceAnnotation`` spans around its calls into the
  program) or ``repro.*`` (the program's own spans) on any ``/host:CPU``
  line.

Device and host events share one clock in the file, so a device gap can be
laid against what the host was doing in it.

The file is decoded here from the protobuf wire format, following the
public schema ``tsl/profiler/protobuf/xplane.proto``, because
``jax.profiler.ProfileData`` does not expose metadata stats; nothing but
the standard library is needed.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

_OP_NAME = re.compile(r"^%?([A-Za-z_][\w.-]*?) = ")
_SUFFIX = re.compile(r"\.\d+$")
# collectives, as XLA names their ops (start/done halves of async ones too)
COLLECTIVE_PREFIXES = ("all-to-all", "all-reduce", "all-gather",
                       "reduce-scatter", "collective-permute")
SPAN_PREFIXES = ("bench.", "repro.")  # the harness's host spans, the program's


@dataclasses.dataclass
class Op:
    name: str  # HLO op name, e.g. grouped_gemm.46
    text: str  # the full instruction text the profiler recorded
    start_ns: float
    dur_ns: float
    scope: str = ""  # op-name path (the metadata's tf_op stat), "" if none

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def family(self) -> str:
        """The op name without its numeric suffix: ``grouped_gemm``."""
        return _SUFFIX.sub("", self.name)


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]  # plane name -> ops sorted by start
    spans: List[Span]  # harness and program spans, sorted by start

    def window(self) -> Tuple[float, float]:
        """First op start to last op end over all devices (ns)."""
        starts = [o.start_ns for ops in self.devices.values() for o in ops[:1]]
        ends = [max(o.end_ns for o in ops) for ops in self.devices.values() if ops]
        return (min(starts), max(ends)) if starts else (0.0, 0.0)


def op_name(text: str) -> str:
    m = _OP_NAME.match(text)
    return m.group(1) if m else text.split(" ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``: events and their
    metadata from one decode, joined by metadata id (an HLO text can repeat
    across programs, an id cannot within a plane). Times are whole
    nanoseconds, the line's ``timestamp_ns`` plus the event's picoseconds
    cut to nanoseconds, as ``jax.profiler.ProfileData`` reads them."""
    with open(path, "rb") as f:
        b = f.read()
    devices: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    for field, plane in _fields(b, 0, len(b)):
        if field != 1:  # XSpace.planes
            continue
        name, lines, metadata, stat_names = _plane(b, plane)
        if name.startswith("/device:TPU:"):
            seen: Dict[int, Tuple[str, str, str]] = {}  # metadata id -> name, text, scope
            ops = []
            for line_name, ts, events in _lines(b, lines):
                if line_name != "XLA Ops":
                    continue
                for mid, start, dur in _events(b, events, ts):
                    if mid not in seen:
                        text, scope = _op_metadata(b, metadata.get(mid), stat_names)
                        seen[mid] = op_name(text), text, scope
                    op, text, scope = seen[mid]
                    ops.append(Op(op, text, start, dur, scope))
            ops.sort(key=lambda o: o.start_ns)
            devices[name] = ops
        elif name.startswith("/host:CPU"):
            names = {mid: _name(b, v) for mid, v in metadata.items()}
            keep = {mid: n for mid, n in names.items() if n.startswith(SPAN_PREFIXES)}
            for _, ts, events in _lines(b, lines):
                spans += [Span(keep[mid], start, dur)
                          for mid, start, dur in _events(b, events, ts) if mid in keep]
    spans.sort(key=lambda s: s.start_ns)
    return Trace(devices, spans)


# -- the wire format of xplane.proto, as far as it is read here -------------
# XSpace: planes = 1
# XPlane: name = 2, lines = 3, event_metadata = 4, stat_metadata = 5
#         (the two maps: entries of key = 1, value = 2)
# XLine: name = 2, timestamp_ns = 3, events = 4
# XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3
# XEventMetadata / XStatMetadata: id = 1, name = 2; XEventMetadata.stats = 5
# XStat: metadata_id = 1, str_value = 5, ref_value = 7 (a stat metadata id
#        whose name is the value)

_Range = Tuple[int, int]  # a length-delimited field's [start, end) in the file


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    c = b[i]
    if c < 0x80:
        return c, i + 1
    value, shift = c & 0x7F, 7
    while True:
        i += 1
        c = b[i]
        value |= (c & 0x7F) << shift
        if c < 0x80:
            return value, i + 1
        shift += 7


def _fields(b: bytes, i: int, end: int) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of the message in ``b[i:end]``: a varint as its
    int, a length-delimited field as its range; fixed-width fields (doubles)
    are skipped and come as None."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            value, i = (i, i + n), i + n
        elif wire == 1 or wire == 5:
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(b: bytes, r: _Range) -> str:
    return b[r[0]:r[1]].decode("utf-8", "replace")


def _name(b: bytes, r: _Range) -> str:
    """Field 2 of a message: an XEventMetadata's or XStatMetadata's name."""
    for field, value in _fields(b, *r):
        if field == 2:
            return _text(b, value)
    return ""


def _plane(b: bytes, r: _Range):
    """An XPlane's name, its lines' ranges, its event metadata's ranges by
    id, and its stat metadata's names by id."""
    name, lines = "", []
    metadata: Dict[int, _Range] = {}
    stat_names: Dict[int, str] = {}
    for field, value in _fields(b, *r):
        if field == 2:
            name = _text(b, value)
        elif field == 3:
            lines.append(value)
        elif field in (4, 5):
            key, entry = 0, None
            for f, v in _fields(b, *value):
                if f == 1:
                    key = v
                elif f == 2:
                    entry = v
            if entry is None:
                continue
            if field == 4:
                metadata[key] = entry
            else:
                stat_names[key] = _name(b, entry)
    return name, lines, metadata, stat_names


def _lines(b: bytes, lines: List[_Range]) -> Iterator[Tuple[str, int, List[_Range]]]:
    """Each XLine's name, timestamp (ns) and its events' ranges."""
    for r in lines:
        name, ts, events = "", 0, []
        for field, value in _fields(b, *r):
            if field == 2:
                name = _text(b, value)
            elif field == 3:
                ts = value
            elif field == 4:
                events.append(value)
        yield name, ts, events


def _events(b: bytes, events: List[_Range], ts: int) -> Iterator[Tuple[int, float, float]]:
    """Each XEvent's metadata id, start and duration (ns)."""
    for r in events:
        mid = offset_ps = duration_ps = 0
        for field, value in _fields(b, *r):
            if field == 1:
                mid = value
            elif field == 2:
                offset_ps = value
            elif field == 3:
                duration_ps = value
        yield mid, float(ts + offset_ps // 1000), float(duration_ps // 1000)


def _op_metadata(b: bytes, r: Optional[_Range], stat_names: Dict[int, str]
                 ) -> Tuple[str, str]:
    """An op's event metadata: its name (the HLO text) and its ``tf_op``
    stat, the op-name path ("" where it has none)."""
    if r is None:
        return "", ""
    name, scope = "", ""
    for field, value in _fields(b, *r):
        if field == 2:
            name = _text(b, value)
        elif field == 5:
            sid, text = 0, ""
            for f, v in _fields(b, *value):
                if f == 1:
                    sid = v
                elif f == 5:
                    text = _text(b, v)
                elif f == 7:
                    text = stat_names.get(v, "")
            if stat_names.get(sid) == "tf_op":
                scope = text
    return name, scope


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_ns(ops: Sequence[Op], lo: float, hi: float) -> float:
    """Time within [lo, hi] in which at least one op ran on the device."""
    return sum(e - s for s, e in clip(union((o.start_ns, o.end_ns) for o in ops), lo, hi))


def mean_busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Busy time averaged over the devices that ran anything."""
    vals = [busy_ns(ops, lo, hi) for ops in trace.devices.values() if ops]
    return sum(vals) / len(vals) if vals else 0.0


def kernel_ns(trace: Trace, families: Sequence[str], lo: float, hi: float
              ) -> Tuple[float, List[Op]]:
    """Summed device time of the ops whose family is in ``families``
    (a Pallas kernel's ops carry the name of its jitted wrapper), over all
    devices, clipped to [lo, hi]; and the ops themselves."""
    total, picked = 0.0, []
    for ops in trace.devices.values():
        for o in ops:
            if o.family in families and o.end_ns > lo and o.start_ns < hi:
                total += min(o.end_ns, hi) - max(o.start_ns, lo)
                picked.append(o)
    return total, picked


def exposed_collective_ns(ops: Sequence[Op], lo: float, hi: float) -> float:
    """Time in which a collective runs on this device and no other op does:
    the part of the exchange that compute does not hide."""
    coll = [(o.start_ns, o.end_ns) for o in ops
            if o.family.startswith(COLLECTIVE_PREFIXES)]
    other = [(o.start_ns, o.end_ns) for o in ops
             if not o.family.startswith(COLLECTIVE_PREFIXES)]
    c = clip(union(coll), lo, hi)
    covered = union(other)
    exposed = 0.0
    for s, e in c:
        part = e - s
        for a, b in covered:
            if b <= s:
                continue
            if a >= e:
                break
            part -= min(b, e) - max(a, s)
        exposed += part
    return exposed


def idle_gaps(ops: Sequence[Op], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Intervals within [lo, hi] in which no op ran."""
    gaps, t = [], lo
    for s, e in clip(union((o.start_ns, o.end_ns) for o in ops), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def attribute_gaps(trace: Trace, lo: float, hi: float, top: int = 10
                   ) -> List[Tuple[str, float]]:
    """Idle seconds of the first device, summed by the innermost span, the
    harness's or the program's, that covers each gap's midpoint (``host``
    where none does); the ``top`` largest."""
    ops = next(iter(trace.devices.values()), [])
    by: Dict[str, float] = defaultdict(float)
    for gs, ge in idle_gaps(ops, lo, hi):
        mid = 0.5 * (gs + ge)
        cover = [s for s in trace.spans if s.start_ns <= mid <= s.end_ns]
        name = min(cover, key=lambda s: s.dur_ns).name if cover else "host"
        by[name] += (ge - gs) * 1e-9
    return sorted(by.items(), key=lambda kv: -kv[1])[:top]


def self_ns(ops: Sequence[Op], lo: float, hi: float) -> List[Tuple[Op, float]]:
    """Each op that overlaps [lo, hi], with its self time there: the part of
    its clipped interval that no op nested in it covers. On the ``XLA Ops``
    line a ``while``, ``conditional`` or ``call`` encloses the ops of its
    body, which would otherwise be counted twice; a leaf's self time is its
    clipped duration. Over ops that nest or follow one another the self
    times sum to :func:`busy_ns`."""
    out: List[List[Any]] = []  # [op, self ns]
    stack: List[List[Any]] = []  # enclosing ops: [end, out entry or None, covered until]
    for o in sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns)):
        while stack and o.start_ns >= stack[-1][0]:
            stack.pop()
        s, e = max(o.start_ns, lo), min(o.end_ns, hi)
        if stack and stack[-1][1] is not None:  # nested: off the parent's self time
            parent = stack[-1]
            a, z = max(s, parent[2]), min(e, parent[0])
            if z > a:
                parent[1][1] -= z - a
                parent[2] = z
        entry = [o, e - s] if o.end_ns > lo and o.start_ns < hi else None
        if entry is not None:
            out.append(entry)
        stack.append([o.end_ns, entry, s])
    return [(o, t) for o, t in out]


def top_ops(trace: Trace, lo: float, hi: float, top: int = 10
            ) -> List[Tuple[str, float]]:
    """Device self seconds by op family, averaged over devices; the ``top``
    largest."""
    by: Dict[str, float] = defaultdict(float)
    n = max(1, sum(1 for ops in trace.devices.values() if ops))
    for ops in trace.devices.values():
        for o, t in self_ns(ops, lo, hi):
            by[o.family] += t * 1e-9 / n
    return sorted(by.items(), key=lambda kv: -kv[1])[:top]


STEP_MODULE = "jit(step)"  # the op-name root of the trainer's jitted state step
PHASES = ("forward", "backward", "remat_recompute", "optimizer_update")


def phase(op: Op) -> str:
    """The train-step phase of a device op, read from its op-name path.
    Under ``jax.value_and_grad`` the forward pass is traced under ``jvp(``,
    the backward pass under ``transpose(`` (which holds ``jvp(``), and what
    ``jax.checkpoint`` recomputes for the backward pass under
    ``rematted_computation``; the rest of the step's module is the
    optimizer update (AdamW, the gradient norm and clip, the anomaly guard,
    the schedule, the RNG split). Ops of other modules, and ops with no
    path (async copy halves, buffer allocations), are ``other``."""
    path = op.scope
    if not (path == STEP_MODULE or path.startswith(STEP_MODULE + "/")):
        return "other"
    if "rematted_computation" in path:
        return "remat_recompute"
    if "transpose(" in path:
        return "backward"
    if "jvp(" in path:
        return "forward"
    return "optimizer_update"


def phase_family_ns(trace: Trace, lo: float, hi: float) -> Dict[str, Dict[str, float]]:
    """Device self time within [lo, hi] by :func:`phase` (every phase and
    ``other``) and op family, averaged over the devices that ran anything."""
    by: Dict[str, Dict[str, float]] = {p: defaultdict(float) for p in PHASES + ("other",)}
    n = max(1, sum(1 for ops in trace.devices.values() if ops))
    for ops in trace.devices.values():
        for o, t in self_ns(ops, lo, hi):
            by[phase(o)][o.family] += t / n
    return by


def span_window(trace: Trace, name: str) -> Optional[Tuple[float, float]]:
    """[start, end] of the first span called ``name``."""
    for s in trace.spans:
        if s.name == name:
            return s.start_ns, s.end_ns
    return None

"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

What is read, and from where:

* device ops: the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane. An
  event's name is the HLO instruction's text (``%grouped_gemm.46 = bf16[..]
  custom-call(...)``); the op's name is the part between ``%`` and `` =``.
  The ``Async XLA Ops`` line is left out: an async copy's span covers the
  compute it overlaps and would count it as busy twice.
* host spans: events named ``bench.*`` on any ``/host:CPU`` line, which are
  the harness's own ``jax.profiler.TraceAnnotation`` spans around its calls
  into the program.

Device and host events share one clock in the file, so a device gap can be
laid against what the host was doing in it.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

_OP_NAME = re.compile(r"^%?([A-Za-z_][\w.-]*?) = ")
_SUFFIX = re.compile(r"\.\d+$")
# collectives, as XLA names their ops (start/done halves of async ones too)
COLLECTIVE_PREFIXES = ("all-to-all", "all-reduce", "all-gather",
                       "reduce-scatter", "collective-permute")


@dataclasses.dataclass
class Op:
    name: str  # HLO op name, e.g. grouped_gemm.46
    text: str  # the full instruction text the profiler recorded
    start_ns: float
    dur_ns: float

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
    spans: List[Span]  # harness spans, sorted by start

    def window(self) -> Tuple[float, float]:
        """First op start to last op end over all devices (ns)."""
        starts = [o.start_ns for ops in self.devices.values() for o in ops[:1]]
        ends = [max(o.end_ns for o in ops) for ops in self.devices.values() if ops]
        return (min(starts), max(ends)) if starts else (0.0, 0.0)


def op_name(text: str) -> str:
    m = _OP_NAME.match(text)
    return m.group(1) if m else text.split(" ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    ops.append(Op(op_name(e.name), e.name, float(e.start_ns),
                                  float(e.duration_ns)))
            ops.sort(key=lambda o: o.start_ns)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append(Span(e.name, float(e.start_ns),
                                          float(e.duration_ns)))
    spans.sort(key=lambda s: s.start_ns)
    return Trace(devices, spans)


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
    """Idle seconds of the first device, summed by the innermost harness
    span that covers each gap's midpoint (``host`` where none does); the
    ``top`` largest."""
    ops = next(iter(trace.devices.values()), [])
    by: Dict[str, float] = defaultdict(float)
    for gs, ge in idle_gaps(ops, lo, hi):
        mid = 0.5 * (gs + ge)
        cover = [s for s in trace.spans if s.start_ns <= mid <= s.end_ns]
        name = min(cover, key=lambda s: s.dur_ns).name if cover else "host"
        by[name] += (ge - gs) * 1e-9
    return sorted(by.items(), key=lambda kv: -kv[1])[:top]


def top_ops(trace: Trace, lo: float, hi: float, top: int = 10
            ) -> List[Tuple[str, float]]:
    """Device seconds by op family, averaged over devices; the ``top``
    largest."""
    by: Dict[str, float] = defaultdict(float)
    n = max(1, sum(1 for ops in trace.devices.values() if ops))
    for ops in trace.devices.values():
        for o in ops:
            if o.end_ns > lo and o.start_ns < hi:
                by[o.family] += (min(o.end_ns, hi) - max(o.start_ns, lo)) * 1e-9 / n
    return sorted(by.items(), key=lambda kv: -kv[1])[:top]


def span_window(trace: Trace, name: str) -> Optional[Tuple[float, float]]:
    """[start, end] of the first harness span called ``name``."""
    for s in trace.spans:
        if s.name == name:
            return s.start_ns, s.end_ns
    return None

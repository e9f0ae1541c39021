"""The serving programs in a profiler trace: which device ops ran in which
run of which compiled program.

``trace.py`` reads the ``XLA Ops`` line; this reads the ``XLA Modules``
line of the same device planes, where each run of a compiled program is
one event named ``<jit name>(<fingerprint>)``, and assigns each op to the
run whose interval holds its start. The engine's
prefill-chunk and decode programs are found by how often they ran against
what the scheduler planned in the window (``programs``), so a jit or a
kernel that a later change names otherwise still reads.
"""
from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

from . import trace

DECODE_KERNEL = "paged_attention"  # breaks a tie of run counts only


@dataclasses.dataclass
class ModuleRun:
    name: str  # jit name with its fingerprint, e.g. jit__lambda(1143...)
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_modules(path: str) -> Dict[str, List[ModuleRun]]:
    """Each device plane's program runs (its ``XLA Modules`` line), sorted
    by start; times as ``trace.load`` reads them."""
    with open(path, "rb") as f:
        b = f.read()
    out: Dict[str, List[ModuleRun]] = {}
    for field, plane in trace._fields(b, 0, len(b)):
        if field != 1:
            continue
        name, lines, metadata, _ = trace._plane(b, plane)
        if not name.startswith("/device:TPU:"):
            continue
        names = {mid: trace._name(b, v) for mid, v in metadata.items()}
        runs = []
        for line_name, ts, events in trace._lines(b, lines):
            if line_name == "XLA Modules":
                runs += [ModuleRun(names.get(mid, ""), s, d)
                         for mid, s, d in trace._events(b, events, ts)]
        out[name] = sorted(runs, key=lambda r: r.start_ns)
    return out


def ops_by_run(ops: Sequence[trace.Op], runs: Sequence[ModuleRun], lo: float, hi: float
               ) -> List[Tuple[ModuleRun, float, Dict[str, float]]]:
    """Each run that starts within [lo, hi], with the self time of the ops
    that start inside it and their self ns by op family."""
    inside = [r for r in runs if lo <= r.start_ns < hi]
    starts = [r.start_ns for r in inside]
    acc = [(r, [0.0], defaultdict(float)) for r in inside]
    for o, t in trace.self_ns(ops, lo, hi):
        i = bisect.bisect_right(starts, o.start_ns) - 1
        if i >= 0 and o.start_ns < inside[i].end_ns:
            acc[i][1][0] += t
            acc[i][2][o.family] += t
    return [(r, tot[0], fams) for r, tot, fams in acc]


def programs(tr: trace.Trace, modules: Dict[str, List[ModuleRun]], lo: float, hi: float,
             decodes: int, chunk_calls: Sequence[int]) -> Dict[str, Dict[str, object]]:
    """The engine's two programs in [lo, hi] of the first device, told
    apart by how often they ran, not by name: ``{"decode": ...,
    "prefill": ...}``, each with ``runs`` (count), ``self_ns`` (summed op
    self time) and ``families`` (self ns by op family). The decode program
    is the program with the most self time among those that ran once per
    decode step the scheduler planned in the window (``decodes``); the
    prefill-chunk program the one with the most among the others that ran
    as often as one of ``chunk_calls`` (once per chunk, or once per step
    that ran chunks). Where two programs ran as often, the one holding the
    decode kernel is decode. A program that the window ran and the trace
    does not show raises: a metric that goes silent hides a change."""
    plane = next(iter(tr.devices), None)
    by: Dict[str, Dict[str, object]] = {}
    for run, t, fams in ops_by_run(tr.devices.get(plane, []), modules.get(plane, []), lo, hi):
        p = by.setdefault(run.name, {"runs": 0, "self_ns": 0.0,
                                     "families": defaultdict(float)})
        p["runs"] += 1
        p["self_ns"] += t
        for f, ns in fams.items():
            p["families"][f] += ns
    rank = lambda n: (DECODE_KERNEL in by[n]["families"], by[n]["self_ns"])
    out: Dict[str, Dict[str, object]] = {}
    found = sorted((n for n, p in by.items() if p["runs"] == decodes), key=rank)
    if decodes:
        if not found:
            raise RuntimeError(f"no program in the trace ran once per decode step "
                               f"({decodes}); programs and runs: "
                               f"{ {n: p['runs'] for n, p in by.items()} }")
        out["decode"] = by[found[-1]]
    others = sorted((n for n, p in by.items() if p["runs"] in chunk_calls
                     and by[n] is not out.get("decode")), key=lambda n: by[n]["self_ns"])
    if max(chunk_calls, default=0):
        if not others:
            raise RuntimeError(f"no program in the trace ran once per prefill chunk or "
                               f"chunk step ({tuple(chunk_calls)}); programs and runs: "
                               f"{ {n: p['runs'] for n, p in by.items()} }")
        out["prefill"] = by[others[-1]]
    return out

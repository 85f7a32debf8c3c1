"""Reduce a profiler trace (``.xplane.pb``) to busy time, top device
operations and idle gaps labelled by what the host was doing.

- Device planes are those named ``/device:<accelerator>:<n>``; their
  operations are the events of the ``XLA Ops`` line (``XLA Modules``
  where a plane has no op line).
- The slice measured is the span of the harness's ``bench.step``
  annotations: from the start of the second step (the first runs while
  the profiler is still starting) to the end of the last.
- Busy time is the union of the operation intervals of a device inside
  the slice, averaged over the device planes.
- An operation's time is its self time (its duration less that of the
  operations nested in it, such as a loop's body) inside the slice,
  summed over its executions.  It is named ``<program>/<instruction>
  <opcode> <result type>`` from the trace's HLO text, the program being
  the enclosing ``XLA Modules`` event without its hash.
- An idle gap is a stretch of the slice in which no operation ran on the
  first device; it is labelled by the harness annotation (``bench.*``)
  that overlaps it most on the host, or ``host`` where none does.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, List, Tuple

Interval = Tuple[float, float]
SPAN_PREFIX = "bench."
STEP_SPAN = "bench.step"
TOP = 10


def _union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[^\]]*\])?.*?\s"
                  r"([a-z][\w\-]*)\(")


def op_label(name: str, module: str) -> str:
    """Short name of an ``XLA Ops`` event from its HLO text."""
    m = _HLO.match(name)
    if not m:
        return f"{module}/{name[:60]}"
    inst, out, opcode = m.groups()
    return f"{module}/{inst} {opcode} {out or ''}".rstrip()


def _modules(plane) -> Tuple[List[float], List[Tuple[float, str]]]:
    lines = {ln.name: ln for ln in plane.lines}
    mods = sorted((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                   e.name.split("(")[0])
                  for e in (lines["XLA Modules"].events
                            if "XLA Modules" in lines else ()))
    return [m[0] for m in mods], [(m[1], m[2]) for m in mods]


def _module_at(index, t: float) -> str:
    starts, rest = index
    i = bisect.bisect_right(starts, t) - 1
    return rest[i][1] if i >= 0 and t <= rest[i][0] else "?"


def _self_times(events, lo: float, hi: float):
    """(start, end, self seconds inside [lo, hi]) of each event, where
    events nested in another count against it."""
    evs = sorted(((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                   e.name) for e in events), key=lambda x: (x[0], -x[1]))
    out, stack = [], []
    for a, b, name in evs:
        while stack and stack[-1][1] <= a:
            stack.pop()
        mine = max(0.0, min(b, hi) - max(a, lo))
        rec = [a, b, name, mine]
        if stack:
            stack[-1][3] -= mine
        stack.append(rec)
        out.append(rec)
    return out


def _device_planes(pd) -> List:
    return [p for p in pd.planes if p.name.startswith("/device:")
            and not p.name.startswith("/device:CUSTOM")]


def _op_events(plane) -> List:
    lines = {ln.name: ln for ln in plane.lines}
    line = lines.get("XLA Ops") or lines.get("XLA Modules")
    return list(line.events) if line is not None else []


def _host_spans(pd) -> List[Tuple[str, float, float]]:
    out = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append((e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9))
    return out


def reduce(pd) -> Dict:
    """``pd``: a ``jax.profiler.ProfileData``.  Returns ``busy_s``,
    ``window_s`` and the ``device_ops`` / ``idle_gaps`` breakdown lists of
    ``[name, seconds]``; ``window_s`` is 0 where the trace holds no step."""
    spans = _host_spans(pd)
    steps = sorted((a, b) for n, a, b in spans if n == STEP_SPAN)[1:]
    planes = [p for p in _device_planes(pd) if _op_events(p)]
    if not steps or not planes:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": []}
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    busy_by_plane, op_time = [], collections.Counter()
    first_busy: List[Interval] = []
    for i, p in enumerate(planes):
        index = _modules(p)
        iv = []
        for a, b, name, mine in _self_times(_op_events(p), lo, hi):
            if b > lo and a < hi:
                iv.append((a, b))
                if mine > 0:
                    op_time[op_label(name, _module_at(index, a))] += mine
        merged = _union(_clip(iv, lo, hi))
        busy_by_plane.append(sum(b - a for a, b in merged))
        if i == 0:
            first_busy = merged
    gaps, t = [], lo
    for a, b in first_busy + [(hi, hi)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    labelled = []
    for a, b in gaps:
        best, label = 0.0, "host"
        for n, sa, sb in spans:
            ov = min(b, sb) - max(a, sa)
            if ov > best:
                best, label = ov, n
        labelled.append([label, b - a])
    labelled.sort(key=lambda x: -x[1])
    return {
        "busy_s": sum(busy_by_plane) / len(busy_by_plane),
        "window_s": hi - lo,
        "device_ops": [[n, t / len(planes)]
                       for n, t in op_time.most_common(TOP)],
        "idle_gaps": labelled[:TOP],
    }


def reduce_file(path: str) -> Dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(str(path)))

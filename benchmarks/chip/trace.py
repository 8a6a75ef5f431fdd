"""Reduction of a profiler trace (``.xplane.pb``) to per-chip intervals.

The benchmark's own host spans (``jax.profiler.TraceAnnotation``) frame
the measured window (``window``) and label what the host was doing
(``data``, ``dispatch``, ``loss_fetch``, ``sync_wait``). Device planes
(``/device:TPU:<n>``) carry one event per executed XLA operation on their
``XLA Ops`` line. From those this module computes, per chip and clipped
to the window:

- busy time: the union of all operation intervals;
- collective time: the union of the intervals of collective operations
  (all-reduce, all-gather, reduce-scatter, all-to-all, collective-permute,
  send and recv, with their async ``-start``/``-done`` halves), and of
  the spans in which an async collective is in flight (the ``Async XLA
  Ops`` line);
- exposed collective time: the part of the collective time in which no
  other operation runs on that chip;
- idle time: the stretches of the window in which no operation runs,
  split by the host span that covers each part (``other`` where none
  does);
- self time per operation name, where an event that encloses others (a
  loop, a call) keeps only the part its children do not cover.

Times are in nanoseconds, as the trace gives them.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

HOST_SPANS = ("data", "dispatch", "loss_fetch", "sync_wait")
WINDOW_SPAN = "window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_ASYNC_LINE = "Async XLA Ops"
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "ragged-all-to-all", "collective-permute",
                "collective-broadcast", "send", "recv")
_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")


def opcode(name: str) -> str:
    """The HLO opcode of a trace event named by its instruction text
    (``%fusion.3 = bf16[4,8]{1,0} fusion(...)``); a bare name (``all-
    reduce.3``) gives its stem."""
    head, sep, rest = name.partition(" = ")
    if sep:
        m = _OPCODE.search(" " + rest)
        if m:
            return m.group(1)
    return re.sub(r"\.\d+$", "", head.lstrip("%"))


def short_name(name: str) -> str:
    """``%fusion.3 = bf16[4,8]{1,0} fusion(...)`` -> ``fusion.3 bf16[4,8]``:
    the instruction's name and the shape of its first result."""
    head, sep, rest = name.partition(" = ")
    head = head.lstrip("%")
    if not sep:
        return head
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return f"{head} {shape.group(1)}" if shape else head


def is_collective(name: str) -> bool:
    op = opcode(name)
    for c in _COLLECTIVES:
        if op == c or op.startswith(c + "-start") or op.startswith(
                c + "-done"):
            return True
    return False


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) intervals ``a`` that the disjoint,
    sorted intervals ``b`` do not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Self time per name: an event's duration less what the events that
    start inside it cover of it (events on one line nest or follow one
    another; one that outlasts the event it starts in counts against it
    only up to that event's end)."""
    evs = sorted(events, key=lambda t: (t[1], -(t[2] - t[1])))
    totals: Dict[str, float] = {}
    stack: List[List] = []   # [name, start, end, covered-by-children]

    def close(frame):
        name, s, e, child = frame
        totals[name] = totals.get(name, 0.0) + (e - s) - child
        if stack:
            parent = stack[-1]
            parent[3] += max(0.0, min(e, parent[2]) - max(s, parent[1]))

    for name, s, e in evs:
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return totals


@dataclasses.dataclass
class Chip:
    device: int
    busy: float
    coll: float
    coll_exposed: float
    ops: Dict[str, float]
    idle: Dict[str, float]         # idle time in the window, by host span


@dataclasses.dataclass
class Summary:
    window: Interval
    chips: List[Chip]
    steps: int                     # ``dispatch`` spans inside the window

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(plane):
    for line in plane.lines:
        for ev in line.events:
            yield line.name, ev.name, float(ev.start_ns), float(ev.end_ns)


def host_spans(profile, names: Iterable[str] = HOST_SPANS + (WINDOW_SPAN,)
               ) -> Dict[str, List[Interval]]:
    wanted = set(names)
    out: Dict[str, List[Interval]] = {n: [] for n in wanted}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for _, name, s, e in _events(plane):
            if name in wanted:
                out[name].append((s, e))
    return out


def device_ops(profile) -> Dict[int, Tuple[List, List]]:
    """Per chip: (operations as executed, async operations in flight
    from their start to their done), each as (name, start, end)."""
    out: Dict[int, Tuple[List, List]] = {}
    for plane in profile.planes:
        m = _DEVICE.match(plane.name)
        if not m:
            continue
        ops, flights = [], []
        for line, name, s, e in _events(plane):
            if line == _OPS_LINE:
                ops.append((name, s, e))
            elif line == _ASYNC_LINE:
                flights.append((name, s, e))
        out[int(m.group(1))] = (ops, flights)
    return out


def _attribute(gap: Interval, spans: Dict[str, List[Interval]],
               into: Dict[str, float]) -> None:
    """Split an idle gap over the host spans (each a disjoint union) that
    cover its parts; the part no span covers is ``other``."""
    left = gap[1] - gap[0]
    for name in HOST_SPANS:
        got = length(clip(spans[name], gap[0], gap[1]))
        if got > 0:
            into[name] = into.get(name, 0.0) + got
            left -= got
    if left > 0:
        into["other"] = into.get("other", 0.0) + left


def summarize(profile) -> Optional[Summary]:
    """Per-chip readings over the benchmark's ``window`` span, or None
    where the trace has no window span or no device plane."""
    spans = host_spans(profile)
    if not spans[WINDOW_SPAN]:
        return None
    lo = min(s for s, _ in spans[WINDOW_SPAN])
    hi = max(e for _, e in spans[WINDOW_SPAN])
    per_dev = device_ops(profile)
    if not per_dev:
        return None
    labels = {n: union(spans[n]) for n in HOST_SPANS}
    chips = []
    inside = lambda evs: [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                          if min(e, hi) > max(s, lo)]
    for dev in sorted(per_dev):
        evs, flights = (inside(x) for x in per_dev[dev])
        busy = union((s, e) for _, s, e in evs)
        coll = union([(s, e) for n, s, e in evs + flights
                      if is_collective(n)])
        other = union((s, e) for n, s, e in evs if not is_collective(n))
        exposed = subtract(coll, other)
        idle: Dict[str, float] = {}
        for g in gaps(busy, lo, hi):
            _attribute(g, labels, idle)
        chips.append(Chip(dev, length(busy), length(coll), length(exposed),
                          self_times([(short_name(n), s, e)
                                      for n, s, e in evs]), idle))
    steps = sum(1 for s, e in spans["dispatch"] if s >= lo and e <= hi)
    return Summary((lo, hi), chips, steps)


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def breakdown(summary: Summary, top: int = 10) -> Dict[str, list]:
    """The device operations with the most self time and the idle time by
    host span, in seconds averaged over the chips, largest first."""
    n = len(summary.chips)
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for c in summary.chips:
        for k, v in c.ops.items():
            ops[k] = ops.get(k, 0.0) + v / n
        for k, v in c.idle.items():
            idle[k] = idle.get(k, 0.0) + v / n
    pick = lambda d: [[k, v * 1e-9] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": pick(ops), "idle_gaps": pick(idle)}

"""Reduction of a profiler trace to device busy time, idle gaps and the
heaviest device operations.

``load`` reads an ``.xplane.pb`` with JAX's own ``ProfileData`` into
plain ``(name, start_ns, dur_ns)`` tuples: the operations on each
device plane's "XLA Ops" line (its "XLA Modules" line where it has no
op line), named by their HLO instruction name alone, and the host spans
(TraceAnnotation events) named in ``span_names``.  ``reduce`` works on
those tuples only, so a test can hand it a synthesized trace.

An op line nests: a ``while`` spans the operations of its body.  The
heaviest operations are ranked by their own time, less that of the
operations nested inside them.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"        # read where a plane has no op line
NO_SPAN = "outside_spans"
TOP = 10


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str, span_names: Iterable[str]
         ) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """-> ({device plane: ops}, host spans)."""
    from jax.profiler import ProfileData
    wanted = set(span_names)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            line = lines.get(OPS_LINE, lines.get(MODULES_LINE))
            if line is not None:
                devices[plane.name] = [(op_name(e.name), e.start_ns,
                                        e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events if e.name in wanted)
    return devices, spans


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Time of each op name less the time of the ops nested inside
    it, from ``(name, start, end)`` intervals of one device."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[float, str]] = []           # (end, name)
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack and b <= stack[-1][0]:
            out[stack[-1][1]] -= b - a
        out[name] += b - a
        stack.append((b, name))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Merged, sorted ``[start, end]`` intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(events: Sequence[Event], lo: float, hi: float):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def _label(spans: Sequence[Event], t: float) -> str:
    """The innermost span open at time ``t``."""
    open_ = [(d, name) for name, s, d in spans if s <= t <= s + d]
    return min(open_)[1] if open_ else NO_SPAN


def reduce(devices: Dict[str, List[Event]], spans: Sequence[Event],
           window: Tuple[float, float]) -> Optional[dict]:
    """Busy and idle time of the devices inside ``window`` (ns), the
    longest idle gaps labelled by the host span open in their middle,
    and the operations that took most time.  ``None`` when no operation
    ran on a device in the window."""
    lo, hi = window
    busy, op_time = [], defaultdict(float)
    gaps: List[Tuple[float, str]] = []
    for ops in devices.values():
        clipped = list(_clip(ops, lo, hi))
        if not clipped:
            continue
        for name, t in self_times(clipped).items():
            op_time[name] += t
        merged = union((a, b) for _, a, b in clipped)
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _label(spans, (a + b) / 2)))
    if not busy:
        return None
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / len(busy) / 1e9
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: -g[0])
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_frac": 1.0 - busy_s / window_s,
        "device_ops": [[n, t / 1e9] for n, t in top_ops],
        "idle_gaps": [[label, t / 1e9] for t, label in gaps[:TOP]],
    }


def window_of(spans: Sequence[Event], name: str) -> Tuple[float, float]:
    for n, s, d in spans:
        if n == name:
            return s, s + d
    raise ValueError(f"no span {name!r} in the trace")
